"""Clock-vector folding + parallel restore lanes (ISSUE 17,
docs/STORAGE.md): `amtpu_fold_clocks` folds settled per-change
`all_deps` clock vectors into the densified per-doc table, and every
causal query -- straggler backfill, `get_missing_changes` /
`get_changes_for_actor`, missing-clock frames, undo/redo -- must answer
byte-identically to an unfolded (`AMTPU_STORAGE_FOLD_CLOCKS=0`) twin,
across both exec modes, `ShardedNativePool`, and the dp=4 mesh.  Plus
the `restore_from_store` parallel cold start: summary accounting,
`storage.restore.*` counters, and the corrupt-blob quarantine."""

import os

import pytest

from automerge_tpu import telemetry
from automerge_tpu.native import NativeDocPool, ShardedNativePool
from automerge_tpu.storage.coldstore import ColdStore, ColdStoreCorrupt

ROOT = '00000000-0000-0000-0000-000000000000'


@pytest.fixture(autouse=True)
def _reset():
    telemetry.reset_all()
    yield
    telemetry.reset_all()


@pytest.fixture
def fold_env():
    """Set/unset AMTPU_STORAGE_FOLD_CLOCKS per arm (checked per call,
    so flipping the env interleaves cleanly)."""
    prior = os.environ.get('AMTPU_STORAGE_FOLD_CLOCKS')

    def arm(folded):
        os.environ['AMTPU_STORAGE_FOLD_CLOCKS'] = '1' if folded else '0'
    yield arm
    if prior is None:
        os.environ.pop('AMTPU_STORAGE_FOLD_CLOCKS', None)
    else:
        os.environ['AMTPU_STORAGE_FOLD_CLOCKS'] = prior


def _history(doc_idx, rounds=6, actors=3):
    """Interleaved multi-actor history with catch-up deps -- the shape
    whose all_deps vectors grow O(history) without folding."""
    chs = []
    clock = {}
    for r in range(rounds):
        actor = 'a%d' % ((doc_idx + r) % actors)
        clock[actor] = clock.get(actor, 0) + 1
        chs.append({'actor': actor, 'seq': clock[actor],
                    'deps': {a: s for a, s in clock.items()
                             if a != actor},
                    'ops': [{'action': 'set', 'obj': ROOT,
                             'key': 'k%d' % (r % 4),
                             'value': doc_idx * 100 + r}]})
    return chs


def _build_twins(fold_env, make_folded, make_unfolded, n_docs=12,
                 rounds=6, compact=True):
    """Identical corpora into a folded and an unfolded pool; compaction
    drives `_fold_settled` + `_fold_clocks` on the folded arm only."""
    pools = []
    for folded, make in ((True, make_folded), (False, make_unfolded)):
        fold_env(folded)
        pool = make()
        pool.apply_batch({('doc%02d' % d): _history(d, rounds)
                          for d in range(n_docs)})
        if compact:
            for d in range(n_docs):
                pool.compact('doc%02d' % d)
        pools.append(pool)
    return pools


def test_fold_frees_pairs_and_acct_reconciles(fold_env, pool_lane):
    folded, unfolded = _build_twins(fold_env, NativeDocPool,
                                    NativeDocPool)
    _ids, fstats = folded.doc_stats()
    _ids, ustats = unfolded.doc_stats()
    # the folded arm's clock memory (sparse pairs + fold table) is
    # strictly below the unfolded arm's sparse pairs
    fold_mem = int((fstats[:, 6] * 8 + fstats[:, 7]).sum())
    unfold_mem = int((ustats[:, 6] * 8 + ustats[:, 7]).sum())
    assert fold_mem < unfold_mem
    assert int(fstats[:, 7].sum()) > 0          # fold table engaged
    # acct column == fresh-walk oracle, both arms
    assert int(fstats[:, 6].sum()) == folded.clock_pairs()
    assert int(ustats[:, 6].sum()) == unfolded.clock_pairs()
    assert telemetry.metrics_snapshot().get(
        'storage.gc.clocks_folded', 0) > 0
    # the unfolded arm must not have folded anything
    assert int(ustats[:, 7].sum()) == 0


def test_causal_queries_parity(fold_env, pool_lane):
    folded, unfolded = _build_twins(fold_env, NativeDocPool,
                                    NativeDocPool)
    for d in range(12):
        doc = 'doc%02d' % d
        assert folded.save(doc) == unfolded.save(doc)
        assert folded.get_patch(doc) == unfolded.get_patch(doc)
        # missing-clock frames byte-identical at multiple clocks
        for have in ({}, {'a0': 1}, {'a0': 2, 'a1': 1},
                     {'a0': 99, 'a1': 99, 'a2': 99}):
            assert folded._missing_clock(doc, have) \
                == unfolded._missing_clock(doc, have)
            assert folded.get_missing_changes(doc, have) \
                == unfolded.get_missing_changes(doc, have)
        # straggler backfill per actor
        for actor in ('a0', 'a1', 'a2'):
            for after in (0, 1):
                assert folded.get_changes_for_actor(doc, actor, after) \
                    == unfolded.get_changes_for_actor(doc, actor, after)


def test_fold_then_more_history_parity(fold_env, pool_lane):
    """Changes applied AFTER a fold must seed their deps through the
    folded rows (update_states reads all_deps via the fold table) --
    the drift the ISSUE forbids."""
    folded, unfolded = _build_twins(fold_env, NativeDocPool,
                                    NativeDocPool)
    for arm, pool in ((True, folded), (False, unfolded)):
        for r in range(4):
            pool.apply_batch({('doc%02d' % d): [
                {'actor': 'a0', 'seq': 7 + r, 'deps': {'a1': 2, 'a2': 2}
                 if r == 0 else {}, 'ops': [
                     {'action': 'set', 'obj': ROOT, 'key': 'late',
                      'value': r}]}] for d in range(12)})
    for d in range(12):
        doc = 'doc%02d' % d
        assert folded.save(doc) == unfolded.save(doc)
        assert folded.get_patch(doc) == unfolded.get_patch(doc)
        assert folded.get_missing_changes(doc, {'a0': 6}) \
            == unfolded.get_missing_changes(doc, {'a0': 6})


def test_undo_redo_parity_at_multiple_clocks(fold_env):
    """Undo/redo through apply_local_change against the unfolded twin,
    folding between rounds on the folded arm only."""
    fold_env(True)
    folded = NativeDocPool()
    fold_env(False)
    unfolded = NativeDocPool()
    for r in range(5):
        req = {'requestType': 'change', 'actor': 'u1', 'seq': r + 1,
               'deps': {}, 'ops': [{'action': 'set', 'obj': ROOT,
                                    'key': 'k%d' % (r % 2),
                                    'value': r}]}
        fold_env(True)
        pf = folded.apply_local_change('u', dict(req))
        folded.compact('u')
        fold_env(False)
        pu = unfolded.apply_local_change('u', dict(req))
        unfolded.compact('u')
        assert pf == pu
    seq = 6
    for kind in ('undo', 'undo', 'redo', 'undo', 'redo', 'redo'):
        req = {'requestType': kind, 'actor': 'u1', 'seq': seq,
               'deps': {}}
        seq += 1
        fold_env(True)
        pf = folded.apply_local_change('u', dict(req))
        folded.compact('u')
        fold_env(False)
        pu = unfolded.apply_local_change('u', dict(req))
        unfolded.compact('u')
        assert pf == pu
    assert folded.get_patch('u') == unfolded.get_patch('u')
    assert folded.save('u') == unfolded.save('u')


def test_sharded_and_mesh_parity(fold_env):
    """ShardedNativePool + the dp=4 mesh with folding on answer
    byte-identically to a flat unfolded NativeDocPool."""
    from automerge_tpu.native.mesh_pool import MeshDocPool
    for make in (lambda: ShardedNativePool(4),
                 lambda: MeshDocPool(dp=4)):
        telemetry.reset_all()
        folded, unfolded = _build_twins(fold_env, make, NativeDocPool)
        for d in range(12):
            doc = 'doc%02d' % d
            assert folded.save(doc) == unfolded.save(doc)
            assert folded.get_patch(doc) == unfolded.get_patch(doc)
            assert folded.get_missing_changes(doc, {'a1': 1}) \
                == unfolded.get_missing_changes(doc, {'a1': 1})
        assert folded.clock_pairs() < unfolded.clock_pairs()


def test_fold_actor_population_cap(fold_env, monkeypatch):
    """Docs whose history spans more actors than
    AMTPU_FOLDCLK_MAX_ACTORS keep those entries sparse -- and still
    answer identically."""
    monkeypatch.setenv('AMTPU_FOLDCLK_MAX_ACTORS', '2')
    folded, unfolded = _build_twins(fold_env, NativeDocPool,
                                    NativeDocPool, n_docs=4, rounds=8)
    # 3 actors > cap 2: the wide entries stay sparse (pairs remain)
    assert folded.clock_pairs() > 0
    for d in range(4):
        doc = 'doc%02d' % d
        assert folded.save(doc) == unfolded.save(doc)
        assert folded.get_patch(doc) == unfolded.get_patch(doc)


def _store_with(blobs, tmp_path, durable=False):
    store = ColdStore(root=str(tmp_path / 'cold'), durable=durable)
    for d, b in blobs.items():
        store.put(d, bytes(b))
    return store


def _corpus(n_docs=24):
    pool = NativeDocPool()
    pool.apply_batch({('doc%02d' % d): _history(d)
                      for d in range(n_docs)})
    return pool, {('doc%02d' % d): pool.save('doc%02d' % d)
                  for d in range(n_docs)}


def test_restore_from_store_roundtrip(tmp_path):
    builder, blobs = _corpus()
    store = _store_with(blobs, tmp_path)
    for make in (NativeDocPool, lambda: ShardedNativePool(4)):
        telemetry.reset_all()
        pool = make()
        summary = pool.restore_from_store(store)
        assert summary['docs'] == len(blobs)
        assert summary['corrupt'] == {} and summary['failed'] == {}
        assert summary['bytes'] == sum(len(b) for b in blobs.values())
        for d in blobs:
            assert pool.save(d) == blobs[d]
        snap = telemetry.metrics_snapshot()
        assert snap.get('storage.restore.docs') == len(blobs)
        assert snap.get('storage.restore.batches', 0) >= 1
        assert snap.get('storage.restore.corrupt', 0) == 0


def test_restore_serial_and_batched(tmp_path, monkeypatch):
    builder, blobs = _corpus()
    store = _store_with(blobs, tmp_path)
    monkeypatch.setenv('AMTPU_RESTORE_THREADS', '1')
    monkeypatch.setenv('AMTPU_RESTORE_BATCH', '5')
    pool = ShardedNativePool(4)
    summary = pool.restore_from_store(store)
    assert summary['docs'] == len(blobs)
    # 24 docs over 4 shards at batch=5 -> every shard chunks
    assert summary['batches'] >= 4
    for d in blobs:
        assert pool.save(d) == blobs[d]


def test_restore_doc_ids_subset(tmp_path):
    builder, blobs = _corpus()
    store = _store_with(blobs, tmp_path)
    want = sorted(blobs)[:7]
    pool = NativeDocPool()
    summary = pool.restore_from_store(store, doc_ids=want)
    assert summary['docs'] == 7
    assert sorted(pool.doc_stats()[0]) == want


def test_restore_quarantines_corrupt_blob(tmp_path):
    """A checksum-failed blob (ISSUE 17 small fix) must skip that doc
    with a typed per-doc error + storage.restore.corrupt, not fail the
    pool restore."""
    builder, blobs = _corpus()
    store = _store_with(blobs, tmp_path, durable=True)
    victim = sorted(blobs)[3]
    path = store._index[victim][0]
    with open(path, 'r+b') as f:
        f.seek(0)
        f.write(b'\xde\xad\xbe\xef')
    # direct get raises the typed error (still a ValueError subclass)
    with pytest.raises(ColdStoreCorrupt):
        store.get(victim)
    assert isinstance(ColdStoreCorrupt('x', 'detail'), ValueError)
    pool = ShardedNativePool(4)
    summary = pool.restore_from_store(store)
    assert summary['docs'] == len(blobs) - 1
    assert list(summary['corrupt']) == [victim]
    assert summary['corrupt'][victim]['errorType'] == 'ColdStoreCorrupt'
    assert victim not in list(pool.doc_stats()[0])
    for d in blobs:
        if d != victim:
            assert pool.save(d) == blobs[d]
    snap = telemetry.metrics_snapshot()
    assert snap.get('storage.restore.corrupt') == 1
    assert snap.get('storage.restore.docs') == len(blobs) - 1


def test_restore_after_fold_roundtrip(fold_env, tmp_path):
    """Save -> fold -> save -> restore: blobs written after clock
    folding restore byte-identically (folding never leaks into the
    wire format)."""
    fold_env(True)
    pool = NativeDocPool()
    pool.apply_batch({('doc%02d' % d): _history(d) for d in range(8)})
    for d in range(8):
        pool.compact('doc%02d' % d)
    blobs = {('doc%02d' % d): pool.save('doc%02d' % d)
             for d in range(8)}
    store = _store_with(blobs, tmp_path)
    fresh = NativeDocPool()
    fresh.restore_from_store(store)
    for d in blobs:
        assert fresh.save(d) == blobs[d]
        assert fresh.get_patch(d) == pool.get_patch(d)
