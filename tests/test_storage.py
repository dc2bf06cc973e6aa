"""Columnar storage tier lanes (ISSUE 10, docs/STORAGE.md): codec fuzz
round-trip in both formats, settled-history GC with straggler backfill
parity, save -> evict -> reload -> mutate byte parity vs a never-
evicted twin (both exec modes), the gateway's LRU eviction +
reload-on-touch, the WAL byte bound, and per-connection fan-out frame
batching."""

import json
import random
import time

import msgpack
import pytest

from automerge_tpu import storage, telemetry
from automerge_tpu.native import NativeDocPool, ShardedNativePool
from automerge_tpu.storage.coldstore import ColdStore, DocEvictor
from automerge_tpu.storage.columnar import (decode_columnar,
                                            decode_columnar_meta,
                                            encode_columnar)

ROOT = '00000000-0000-0000-0000-000000000000'


@pytest.fixture(autouse=True)
def _reset_metrics():
    # reset_all, not metrics_reset: the gateway e2e lane observes
    # registry histograms (BATCH_OCCUPANCY) that would otherwise leak
    # into test_scheduler's exact-count assertions (same pattern as
    # tests/test_fanout.py)
    telemetry.reset_all()
    yield
    telemetry.reset_all()


def _rand_changes(rng, n_actors=4, n_rounds=6, with_weird=True):
    """A random mixed corpus: maps, text inserts, deletes, odd value
    types -- the fuzz lane's input."""
    changes = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
        {'action': 'makeText', 'obj': 'T'},
        {'action': 'link', 'obj': ROOT, 'key': 'text', 'value': 'T'}]}]
    seqs = {'a0': 1}
    prev, elem = '_head', 0
    for r in range(n_rounds):
        actor = 'a%d' % rng.randrange(n_actors)
        seqs.setdefault(actor, 0)
        seqs[actor] += 1
        ops = []
        for _ in range(rng.randrange(1, 6)):
            roll = rng.random()
            if roll < 0.4:
                elem += 1
                ops.append({'action': 'ins', 'obj': 'T', 'key': prev,
                            'elem': elem})
                ops.append({'action': 'set', 'obj': 'T',
                            'key': '%s:%d' % (actor, elem),
                            'value': chr(97 + elem % 26)})
                prev = '%s:%d' % (actor, elem)
            elif roll < 0.6:
                ops.append({'action': 'del', 'obj': ROOT,
                            'key': 'k%d' % rng.randrange(8)})
            else:
                vals = [rng.randrange(-1000, 1000), 'v%d' % r, True,
                        False, None]
                if with_weird:
                    vals += [rng.random(), {'nest': [1, 'x']},
                             [1, 2, 3]]
                ops.append({'action': 'set', 'obj': ROOT,
                            'key': 'k%d' % rng.randrange(8),
                            'value': rng.choice(vals)})
        deps = {a: s for a, s in seqs.items() if a != actor and s
                and rng.random() < 0.7}
        changes.append({'actor': actor, 'seq': seqs[actor],
                        'deps': deps, 'ops': ops})
    return changes


# ---------------------------------------------------------------------------
# codec round-trip lanes
# ---------------------------------------------------------------------------

class TestColumnarCodec(object):
    def test_fuzz_round_trip_byte_identical(self):
        rng = random.Random(11)
        for trial in range(20):
            changes = _rand_changes(rng, n_rounds=rng.randrange(1, 30))
            raws = [msgpack.packb(c, use_bin_type=True)
                    for c in changes]
            blob = encode_columnar(raws)
            assert decode_columnar(blob) == raws, 'trial %d' % trial
            # decode -> re-encode is stable (the fuzz lane's
            # byte-equality after decode->re-encode)
            assert decode_columnar(encode_columnar(
                decode_columnar(blob))) == raws

    def test_non_canonical_bytes_ride_the_residual_column(self):
        c = {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT, 'key': 'k', 'value': 5}]}
        raw = msgpack.packb(c, use_bin_type=True)
        # value 5 re-spelled as uint16: same object, different bytes --
        # a canonical re-encode would corrupt it, the residual column
        # must carry it verbatim
        bad = raw.replace(b'\x05', b'\xcd\x00\x05')
        assert msgpack.unpackb(bad, raw=False) == c
        raws = [raw, bad, raw]
        telemetry.metrics_reset()
        blob = encode_columnar(raws)
        assert decode_columnar(blob) == raws
        snap = telemetry.metrics_snapshot()
        assert snap['storage.columnar.residual_changes'] == 1
        # meta decode recovers actor/seq for residuals too
        assert [(a, s) for _r, a, s in decode_columnar_meta(blob)] == \
            [('a', 1)] * 3

    def test_compression_beats_json_on_structured_corpora(self):
        rng = random.Random(3)
        changes = _rand_changes(rng, n_rounds=200, with_weird=False)
        raws = [msgpack.packb(c, use_bin_type=True) for c in changes]
        blob = encode_columnar(raws)
        jbytes = len(json.dumps(changes, separators=(',', ':')))
        assert len(blob) * 5 <= jbytes, \
            'columnar %d vs json %d' % (len(blob), jbytes)

    def test_unicode_digit_keys_stay_encodable(self):
        # '\u00b2'.isdigit() is True but int('\u00b2') raises: the key
        # splitter must not crash on such a legal string key
        c = {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT, 'key': 'x:\u00b2',
             'value': 1},
            {'action': 'set', 'obj': ROOT, 'key': 'y:\u0663',
             'value': 2},
            {'action': 'set', 'obj': ROOT, 'key': 'z:007',
             'value': 3}]}
        raws = [msgpack.packb(c, use_bin_type=True)]
        assert decode_columnar(encode_columnar(raws)) == raws

    def test_corrupt_container_raises_the_typed_error(self):
        """A blob with a valid v2 prefix but garbage body keeps the
        RangeError contract on pool.load (never a raw zlib/IndexError)."""
        from automerge_tpu.errors import RangeError
        bad = storage.CKPT_V2_PREFIX + b'\xc4\x08garbage!'
        with pytest.raises((ValueError, RangeError)):
            storage.unpack_checkpoint(bad)
        pool = NativeDocPool()
        with pytest.raises(RangeError, match='checkpoint'):
            pool.load('d', bad)
        # corrupt columnar body inside a well-formed container
        blob = storage.pack_checkpoint(
            {'a': 1}, [b'AMTC\x01\x01not-zlib'],
            [msgpack.packb({'actor': 'a', 'seq': 1, 'deps': {},
                            'ops': []}, use_bin_type=True)])
        with pytest.raises(RangeError, match='checkpoint'):
            pool.load('d', blob)
        with pytest.raises(RangeError, match='checkpoint'):
            ShardedNativePool(n_shards=2).load('d', blob)

    def test_null_deps_or_ops_ride_the_residual_column(self):
        # explicit nulls are legal msgpack but not columnarizable: they
        # must fall to the residual column, not crash the encoder
        raws = [msgpack.packb({'actor': 'a', 'seq': 1, 'deps': None,
                               'ops': [{'action': 'set', 'obj': ROOT,
                                        'key': 'k', 'value': 1}]},
                              use_bin_type=True),
                msgpack.packb({'actor': 'a', 'seq': 2, 'deps': {},
                               'ops': None}, use_bin_type=True)]
        telemetry.metrics_reset()
        blob = encode_columnar(raws)
        assert decode_columnar(blob) == raws
        assert telemetry.metrics_snapshot()[
            'storage.columnar.residual_changes'] == 2

    def test_checkpoint_container_round_trip(self):
        rng = random.Random(5)
        raws = [msgpack.packb(c, use_bin_type=True)
                for c in _rand_changes(rng)]
        blob = storage.pack_checkpoint({'a0': 1}, [
            encode_columnar(raws[:2])], raws[2:])
        assert storage.is_checkpoint(blob)
        frontier, chunks, tail = storage.unpack_checkpoint(blob)
        assert frontier == {'a0': 1} and len(chunks) == 1
        assert tail == raws[2:]
        assert storage.checkpoint_raw_changes(blob) == raws
        v1 = storage.pack_checkpoint_v1(raws)
        assert storage.checkpoint_raw_changes(v1) == raws


# ---------------------------------------------------------------------------
# both-formats apply parity (the AMTPU_STORAGE_FORMAT oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('fmt', ['columnar', 'json'])
def test_save_format_oracle_parity(fmt, monkeypatch, pool_lane):
    """Both container formats restore byte-identical state, and the
    decoded changes applied to a fresh pool equal the original apply
    (decode->apply parity oracle, both exec modes)."""
    monkeypatch.setenv('AMTPU_STORAGE_FORMAT', fmt)
    rng = random.Random(21)
    changes = _rand_changes(rng, n_rounds=12, with_weird=False)
    pool = NativeDocPool()
    for c in changes:
        pool.apply_changes('d', [c])
    blob = pool.save('d')
    if fmt == 'json':
        assert blob.startswith(storage.CKPT_V1_PREFIX)
    else:
        assert blob.startswith(storage.CKPT_V2_PREFIX)
    fresh = NativeDocPool()
    assert fresh.load('d2', blob) == pool.get_patch('d')
    assert fresh.get_missing_changes('d2', {}) == \
        pool.get_missing_changes('d', {})


# ---------------------------------------------------------------------------
# settled-history GC: frontier + straggler backfill
# ---------------------------------------------------------------------------

def _interleaved_history(pool, doc='d'):
    pool.apply_changes(doc, [
        {'actor': 'A', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeText', 'obj': 'T'},
            {'action': 'ins', 'obj': 'T', 'key': '_head', 'elem': 1},
            {'action': 'set', 'obj': 'T', 'key': 'A:1', 'value': 'x'},
            {'action': 'link', 'obj': ROOT, 'key': 'text',
             'value': 'T'}]}])
    for seq in range(1, 6):
        for actor in ('B', 'C'):
            elem = 10 * seq + (1 if actor == 'B' else 2)
            pool.apply_changes(doc, [
                {'actor': actor, 'seq': seq, 'deps': {'A': 1}, 'ops': [
                    {'action': 'ins', 'obj': 'T', 'key': 'A:1',
                     'elem': elem},
                    {'action': 'set', 'obj': 'T',
                     'key': '%s:%d' % (actor, elem),
                     'value': chr(97 + seq)},
                    {'action': 'set', 'obj': ROOT,
                     'key': 'k%d' % (seq % 3), 'value': seq}]}])


class TestSettledHistoryGC(object):
    def test_gc_shrinks_arena_and_straggler_backfills(self, pool_lane):
        """A straggler subscriber whose clock sits BEHIND the settled
        frontier still backfills byte-identically via
        get_missing_changes (the GC-frontier lane)."""
        pool = NativeDocPool()
        twin = NativeDocPool()
        _interleaved_history(pool)
        _interleaved_history(twin)
        before = pool.history_bytes('d')
        folded = pool.compact('d', frontier={'A': 1, 'B': 3, 'C': 3})
        assert folded > 0
        assert pool.history_bytes('d') < before
        assert pool.get_patch('d') == twin.get_patch('d')
        for have in ({}, {'A': 1}, {'A': 1, 'B': 2}, {'B': 1},
                     {'A': 1, 'B': 5, 'C': 5},
                     {'A': 1, 'B': 3, 'C': 3}):
            assert pool.get_missing_changes('d', have) == \
                twin.get_missing_changes('d', have), have
        for actor in ('A', 'B', 'C'):
            for after in (0, 1, 2):
                assert pool.get_changes_for_actor('d', actor, after) \
                    == twin.get_changes_for_actor('d', actor, after)
        snap = telemetry.metrics_snapshot()
        assert snap.get('storage.snapshot_backfills', 0) > 0
        assert snap.get('storage.gc.compactions', 0) == 1

    def test_gc_folds_only_the_settled_prefix(self):
        """Folding must preserve application order: a frontier that
        settles a LATER change before an earlier concurrent one only
        folds up to the first unsettled change."""
        pool = NativeDocPool()
        # B1 applied before A1; both concurrent
        pool.apply_changes('d', [
            {'actor': 'B', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT, 'key': 'x',
                 'value': 1}]}])
        pool.apply_changes('d', [
            {'actor': 'A', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT, 'key': 'y',
                 'value': 2}]}])
        # frontier settles A1 but NOT B1 -> nothing may fold (A1 sits
        # after the unsettled B1 in application order)
        assert pool.compact('d', frontier={'A': 1}) == 0
        # settling B1 folds exactly the B1 prefix
        assert pool.compact('d', frontier={'B': 1}) == 1

    def test_loading_old_checkpoint_into_live_doc_loses_nothing(self):
        """Loading an OLDER v2 checkpoint into a live doc must not
        overwrite the doc's newer snapshot state: later-compacted
        changes would then live in neither arena nor snapshot."""
        pool = NativeDocPool()
        twin = NativeDocPool()
        for seq in range(1, 4):
            ch = [{'actor': 'a', 'seq': seq,
                   'deps': {'a': seq - 1} if seq > 1 else {},
                   'ops': [{'action': 'set', 'obj': ROOT,
                            'key': 'k%d' % seq, 'value': seq}]}]
            pool.apply_changes('d', ch)
            twin.apply_changes('d', ch)
            if seq == 1:
                pool.compact('d')
                old_blob = pool.save('d')
        pool.compact('d')                   # frontier now {a: 3}
        pool.load('d', old_blob)            # replays as seq-dedup no-ops
        assert pool.get_clock('d')['clock'] == {'a': 3}
        assert pool.get_missing_changes('d', {}) == \
            twin.get_missing_changes('d', {})
        fresh = NativeDocPool()
        fresh.load('d2', pool.save('d'))
        assert fresh.get_patch('d2') == twin.get_patch('d')

    def test_repeated_compactions_append_chunks(self):
        pool = NativeDocPool()
        twin = NativeDocPool()
        seqs = []
        for seq in range(1, 9):
            ch = [{'actor': 'W', 'seq': seq,
                   'deps': {'W': seq - 1} if seq > 1 else {},
                   'ops': [{'action': 'set', 'obj': ROOT,
                            'key': 'k%d' % (seq % 2), 'value': seq}]}]
            pool.apply_changes('d', ch)
            twin.apply_changes('d', ch)
            seqs.append(seq)
            if seq % 3 == 0:
                assert pool.compact('d') > 0
        assert pool.get_missing_changes('d', {}) == \
            twin.get_missing_changes('d', {})
        blob = pool.save('d')
        fresh = NativeDocPool()
        assert fresh.load('d2', blob) == twin.get_patch('d')


# ---------------------------------------------------------------------------
# save -> evict -> reload -> mutate byte parity (both exec modes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('make_pool', [
    NativeDocPool, lambda: ShardedNativePool(n_shards=2)],
    ids=['native', 'sharded'])
def test_evict_reload_mutate_parity(make_pool, pool_lane):
    pool = make_pool()
    twin = make_pool()
    _interleaved_history(pool)
    _interleaved_history(twin)
    pool.compact('d')
    blob = pool.save('d')
    assert pool.drop_doc('d')
    assert not pool.drop_doc('d')          # idempotent
    assert pool.history_bytes('d') == 0
    pool.load('d', blob)
    mut = [{'actor': 'B', 'seq': 6, 'deps': {'B': 5, 'C': 5},
            'ops': [{'action': 'set', 'obj': ROOT, 'key': 'post',
                     'value': 7},
                    {'action': 'ins', 'obj': 'T', 'key': 'A:1',
                     'elem': 99},
                    {'action': 'set', 'obj': 'T', 'key': 'B:99',
                     'value': 'z'}]}]
    got = pool.apply_changes('d', mut)
    want = twin.apply_changes('d', mut)
    assert got == want
    assert pool.get_patch('d') == twin.get_patch('d')
    assert pool.get_missing_changes('d', {}) == \
        twin.get_missing_changes('d', {})
    # a reloaded doc keeps its compacted economics
    assert pool.history_bytes('d') < twin.history_bytes('d')


# ---------------------------------------------------------------------------
# the cold store + evictor (unit level)
# ---------------------------------------------------------------------------

class TestDocEvictor(object):
    def test_lru_eviction_and_reload(self, tmp_path):
        pool = NativeDocPool()
        ev = DocEvictor(pool, max_resident=2,
                        store=ColdStore(str(tmp_path)), gc_every=0)
        patches = {}
        for i in range(4):
            doc = 'doc%d' % i
            pool.apply_changes(doc, [
                {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                    {'action': 'set', 'obj': ROOT, 'key': 'k',
                     'value': i}]}])
            patches[doc] = pool.get_patch(doc)
            ev.note_touch([doc])
            ev.maybe_evict(protect=[doc])
        assert len(ev.store) == 2           # doc0, doc1 went cold
        assert 'doc0' in ev.store and 'doc1' in ev.store
        assert pool.doc_count() == 2
        # reload-on-touch restores byte-identical state
        ev.ensure_resident(['doc0'])
        assert 'doc0' not in ev.store
        assert pool.get_patch('doc0') == patches['doc0']
        snap = telemetry.metrics_snapshot()
        assert snap['storage.evictions'] == 2
        assert snap['storage.reloads'] == 1

    def test_failed_reload_keeps_the_cold_blob(self, tmp_path):
        """A reload that raises must NOT destroy the only copy of the
        doc: the blob stays in the store, the failure is reported per
        doc, and a later touch succeeds."""
        pool = NativeDocPool()
        ev = DocEvictor(pool, max_resident=0,
                        store=ColdStore(str(tmp_path)), gc_every=0)
        want = {}
        for doc in ('d', 'healthy'):
            pool.apply_changes(doc, [
                {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                    {'action': 'set', 'obj': ROOT, 'key': 'k',
                     'value': 1}]}])
            want[doc] = pool.get_patch(doc)
            ev.store.put(doc, pool.save(doc))
            pool.drop_doc(doc)

        real_load = pool.load_batch
        poison = {'on': True}

        def flaky_load(blobs):
            if poison['on'] and 'd' in blobs:
                raise RuntimeError('transient replay failure')
            return real_load(blobs)
        pool.load_batch = flaky_load
        failed = ev.ensure_resident(['d', 'healthy'])
        # the poison doc is isolated: its blob survives, the healthy
        # doc reloaded anyway
        assert list(failed) == ['d']
        assert 'd' in ev.store and 'healthy' not in ev.store
        assert pool.get_patch('healthy') == want['healthy']
        snap = telemetry.metrics_snapshot()
        assert snap['storage.reload_failed'] == 1
        poison['on'] = False
        assert ev.ensure_resident(['d']) == {}
        assert 'd' not in ev.store
        assert pool.get_patch('d') == want['d']

    def test_protected_docs_never_evict(self, tmp_path):
        pool = NativeDocPool()
        ev = DocEvictor(pool, max_resident=1,
                        store=ColdStore(str(tmp_path)), gc_every=0)
        for doc in ('a', 'b'):
            pool.apply_changes(doc, [
                {'actor': 'x', 'seq': 1, 'deps': {}, 'ops': [
                    {'action': 'set', 'obj': ROOT, 'key': 'k',
                     'value': 1}]}])
            ev.note_touch([doc])
        ev.maybe_evict(protect=['a', 'b'])
        assert len(ev.store) == 0           # both protected: no evict
        ev.maybe_evict(protect=['b'])
        assert 'a' in ev.store


# ---------------------------------------------------------------------------
# gateway e2e: eviction + reload-on-touch through the flush cycle
# ---------------------------------------------------------------------------

def test_gateway_evicts_and_reloads_on_touch(tmp_path, monkeypatch):
    from automerge_tpu.scheduler import GatewayServer
    from automerge_tpu.sidecar.client import SidecarClient
    from automerge_tpu.sidecar.server import SidecarBackend
    monkeypatch.setenv('AMTPU_FLUSH_DEADLINE_MS', '2')
    monkeypatch.setenv('AMTPU_RESIDENT_DOCS_MAX', '2')
    monkeypatch.setenv('AMTPU_STORAGE_DIR', str(tmp_path / 'cold'))
    path = str(tmp_path / 'gw-storage.sock')
    gw = GatewayServer(path, backend=SidecarBackend()).start()
    try:
        with SidecarClient(sock_path=path) as c:
            want = {}
            for i in range(5):
                doc = 'cold%d' % i
                c.apply_changes(doc, [
                    {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                        {'action': 'set', 'obj': ROOT, 'key': 'k',
                         'value': i}]}])
                want[doc] = c.get_patch(doc)
            # wait until the storage tier reports evictions
            deadline = time.time() + 30
            while time.time() < deadline:
                h = c.healthz()
                if h['storage']['cold_docs'] >= 1:
                    break
                time.sleep(0.05)
            assert h['storage']['cold_docs'] >= 1, h['storage']
            assert h['storage']['resident_docs'] <= 2
            # touching every doc again (reads AND writes) reloads cold
            # ones transparently with byte-identical state
            for i in range(5):
                doc = 'cold%d' % i
                assert c.get_patch(doc) == want[doc], doc
            c.apply_changes('cold0', [
                {'actor': 'a', 'seq': 2, 'deps': {'a': 1}, 'ops': [
                    {'action': 'set', 'obj': ROOT, 'key': 'k2',
                     'value': 'post-reload'}]}])
            p = c.get_patch('cold0')
            assert p['clock'] == {'a': 2}
            snap = c.healthz()['storage']
            assert snap['max_resident'] == 2
    finally:
        gw.stop()


# ---------------------------------------------------------------------------
# WAL byte bound
# ---------------------------------------------------------------------------

class TestWALByteBound(object):
    def _wal_server(self, wal):
        """A fake call_raw that answers save/load like the sidecar."""
        state = {'saves': 0}

        def call_raw(cmd, kwargs):
            if cmd == 'save':
                state['saves'] += 1
                return {'checkpoint_b64': 'QQ=='}
            return {}
        return call_raw, state

    def test_byte_bound_trips_before_entry_count(self):
        from automerge_tpu.sidecar.client import CheckpointWAL
        wal = CheckpointWAL(compact_every=1000, max_bytes=100)
        call_raw, state = self._wal_server(wal)
        big = {'doc': 'd', 'changes': [{'actor': 'a', 'seq': 1,
                                        'ops': [], 'pad': 'x' * 120}]}
        wal.record('apply_changes', big)
        assert wal.log_bytes > 100
        wal.maybe_compact(call_raw)        # 1 entry but > 100 bytes
        assert state['saves'] == 1
        assert wal.log == [] and wal.log_bytes == 0
        snap = telemetry.metrics_snapshot()
        assert snap['sidecar.client.wal_compactions'] == 1
        # the gauge tracks the current footprint (snapshots only now)
        assert snap['sidecar.client.wal_bytes'] == wal.snap_bytes

    def test_compaction_failure_keeps_retrying_under_byte_bound(self):
        from automerge_tpu.sidecar.client import CheckpointWAL
        wal = CheckpointWAL(compact_every=1000, max_bytes=64)

        def broken(cmd, kwargs):
            raise ConnectionError('server died')
        entry = {'doc': 'd', 'changes': [{'actor': 'a', 'seq': 1,
                                          'pad': 'y' * 80}]}
        wal.record('apply_changes', entry)
        wal.maybe_compact(broken)
        wal.record('apply_changes', entry)
        wal.maybe_compact(broken)
        snap = telemetry.metrics_snapshot()
        assert snap['sidecar.client.wal_compact_failed'] == 2
        assert len(wal.log) == 2           # log retained for replay
        # a healthy server finally compacts
        call_raw, state = self._wal_server(wal)
        wal.maybe_compact(call_raw)
        assert state['saves'] == 1 and wal.log == []

    def test_disabled_byte_bound_keeps_entry_trigger_only(self):
        from automerge_tpu.sidecar.client import CheckpointWAL
        wal = CheckpointWAL(compact_every=3, max_bytes=0)
        call_raw, state = self._wal_server(wal)
        huge = {'doc': 'd', 'pad': 'z' * 10000}
        wal.record('apply_changes', huge)
        wal.maybe_compact(call_raw)
        assert state['saves'] == 0          # bytes never trip
        wal.record('apply_changes', huge)
        wal.record('apply_changes', huge)
        wal.maybe_compact(call_raw)
        assert state['saves'] == 1          # entry count does


# ---------------------------------------------------------------------------
# fan-out: one write per connection per flush
# ---------------------------------------------------------------------------

def test_fanout_one_write_per_conn_across_docs():
    """A connection multiplexing peers on TWO dirty docs receives both
    frames in ONE write per flush (`sync.fanout.writes_coalesced`)."""
    from automerge_tpu.sync.fanout import FanoutEngine
    pool = NativeDocPool()
    engine = FanoutEngine(
        pool, lambda obj: (json.dumps(obj) + '\n').encode())
    writes = []
    shared = writes.append
    solo_writes = []
    for doc in ('dA', 'dB'):
        pool.apply_changes(doc, [
            {'actor': 'w', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT, 'key': 'k',
                 'value': 0}]}])
        engine.subscribe((1, 'multi'), doc, {'w': 1}, shared)
    engine.subscribe((2, 'solo'), 'dA', {'w': 1}, solo_writes.append)
    telemetry.metrics_reset()
    updates = {}
    for doc in ('dA', 'dB'):
        updates[doc] = pool.apply_changes(doc, [
            {'actor': 'w', 'seq': 2, 'deps': {'w': 1}, 'ops': [
                {'action': 'set', 'obj': ROOT, 'key': 'k',
                 'value': 1}]}])['clock']
    engine.on_flush(updates)
    assert len(writes) == 1, 'expected ONE write for the shared conn'
    frames = [json.loads(line)
              for line in writes[0].decode().strip().split('\n')]
    assert sorted(f['doc'] for f in frames) == ['dA', 'dB']
    assert len(solo_writes) == 1
    snap = telemetry.metrics_snapshot()
    assert snap['sync.fanout.writes_coalesced'] == 1
    assert snap['sync.fanout.frames'] == 3
    # both subscriptions advanced: the next flush has nothing to send
    writes.clear()
    engine.on_flush(updates)
    assert not writes


def test_fanout_acked_clock_is_pointwise_min():
    from automerge_tpu.sync.fanout import FanoutEngine
    pool = NativeDocPool()
    engine = FanoutEngine(pool, lambda obj: b'')
    assert engine.acked_clock('nope') is None
    engine.subscribe((1, 'p1'), 'd', {'a': 3, 'b': 1}, lambda b: None,
                     backfill=False)
    engine.subscribe((2, 'p2'), 'd', {'a': 2, 'b': 5}, lambda b: None,
                     backfill=False)
    assert engine.acked_clock('d') == {'a': 2, 'b': 1}


def test_gc_frontier_from_fanout_keeps_straggler_serveable():
    """End-to-end GC sanity: compaction bounded by the fan-out acked
    clock never folds past what a live straggler still needs from the
    C++ tail, and the straggler's catch-up stays byte-identical."""
    from automerge_tpu.sync.fanout import FanoutEngine
    pool = NativeDocPool()
    twin = NativeDocPool()
    engine = FanoutEngine(pool, lambda obj: b'')
    _interleaved_history(pool)
    _interleaved_history(twin)
    engine.subscribe((1, 'slow'), 'd', {'A': 1, 'B': 2}, lambda b: None,
                     backfill=False)
    acked = engine.acked_clock('d')
    assert acked == {'A': 1, 'B': 2}
    folded = pool.compact('d', frontier=acked)
    assert folded > 0
    # the straggler's own catch-up comes straight off the C++ tail
    telemetry.metrics_reset()
    assert pool.get_missing_changes('d', {'A': 1, 'B': 2}) == \
        twin.get_missing_changes('d', {'A': 1, 'B': 2})
    assert telemetry.metrics_snapshot().get(
        'storage.snapshot_backfills', 0) == 0
    # an EVEN OLDER reconnector merges from the snapshot
    assert pool.get_missing_changes('d', {}) == \
        twin.get_missing_changes('d', {})
    assert telemetry.metrics_snapshot().get(
        'storage.snapshot_backfills', 0) == 1


def test_checkpoints_cross_pool_layouts():
    """A checkpoint crosses pool layouts both ways, compacted history
    included: what a failover between a multi-chip host's doc-sharded
    pool and a one-chip host's single pool depends on."""
    sharded = ShardedNativePool(n_shards=2)
    _interleaved_history(sharded)
    want = sharded.get_patch('d')
    single = NativeDocPool()
    assert single.load('x', sharded.save('d')) == want
    assert single.compact('x') > 0
    back = ShardedNativePool(n_shards=2)
    assert back.load('y', single.save('x')) == want
    assert back.get_patch('y') == want
