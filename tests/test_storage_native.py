"""Native columnar fast path + arena op-state folding lanes (ISSUE 14,
docs/STORAGE.md): codec fuzz parity native vs Python (blob-for-blob AND
byte round-trip, including hand-mangled non-canonical bytes, dup-
(actor,seq) streams, and GC-truncated docs), arena-direct decode parity
vs the dict-replay oracle across both exec modes, the op-state folding
lane (flat arena under settled-overwrite churn with byte-identical
straggler backfill), chunk re-compaction, and the durable cold store
(kill-mid-save via the ``storage.save`` fault lane, manifest recovery,
checksum detection)."""

import os
import random

import msgpack
import pytest

from automerge_tpu import faults, storage, telemetry
from automerge_tpu.native import NativeDocPool
from automerge_tpu.native import columnar_decode_native, \
    columnar_encode_native
from automerge_tpu.storage.coldstore import ColdStore

ROOT = '00000000-0000-0000-0000-000000000000'


@pytest.fixture(autouse=True)
def _reset():
    telemetry.reset_all()
    faults.reset('')
    yield
    faults.reset('')
    telemetry.reset_all()


def _encode_arm(raws, native):
    """encode_columnar through one dispatch arm (the gate is checked
    per call, so flipping the env interleaves cleanly)."""
    prior = os.environ.get('AMTPU_STORAGE_NATIVE')
    os.environ['AMTPU_STORAGE_NATIVE'] = '1' if native else '0'
    try:
        return storage.encode_columnar(raws)
    finally:
        if prior is None:
            os.environ.pop('AMTPU_STORAGE_NATIVE', None)
        else:
            os.environ['AMTPU_STORAGE_NATIVE'] = prior


def _decode_arm(blob, native):
    prior = os.environ.get('AMTPU_STORAGE_NATIVE')
    os.environ['AMTPU_STORAGE_NATIVE'] = '1' if native else '0'
    try:
        return storage.decode_columnar(blob)
    finally:
        if prior is None:
            os.environ.pop('AMTPU_STORAGE_NATIVE', None)
        else:
            os.environ['AMTPU_STORAGE_NATIVE'] = prior


def _rand_change_dicts(rng, n=120, n_actors=5):
    """Well-formed random corpus: map sets, text runs, links, odd-but-
    canonical value types, catch-up deps, dup-(actor,seq) replays."""
    out = []
    seqs = {}
    elem = 0
    for i in range(n):
        actor = 'actor-%d' % rng.randrange(n_actors)
        seqs[actor] = seqs.get(actor, 0) + 1
        ops = []
        for _ in range(rng.randrange(1, 5)):
            roll = rng.random()
            if roll < 0.3:
                ops.append({'action': 'set', 'obj': ROOT,
                            'key': 'k%d' % rng.randrange(8),
                            'value': rng.choice([
                                rng.randrange(-10**9, 10**9), 'héllo 中',
                                3.140625, True, False, None, b'\x00\xff',
                                {'nested': [1, 'two', None]},
                                ['deep', {'er': 2.5}]])})
            elif roll < 0.5:
                elem += 1
                ops.append({'action': 'ins', 'obj': 'T',
                            'key': '_head' if elem == 1
                            else '%s:%d' % (actor, elem - 1),
                            'elem': elem})
            elif roll < 0.7:
                ops.append({'action': 'set', 'obj': 'T',
                            'key': '%s:%d' % (actor, max(1, elem)),
                            'value': chr(97 + i % 26)})
            elif roll < 0.8:
                ops.append({'action': 'makeMap', 'obj': 'm-%d' % i})
            else:
                ops.append({'action': 'del', 'obj': ROOT,
                            'key': 'k%d' % rng.randrange(8)})
        ch = {'actor': actor, 'seq': seqs[actor],
              'deps': {a: s for a, s in list(seqs.items())
                       [:rng.randrange(0, 3)]},
              'ops': ops}
        if rng.random() < 0.25:
            ch['message'] = 'round %d' % i
        out.append(ch)
        if rng.random() < 0.1:
            out.append(dict(ch))     # dup-(actor,seq) replay
    return out


def _mangled_raws():
    """Hand-mangled / non-canonical change bytes: every one must ride
    the residual column and still round-trip byte-exactly."""
    k = msgpack.packb
    return [
        # non-canonical int spelling (uint8 for a fixint value)
        b'\x82' + k('actor') + k('a') + k('seq') + b'\xcc\x05',
        # float32 value (canonical re-encode widens to float64)
        b'\x83' + k('actor') + k('a') + k('seq') + k(1) +
        k('x') + b'\xca\x3f\x80\x00\x00',
        # not a map at all
        k([1, 2, 3], use_bin_type=True),
        # bool seq (schema reject)
        k({'actor': 'a', 'seq': True}, use_bin_type=True),
        # negative seq (schema reject)
        k({'actor': 'a', 'seq': -3}, use_bin_type=True),
        # deps with a non-int value (schema reject)
        k({'actor': 'a', 'seq': 1, 'deps': {'b': 'x'}},
          use_bin_type=True),
        # duplicate map key (canonical re-encode collapses it)
        b'\x82' + k('actor') + k('a') + k('actor') + k('b'),
        # int obj in an op (schema reject: typed column desync)
        k({'actor': 'a', 'seq': 1,
           'ops': [{'action': 'set', 'obj': 7, 'key': 'k'}]},
          use_bin_type=True),
        # trailing bytes after the change map
        k({'actor': 'a', 'seq': 1}, use_bin_type=True) + b'\x01',
    ]


class TestCodecFuzzParity:
    """Native codec vs Python codec: blob-for-blob identical output and
    guaranteed byte round-trip on random corpora."""

    @pytest.mark.parametrize('seed', [7, 23, 101])
    def test_blob_and_roundtrip_parity(self, seed):
        rng = random.Random(seed)
        raws = [msgpack.packb(c, use_bin_type=True)
                for c in _rand_change_dicts(rng)]
        py_blob = _encode_arm(raws, native=False)
        nat_blob = _encode_arm(raws, native=True)
        assert py_blob == nat_blob          # bit-for-bit, zlib included
        # all four (encoder, decoder) pairs reproduce the input bytes
        assert _decode_arm(py_blob, native=False) == raws
        assert _decode_arm(py_blob, native=True) == raws
        assert _decode_arm(nat_blob, native=False) == raws
        assert _decode_arm(nat_blob, native=True) == raws
        flat = telemetry.metrics_snapshot()
        assert flat.get('storage.native_encodes', 0) >= 1
        assert flat.get('storage.python_encodes', 0) >= 1

    def test_mangled_bytes_ride_residual_and_roundtrip(self):
        rng = random.Random(5)
        good = [msgpack.packb(c, use_bin_type=True)
                for c in _rand_change_dicts(rng, n=20)]
        raws = []
        mangled = _mangled_raws()
        for i, raw in enumerate(good):
            raws.append(raw)
            if i < len(mangled):
                raws.append(mangled[i])
        py_blob = _encode_arm(raws, native=False)
        nat_blob = _encode_arm(raws, native=True)
        # round-trip is the hard guarantee for residual-laden streams
        assert _decode_arm(py_blob, native=True) == raws
        assert _decode_arm(nat_blob, native=False) == raws
        assert _decode_arm(nat_blob, native=True) == raws
        # both encoders sent the mangled changes residual (the exact
        # split is each encoder's own; the counter proves nonzero)
        assert telemetry.metrics_snapshot().get(
            'storage.columnar.residual_changes', 0) >= len(mangled)

    def test_native_decode_rejects_corrupt_blobs(self):
        blob = _encode_arm(
            [msgpack.packb({'actor': 'a', 'seq': 1, 'deps': {},
                            'ops': []}, use_bin_type=True)],
            native=True)
        for bad in (b'AMTX' + blob[4:],          # magic
                    blob[:4] + b'\x07' + blob[5:],   # version
                    blob[:6] + b'garbage',        # body
                    blob[:-3]):                   # truncated
            with pytest.raises(ValueError):
                columnar_decode_native(bad)

    def test_gc_truncated_doc_chunks_decode_identically(self):
        """GC-truncated docs: the snapshot chunks a compacted pool
        holds decode byte-identically through both codecs."""
        pool = NativeDocPool()
        for r in range(8):
            pool.apply_batch({'d': [
                {'actor': 'a1', 'seq': r + 1, 'deps': {},
                 'ops': [{'action': 'set', 'obj': ROOT,
                          'key': 'k%d' % (r % 2), 'value': r}]}]})
        assert pool.compact('d') > 0
        st = pool._storage[pool._doc_key('d')]
        assert st['chunks']
        for chunk in st['chunks']:
            assert _decode_arm(chunk, native=True) == \
                _decode_arm(chunk, native=False)

    def test_exotic_ext_bytes_ride_residual(self):
        """msgpack ext framing (outside the conservative canonical
        subset): the native encoder carries it verbatim in the residual
        column -- round-trip and cross-decode still hold."""
        ext = msgpack.packb(msgpack.ExtType(4, b'\x01\x02'))
        raws = [msgpack.packb({'actor': 'a', 'seq': 1},
                              use_bin_type=True), ext]
        blob = _encode_arm(raws, native=True)
        assert _decode_arm(blob, native=False) == raws
        assert _decode_arm(blob, native=True) == raws
        assert telemetry.metrics_snapshot().get(
            'storage.columnar.residual_changes', 0) >= 1


def _corpus_round(rng, state, n=3, n_actors=3, tag=''):
    """Causally-valid mixed changes for ONE doc round: map sets, a
    growing text run, object creations, deletes (the apply-side twin
    of the codec fuzz generator)."""
    out = []
    for _i in range(n):
        actor = 'b%d' % rng.randrange(n_actors)
        ops = []
        for _ in range(rng.randrange(1, 4)):
            roll = rng.random()
            if roll < 0.35:
                ops.append({'action': 'set', 'obj': ROOT,
                            'key': 'k%d' % rng.randrange(6),
                            'value': rng.choice([
                                rng.randrange(-999, 9999), 'v中', 2.5,
                                None, True, b'\x01\x02'])})
            elif roll < 0.7:
                state['elem'] += 1
                ops.append({'action': 'ins', 'obj': 'T',
                            'key': state['prev'],
                            'elem': state['elem']})
                key = '%s:%d' % (actor, state['elem'])
                ops.append({'action': 'set', 'obj': 'T', 'key': key,
                            'value': chr(97 + state['elem'] % 26)})
                state['prev'] = key
            elif roll < 0.85:
                state['mk'] += 1
                ops.append({'action': 'makeMap',
                            'obj': 'M-%s-%d' % (tag, state['mk'])})
            else:
                ops.append({'action': 'del', 'obj': ROOT,
                            'key': 'k%d' % rng.randrange(6)})
        out.append({'actor': actor, 'ops': ops})
    return out


def _stamp(rng, clock, chs):
    """Stamps a change list into a causally-ready per-doc stream
    (seq = next per actor, deps a subset of the applied clock)."""
    out = []
    for c in chs:
        a = c['actor']
        clock[a] = clock.get(a, 0) + 1
        c = dict(c)
        c['seq'] = clock[a]
        c['deps'] = {k: v for k, v in clock.items()
                     if k != a and rng.random() < 0.5}
        out.append(c)
    return out


def _build_corpus_pool(rng, n_docs=6, compact_some=True):
    """A builder pool with mixed doc shapes; some docs compacted so
    their checkpoints carry snapshot chunks."""
    pool = NativeDocPool()
    for d in range(n_docs):
        doc = 'doc-%d' % d
        clock = {}
        state = {'elem': 0, 'prev': '_head', 'mk': 0}
        init = [{'actor': 'b0', 'ops': [
            {'action': 'makeText', 'obj': 'T'},
            {'action': 'link', 'obj': ROOT, 'key': 'text',
             'value': 'T'}]}]
        pool.apply_batch({doc: _stamp(rng, clock, init)})
        for r in range(6):
            chs = _corpus_round(rng, state, tag='%s-%d' % (doc, r))
            pool.apply_batch({doc: _stamp(rng, clock, chs)})
        if compact_some and d % 2 == 0:
            pool.compact(doc)
    return pool


class TestDecodePathParity:
    """Arena-direct native load vs the dict-replay oracle: per-doc
    byte-identical state across both exec modes."""

    def test_load_batch_parity_both_arms(self, pool_lane, monkeypatch):
        rng = random.Random(11)
        pool = _build_corpus_pool(rng)
        docs = ['doc-%d' % d for d in range(6)]
        blobs = {d: pool.save(d) for d in docs}

        monkeypatch.setenv('AMTPU_STORAGE_NATIVE', '1')
        nat = NativeDocPool()
        nat.load_batch(blobs)
        assert telemetry.metrics_snapshot().get('storage.native_loads', 0) >= 1

        monkeypatch.setenv('AMTPU_STORAGE_NATIVE', '0')
        py = NativeDocPool()
        py.load_batch(blobs)

        monkeypatch.delenv('AMTPU_STORAGE_NATIVE', raising=False)
        for d in docs:
            assert nat.get_patch(d) == py.get_patch(d) == \
                pool.get_patch(d)
            assert nat.save(d) == py.save(d)
            assert nat.get_missing_changes(d, {}) == \
                py.get_missing_changes(d, {})

    def test_v1_checkpoints_load_native(self, monkeypatch):
        monkeypatch.setenv('AMTPU_STORAGE_FORMAT', 'json')
        rng = random.Random(3)
        pool = _build_corpus_pool(rng, n_docs=2, compact_some=False)
        blobs = {d: pool.save(d) for d in ('doc-0', 'doc-1')}
        assert all(b.startswith(storage.CKPT_V1_PREFIX)
                   for b in blobs.values())
        monkeypatch.setenv('AMTPU_STORAGE_NATIVE', '1')
        nat = NativeDocPool()
        nat.load_batch(blobs)
        for d in blobs:
            assert nat.get_patch(d) == pool.get_patch(d)


class TestOpStateFolding:
    """Settled-overwrite churn: history bytes AND op count stay FLAT
    (not merely sub-linear) with folding on, while a straggler behind
    the fold frontier still backfills byte-identically."""

    def _churn(self, fold_on, rounds=8, keys=6, monkeypatch=None):
        monkeypatch.setenv('AMTPU_STORAGE_FOLD', '1' if fold_on else '0')
        pool = NativeDocPool()
        track = []
        round_changes = []
        seq = 0
        for r in range(rounds):
            chs = []
            for k in range(keys):
                seq += 1
                chs.append({'actor': 'w', 'seq': seq, 'deps': {},
                            'ops': [{'action': 'set', 'obj': ROOT,
                                     'key': 'k%d' % k, 'value': r}]})
            round_changes.append(chs)
            pool.apply_batch({'churn': chs})
            pool.compact('churn')      # no subscribers: all settled
            track.append((pool.history_bytes('churn'),
                          pool.op_count('churn')))
        return pool, track, round_changes

    def test_arena_flat_under_churn_with_folding(self, monkeypatch):
        pool, track, _ = self._churn(True, monkeypatch=monkeypatch)
        bytes_per_round = [b for b, _n in track]
        ops_per_round = [n for _b, n in track]
        # FLAT: every post-compact round measures exactly the same
        assert len(set(bytes_per_round[1:])) == 1, bytes_per_round
        assert len(set(ops_per_round[1:])) == 1, ops_per_round
        assert telemetry.metrics_snapshot().get('storage.gc.ops_folded', 0) > 0

    def test_no_fold_arm_grows_and_patches_match(self, monkeypatch):
        pool_f, _track, _ = self._churn(True, monkeypatch=monkeypatch)
        patch_f = pool_f.get_patch('churn')
        telemetry.reset_all()
        pool_n, track_n, _ = self._churn(False, monkeypatch=monkeypatch)
        ops_n = [n for _b, n in track_n]
        assert ops_n[-1] > ops_n[1]          # no-fold arm grows
        assert telemetry.metrics_snapshot().get('storage.gc.ops_folded', 0) == 0
        assert pool_n.get_patch('churn') == patch_f

    def test_straggler_backfills_byte_identically(self, monkeypatch):
        """A replica that stopped at round 1 catches up from behind the
        fold frontier: the shipped bytes and final state must match the
        no-fold arm exactly."""
        results = {}
        for arm in (True, False):
            pool, _track, round_changes = self._churn(
                arm, monkeypatch=monkeypatch)
            straggler = NativeDocPool()
            straggler.apply_batch({'churn': round_changes[0]})
            have = straggler.get_clock('churn')['clock']
            missing = pool.get_missing_changes('churn', have)
            raw = pool.get_changes_for_actor_bytes('churn', 'w',
                                                   have.get('w', 0))
            straggler.apply_batch({'churn': missing})
            results[arm] = (missing, raw,
                            straggler.get_patch('churn'),
                            pool.get_patch('churn'))
        assert results[True] == results[False]
        fold_missing, _raw, straggler_patch, main_patch = results[True]
        assert straggler_patch == main_patch

    def test_duplicate_resend_of_folded_change_is_harmless(
            self, monkeypatch):
        pool, _track, round_changes = self._churn(
            True, monkeypatch=monkeypatch)
        before = pool.get_patch('churn')
        # folded entries freed their op records; a straggler re-sending
        # the settled change must dedup, not raise
        pool.apply_batch({'churn': round_changes[0]})
        assert pool.get_patch('churn') == before


class TestChunkRecompaction:
    def test_chunks_merge_past_cap(self, monkeypatch):
        monkeypatch.setenv('AMTPU_STORAGE_CHUNK_MAX', '3')
        pool = NativeDocPool()
        seq = 0
        for r in range(7):
            seq += 1
            pool.apply_batch({'d': [
                {'actor': 'a', 'seq': seq, 'deps': {},
                 'ops': [{'action': 'set', 'obj': ROOT, 'key': 'k',
                          'value': r}]}]})
            pool.compact('d')
        st = pool._storage[pool._doc_key('d')]
        assert len(st['chunks']) < 3
        assert telemetry.metrics_snapshot().get('storage.gc.rechunks', 0) >= 1
        # the merged snapshot still restores byte-identically
        twin = NativeDocPool()
        twin.load_batch({'d': pool.save('d')})
        assert twin.get_patch('d') == pool.get_patch('d')
        assert twin.save('d') == pool.save('d')

    def test_rechunk_disabled_by_zero(self, monkeypatch):
        monkeypatch.setenv('AMTPU_STORAGE_CHUNK_MAX', '0')
        pool = NativeDocPool()
        for r in range(5):
            pool.apply_batch({'d': [
                {'actor': 'a', 'seq': r + 1, 'deps': {},
                 'ops': [{'action': 'set', 'obj': ROOT, 'key': 'k',
                          'value': r}]}]})
            pool.compact('d')
        st = pool._storage[pool._doc_key('d')]
        assert len(st['chunks']) == 5
        assert telemetry.metrics_snapshot().get('storage.gc.rechunks', 0) == 0


class TestDurableColdStore:
    def _blob(self, tag):
        return (b'AMTC-fake-' + tag) * 40

    def test_manifest_recovery(self, tmp_path):
        root = str(tmp_path / 'cold')
        cs = ColdStore(root=root, durable=True)
        cs.put('doc-a', self._blob(b'a'))
        cs.put('doc-b', self._blob(b'b'))
        fresh = ColdStore(root=root, durable=True)
        assert sorted(fresh.doc_ids()) == ['doc-a', 'doc-b']
        assert fresh.get('doc-a') == self._blob(b'a')
        assert telemetry.metrics_snapshot().get(
            'storage.manifest_recovered', 0) == 2

    @pytest.mark.parametrize('durable', [True, False])
    def test_kill_mid_save_leaves_prior_intact(self, tmp_path, durable):
        """The storage.save fault lane: a save killed mid-write (a
        partial tempfile exists, the rename never ran) must leave the
        prior committed copy -- and in durable mode the manifest
        naming it -- untouched."""
        root = str(tmp_path / 'cold')
        cs = ColdStore(root=root, durable=durable)
        cs.put('doc-a', self._blob(b'v1'))
        spec = faults.arm('storage.save', 'permanent')
        with pytest.raises(faults.InjectedFault):
            cs.put('doc-a', self._blob(b'v2-new-bytes'))
        faults.disarm(spec)
        assert cs.get('doc-a') == self._blob(b'v1')
        # the crash evidence: a partial tempfile, strictly shorter
        tmps = [f for f in os.listdir(root) if f.endswith('.tmp')]
        assert tmps
        assert os.path.getsize(os.path.join(root, tmps[0])) \
            < len(self._blob(b'v2-new-bytes'))
        if durable:
            fresh = ColdStore(root=root, durable=True)
            assert fresh.get('doc-a') == self._blob(b'v1')

    def test_kill_between_rename_and_manifest_keeps_prior(
            self, tmp_path, monkeypatch):
        """The post-rename pre-manifest window: durable blob files are
        VERSIONED by content hash, so even after the new file landed,
        the manifest still names the intact prior copy."""
        root = str(tmp_path / 'cold')
        cs = ColdStore(root=root, durable=True)
        cs.put('doc-a', self._blob(b'v1'))

        def die(*_a, **_k):
            raise OSError('killed before the manifest write')

        monkeypatch.setattr(cs, '_write_manifest', die)
        with pytest.raises(OSError):
            cs.put('doc-a', self._blob(b'v2'))
        monkeypatch.undo()
        fresh = ColdStore(root=root, durable=True)
        assert fresh.get('doc-a') == self._blob(b'v1')

    def test_put_many_single_manifest_write(self, tmp_path):
        root = str(tmp_path / 'cold')
        cs = ColdStore(root=root, durable=True)
        cs.put_many({'doc-%d' % i: self._blob(b'%d' % i)
                     for i in range(10)})
        assert telemetry.metrics_snapshot().get(
            'storage.manifest_writes', 0) == 1
        fresh = ColdStore(root=root, durable=True)
        assert len(fresh.doc_ids()) == 10
        assert fresh.get('doc-3') == self._blob(b'3')

    def test_checksum_detects_bit_rot(self, tmp_path):
        root = str(tmp_path / 'cold')
        cs = ColdStore(root=root, durable=True)
        cs.put('doc-a', self._blob(b'a'))
        path = cs._index['doc-a'][0]
        data = bytearray(open(path, 'rb').read())
        data[5] ^= 0xff
        with open(path, 'wb') as f:
            f.write(data)
        with pytest.raises(ValueError, match='checksum'):
            cs.get('doc-a')
        assert telemetry.metrics_snapshot().get(
            'storage.checksum_failed', 0) == 1

    def test_non_durable_has_no_manifest(self, tmp_path):
        root = str(tmp_path / 'cold')
        cs = ColdStore(root=root, durable=False)
        cs.put('doc-a', self._blob(b'a'))
        assert not os.path.exists(os.path.join(root, 'manifest.amtm'))
        # a fresh non-durable store starts empty (extension of pool
        # memory, not durable storage)
        assert len(ColdStore(root=root, durable=False)) == 0


class TestEncodeSplit:
    """The CheckpointWAL satellite: save() (what WAL compaction
    records) routes through the native codec when available, and the
    native/python split is observable."""

    def _pool(self):
        pool = NativeDocPool()
        for r in range(3):
            pool.apply_batch({'d': [
                {'actor': 'a', 'seq': r + 1, 'deps': {},
                 'ops': [{'action': 'set', 'obj': ROOT, 'key': 'k',
                          'value': r}]}]})
        return pool

    def test_save_counts_native_encodes(self, monkeypatch):
        monkeypatch.setenv('AMTPU_STORAGE_NATIVE', '1')
        self._pool().save('d')
        flat = telemetry.metrics_snapshot()
        assert flat.get('storage.native_encodes', 0) >= 1
        assert flat.get('storage.python_encodes', 0) == 0

    def test_save_oracle_arm_counts_python_encodes(self, monkeypatch):
        monkeypatch.setenv('AMTPU_STORAGE_NATIVE', '0')
        self._pool().save('d')
        flat = telemetry.metrics_snapshot()
        assert flat.get('storage.python_encodes', 0) >= 1
        assert flat.get('storage.native_encodes', 0) == 0

    def test_direct_native_bindings_roundtrip(self):
        raws = [msgpack.packb({'actor': 'a', 'seq': i + 1, 'deps': {},
                               'ops': []}, use_bin_type=True)
                for i in range(10)]
        blob, n_changes, n_residual = columnar_encode_native(raws)
        assert (n_changes, n_residual) == (10, 0)
        assert columnar_decode_native(blob) == raws
