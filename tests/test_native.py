"""Differential tests for the C++ native host runtime (NativeDocPool):
its patches must equal the scalar oracle's for the same change streams,
on the CPU default and on the chip's kernel path, including msgpack
round-trips of every value type.
"""

import random

import pytest

from automerge_tpu import backend as Backend

from test_engine_differential import WorkloadGen, deliver_and_compare

ROOT_ID = '00000000-0000-0000-0000-000000000000'


def native_pool():
    from automerge_tpu.native import NativeDocPool
    return NativeDocPool()


class TestNativeBasics:
    def test_map_sets_and_dels(self):
        deliver_and_compare([
            {0: [{'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'bird',
                 'value': 'magpie'}]}]},
            {0: [{'actor': 'a', 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'bird',
                 'value': 'jay'},
                {'action': 'del', 'obj': ROOT_ID, 'key': 'bird'}]}]},
        ])

    def test_value_types_round_trip(self):
        # int, float, bool, None, str, timestamp datatype
        deliver_and_compare([{0: [{'actor': 'a', 'seq': 1, 'deps': {},
                                   'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'i', 'value': 42},
            {'action': 'set', 'obj': ROOT_ID, 'key': 'neg', 'value': -7},
            {'action': 'set', 'obj': ROOT_ID, 'key': 'big',
             'value': 2 ** 40},
            {'action': 'set', 'obj': ROOT_ID, 'key': 'f', 'value': 3.25},
            {'action': 'set', 'obj': ROOT_ID, 'key': 'b', 'value': True},
            {'action': 'set', 'obj': ROOT_ID, 'key': 'n', 'value': None},
            {'action': 'set', 'obj': ROOT_ID, 'key': 's', 'value': 'hi'},
            {'action': 'set', 'obj': ROOT_ID, 'key': 't', 'value': 1234567,
             'datatype': 'timestamp'}]}]}])

    def test_concurrent_conflict(self):
        deliver_and_compare([
            {0: [{'actor': 'a1', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                 'value': 'from-a'}]},
                {'actor': 'z9', 'seq': 1, 'deps': {}, 'ops': [
                    {'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                     'value': 'from-z'}]}]},
        ])

    def test_nested_maps_and_links(self):
        deliver_and_compare([
            {0: [{'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeMap', 'obj': 'm1'},
                {'action': 'set', 'obj': 'm1', 'key': 'x', 'value': 1},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'child',
                 'value': 'm1'}]}]},
            {0: [{'actor': 'a', 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': 'm1', 'key': 'y', 'value': 2},
                {'action': 'del', 'obj': ROOT_ID, 'key': 'child'}]}]},
        ])

    def test_out_of_order_buffering(self):
        nat = native_pool()
        ch1 = {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 1}]}
        ch2 = {'actor': 'b', 'seq': 1, 'deps': {'a': 1}, 'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 2}]}
        st = Backend.init()
        st, _ = Backend.apply_changes(st, [ch2])
        nat.apply_changes(0, [ch2])
        assert nat.get_missing_deps(0) == Backend.get_missing_deps(st)
        st, _ = Backend.apply_changes(st, [ch1, ch1])  # dup tolerated
        nat.apply_changes(0, [ch1, ch1])
        assert nat.get_patch(0) == Backend.get_patch(st)

    def test_inconsistent_seq_reuse_raises(self):
        from automerge_tpu.errors import AutomergeError
        nat = native_pool()
        nat.apply_changes(0, [{'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 1}]}])
        with pytest.raises(AutomergeError):
            nat.apply_changes(0, [{'actor': 'a', 'seq': 1, 'deps': {},
                                   'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                 'value': 999}]}])

    def test_get_changes_for_actor(self):
        nat = native_pool()
        st = Backend.init()
        chs = [
            {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 1}]},
            {'actor': 'a', 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 2}]},
            {'actor': 'b', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'j', 'value': 3}]},
        ]
        st, _ = Backend.apply_changes(st, chs)
        nat.apply_changes(0, chs)
        for actor, after in (('a', 0), ('a', 1), ('b', 0), ('zz', 0)):
            got = nat.get_changes_for_actor(0, actor, after)
            want = [dict(c) for c in chs
                    if c['actor'] == actor and c['seq'] > after]
            assert got == want, (actor, after, got)

    def test_get_missing_changes(self):
        nat = native_pool()
        st = Backend.init()
        chs = [
            {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 1}]},
            {'actor': 'a', 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 2}]},
            {'actor': 'b', 'seq': 1, 'deps': {'a': 2}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'j', 'value': 3}]},
        ]
        st, _ = Backend.apply_changes(st, chs)
        nat.apply_changes(0, chs)
        for have in ({}, {'a': 1}, {'a': 2}, {'a': 2, 'b': 1}):
            want = Backend.get_missing_changes(st, have)
            got = nat.get_missing_changes(0, have)
            assert got == want, (have, got, want)


class TestNativeLists:
    def test_text_interleaved(self):
        actor = 'actor-a'
        deliver_and_compare([
            {0: [{'actor': actor, 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeText', 'obj': 'text-1'},
                {'action': 'ins', 'obj': 'text-1', 'key': '_head',
                 'elem': 1},
                {'action': 'set', 'obj': 'text-1', 'key': '%s:1' % actor,
                 'value': 'h'},
                {'action': 'ins', 'obj': 'text-1', 'key': '%s:1' % actor,
                 'elem': 2},
                {'action': 'set', 'obj': 'text-1', 'key': '%s:2' % actor,
                 'value': 'i'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
                 'value': 'text-1'}]}]},
            {0: [{'actor': actor, 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'del', 'obj': 'text-1', 'key': '%s:1' % actor},
                {'action': 'ins', 'obj': 'text-1', 'key': '%s:1' % actor,
                 'elem': 3},
                {'action': 'set', 'obj': 'text-1', 'key': '%s:3' % actor,
                 'value': 'H'}]}]},
        ])

    def test_concurrent_same_position_inserts(self):
        deliver_and_compare([
            {0: [{'actor': 'aa', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeList', 'obj': 'list-1'},
                {'action': 'ins', 'obj': 'list-1', 'key': '_head',
                 'elem': 1},
                {'action': 'set', 'obj': 'list-1', 'key': 'aa:1',
                 'value': 'base'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'l',
                 'value': 'list-1'}]}]},
            {0: [{'actor': 'aa', 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'ins', 'obj': 'list-1', 'key': 'aa:1', 'elem': 2},
                {'action': 'set', 'obj': 'list-1', 'key': 'aa:2',
                 'value': 'from-aa'}]}]},
            {0: [{'actor': 'zz', 'seq': 1, 'deps': {'aa': 1}, 'ops': [
                {'action': 'ins', 'obj': 'list-1', 'key': 'aa:1', 'elem': 2},
                {'action': 'set', 'obj': 'list-1', 'key': 'zz:2',
                 'value': 'from-zz'}]}]},
        ])

    def test_multi_doc_batch(self):
        batches = []
        for d in range(4):
            tid = 'text-%d' % d
            batches.append({'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeText', 'obj': tid},
                {'action': 'ins', 'obj': tid, 'key': '_head', 'elem': 1},
                {'action': 'set', 'obj': tid, 'key': 'a:1',
                 'value': chr(97 + d)},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
                 'value': tid}]})
        deliver_and_compare([{d: [batches[d]] for d in range(4)}], n_docs=4)


class TestShardedPool:
    def _mk(self, n_shards=3):
        from automerge_tpu.native import ShardedNativePool
        return ShardedNativePool(n_shards)

    def test_parity_with_single_pool_many_docs(self):
        # >15 docs forces the byte-level merge across the fixmap/map16
        # header boundary; doc set spans all shards
        from automerge_tpu.native import NativeDocPool
        batch = {}
        for d in range(20):
            tid = 'text-%d' % d
            batch['doc-%d' % d] = [{'actor': 'a', 'seq': 1, 'deps': {},
                                    'ops': [
                {'action': 'makeText', 'obj': tid},
                {'action': 'ins', 'obj': tid, 'key': '_head', 'elem': 1},
                {'action': 'set', 'obj': tid, 'key': 'a:1',
                 'value': chr(97 + d % 26)},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
                 'value': tid}]}]
        single = NativeDocPool()
        sharded = self._mk(3)
        want = single.apply_batch(batch)
        got = sharded.apply_batch(batch)
        assert got == want
        for d in batch:
            assert sharded.get_patch(d) == single.get_patch(d)
            assert sharded.get_missing_deps(d) == {}

    def test_int_doc_ids_route_consistently(self):
        sharded = self._mk(4)
        sharded.apply_changes(7, [{'actor': 'a', 'seq': 1, 'deps': {},
                                   'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 1}]}])
        assert sharded.get_patch(7)['clock'] == {'a': 1}

    def test_empty_payload(self):
        import msgpack
        sharded = self._mk(2)
        out = sharded.apply_batch_bytes(msgpack.packb({}))
        assert msgpack.unpackb(out, raw=False) == {}

    def test_invalid_shard_count(self):
        from automerge_tpu.native import ShardedNativePool
        with pytest.raises(ValueError):
            ShardedNativePool(0)

    def test_python_cpp_routing_parity(self):
        from automerge_tpu.native import lib
        sharded = self._mk(5)
        for d in ('a', 'doc-42', 'i:7', 'long-document-name-xyz'):
            key = d.encode()
            assert sharded._shard_of(d) == \
                int(lib().amtpu_doc_shard(key, len(key), 5))


class TestNativeRandomWorkloads:
    @pytest.mark.parametrize('seed,structure', [
        (1, 'map'), (3, 'list'), (5, 'mixed'), (6, 'mixed'),
    ])
    def test_in_order_delivery(self, seed, structure):
        changes = WorkloadGen(seed, structure=structure).generate(20)
        deliver_and_compare([{0: [c]} for c in changes])

    @pytest.mark.parametrize('seed', [11, 13])
    def test_shuffled_delivery(self, seed):
        rng = random.Random(seed)
        changes = WorkloadGen(seed, structure='mixed').generate(16)
        shuffled = list(changes)
        rng.shuffle(shuffled)
        deliver_and_compare([{0: shuffled}])

    @pytest.mark.parametrize('seed', [21, 22])
    def test_batched_delivery(self, seed):
        rng = random.Random(seed)
        changes = WorkloadGen(seed, structure='mixed').generate(24)
        batches = []
        i = 0
        while i < len(changes):
            n = rng.randint(1, 6)
            batches.append({0: changes[i:i + n]})
            i += n
        deliver_and_compare(batches)


class TestSingleLargeDoc:
    def test_long_sequential_text(self):
        """One Text doc with thousands of sequential inserts from two
        alternating actors (BASELINE config-1 shape, scaled down): the
        big-arena size classes and cross-change dependency chains."""
        nat = native_pool()
        st = Backend.init()
        chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeText', 'obj': 't'},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
             'value': 't'}]}]
        last = '_head'
        seqs = {'a0': 1, 'a1': 0}
        e = 0
        while e < 1200:
            for a in ('a0', 'a1'):
                ops = []
                for _ in range(50):
                    e += 1
                    ops.append({'action': 'ins', 'obj': 't', 'key': last,
                                'elem': e})
                    ops.append({'action': 'set', 'obj': 't',
                                'key': '%s:%d' % (a, e),
                                'value': chr(97 + e % 26)})
                    last = '%s:%d' % (a, e)
                seqs[a] += 1
                chs.append({'actor': a, 'seq': seqs[a],
                            'deps': {k: v for k, v in seqs.items()
                                     if k != a and v > 0},
                            'ops': ops})
        st, _ = Backend.apply_changes(st, chs)
        nat.apply_changes('big', chs)
        assert nat.get_patch('big') == Backend.get_patch(st)


class TestQueryConstLookup:
    """Queries on unknown doc ids must not materialize pool state
    (round-2 advisor finding: phantom DocState on typo'd ids)."""

    QUERIES = [
        lambda p: p.get_patch('no-such-doc'),
        lambda p: p.get_clock('no-such-doc'),
        lambda p: p.get_missing_deps('no-such-doc'),
        lambda p: p.get_missing_changes('no-such-doc', {'a0': 1}),
        lambda p: p.get_changes_for_actor('no-such-doc', 'a0'),
        lambda p: p.save('no-such-doc'),
    ]

    def _exercise(self, pool, doc_count):
        pool.apply_changes('real', [{'actor': 'a0', 'seq': 1, 'deps': {},
                                     'ops': [{'action': 'set',
                                              'obj': ROOT_ID, 'key': 'x',
                                              'value': 1}]}])
        for q in self.QUERIES:
            q(pool)
        assert doc_count(pool) == 1
        # and the real doc still answers correctly
        patch = pool.get_patch('real')
        assert patch['clock'] == {'a0': 1}

    @pytest.mark.parametrize('pool_lane', ['kernel'], indirect=True)
    def test_kernel_lane_pool(self, pool_lane):
        self._exercise(pool_lane(), lambda p: p.doc_count())

    def test_native_pool(self):
        from automerge_tpu.native import NativeDocPool
        pool = NativeDocPool()
        self._exercise(pool, lambda p: p.doc_count())

    def test_sharded_pool(self):
        from automerge_tpu.native import ShardedNativePool
        pool = ShardedNativePool(4)
        self._exercise(pool, lambda p: sum(s.doc_count()
                                           for s in p.pools))

    @pytest.mark.parametrize('pool_lane', ['kernel'], indirect=True)
    def test_unknown_doc_patch_is_empty(self, pool_lane):
        pool = pool_lane()
        patch = pool.get_patch('ghost')
        assert patch['clock'] == {} and patch['deps'] == {}
        assert pool.doc_count() == 0


class TestBatchHandleLeaks:
    """Phase-a failures after amtpu_begin must free the C++ batch handle
    (each handle owns the whole decoded batch; leaking under sustained
    error traffic is unbounded growth).  live_batch_handles() is the
    audit hook: every begin increments, every free decrements."""

    def _simple_batch(self):
        return {0: [{'actor': 'a0', 'seq': 1, 'deps': {},
                     'ops': [{'action': 'set', 'obj': ROOT_ID,
                              'key': 'k', 'value': 1}]}]}

    def test_success_path_balances(self):
        from automerge_tpu import native
        base = native.live_batch_handles()
        pool = native.NativeDocPool()
        pool.apply_batch(self._simple_batch())
        assert native.live_batch_handles() == base

    def test_phase_a_failure_frees_handle(self):
        """AMTPU_WEFF with a non-numeric value raises inside
        _phase_a_rest AFTER begin succeeded -- exactly the window where
        a leak would hide."""
        import os
        from automerge_tpu import native
        base = native.live_batch_handles()
        pool = native.NativeDocPool()
        prior = os.environ.get('AMTPU_WEFF')
        os.environ['AMTPU_WEFF'] = 'bogus'
        try:
            with pytest.raises(ValueError):
                pool.apply_batch(self._simple_batch())
        finally:
            if prior is None:
                os.environ.pop('AMTPU_WEFF', None)
            else:
                os.environ['AMTPU_WEFF'] = prior
        assert native.live_batch_handles() == base
        # the pool is still serviceable after the failed batch
        pool.apply_batch(self._simple_batch())
        assert native.live_batch_handles() == base

    def test_pipelined_phase_a_failure_frees_all(self):
        """The pipelined driver collects phase-a errors across pools;
        every handle -- failed and healthy alike -- must be freed."""
        import os
        from automerge_tpu import native
        base = native.live_batch_handles()
        import msgpack
        payload = msgpack.packb(
            {native.NativeDocPool._doc_key(0):
             self._simple_batch()[0]}, use_bin_type=True)
        pools = [native.NativeDocPool() for _ in range(3)]
        prior = os.environ.get('AMTPU_WEFF')
        os.environ['AMTPU_WEFF'] = 'bogus'
        try:
            with pytest.raises(ValueError):
                native.apply_payloads_pipelined(
                    [(p, payload) for p in pools])
        finally:
            if prior is None:
                os.environ.pop('AMTPU_WEFF', None)
            else:
                os.environ['AMTPU_WEFF'] = prior
        assert native.live_batch_handles() == base


class TestShardErrorReporting:
    def test_error_names_failing_shard(self):
        from automerge_tpu.native import ShardedNativePool
        pool = ShardedNativePool(4)
        bad = {'d%d' % i: [{'actor': 'a0', 'seq': 1, 'deps': {},
                            'ops': [{'action': 'set', 'obj': ROOT_ID,
                                     'key': 'k', 'value': i}]}]
               for i in range(8)}
        # one doc carries an inconsistent seq reuse -> its shard errors
        victim = 'd3'
        bad[victim] = [
            {'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': []},
            {'actor': 'a0', 'seq': 1, 'deps': {},
             'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                      'value': 9}]},
        ]
        shard = pool._shard_of(victim)
        with pytest.raises(Exception) as ei:
            pool.apply_batch(bad)
        assert '[shard %d]' % shard in str(ei.value)


def test_wide_overflow_register_conflicts_emit_correctly():
    """20 concurrent writers on one key exceed both the register window
    (host-oracle fallback) and the fixarray conflicts bound (>15
    entries) -- the diff stream must stay valid msgpack and match the
    oracle byte for byte (round-3 regression: the stack fast path must
    reject such registers)."""
    nat = native_pool()
    st = Backend.init()
    chs = [{'actor': 'w%02d' % a, 'seq': 1, 'deps': {},
            'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'hot',
                     'value': 'v%d' % a}]}
           for a in range(20)]
    nat.apply_changes('doc', chs)
    st, _ = Backend.apply_changes(st, chs)
    patch = nat.get_patch('doc')
    assert patch == Backend.get_patch(st)
    final = [d for d in patch['diffs'] if d.get('key') == 'hot'][-1]
    assert len(final['conflicts']) == 19


def test_duplicate_actor_seq_after_ops_last_wins():
    """Malformed envelope repeating 'actor'/'seq' with DIFFERENT values
    AFTER the 'ops' key: the inline-decoded ops must be re-stamped with
    the final (last-wins) values, matching the span-reparse path and JS
    object semantics -- previously they kept the stale attribution."""
    import msgpack

    from automerge_tpu.native import NativeDocPool
    ops = [{'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 7}]
    # canonical reference: the change as a JS object would decode it
    ref = native_pool()
    ref_patch = ref.apply_changes('doc', [
        {'actor': 'zzz', 'seq': 1, 'deps': {}, 'ops': ops}])

    # malformed wire form: actor 'aaa' triggers inline op decode, then
    # 'actor'/'seq' repeat after 'ops' with the values that must win
    body = (msgpack.packb('actor') + msgpack.packb('aaa') +
            msgpack.packb('seq') + msgpack.packb(9) +
            msgpack.packb('deps') + msgpack.packb({}) +
            msgpack.packb('ops') + msgpack.packb(ops) +
            msgpack.packb('actor') + msgpack.packb('zzz') +
            msgpack.packb('seq') + msgpack.packb(1))
    change = b'\x86' + body                        # fixmap, 6 pairs
    key = NativeDocPool._doc_key('doc')
    payload = (b'\x81' + msgpack.packb(key) +      # {doc: [change]}
               b'\x91' + change)
    nat = native_pool()
    got = msgpack.unpackb(nat.apply_batch_bytes(payload), raw=False)[key]
    assert got == ref_patch
    assert got['clock'] == {'zzz': 1}
    # the register record itself carries the re-stamped attribution
    reg = nat.get_register('doc', ROOT_ID, 'k')
    assert [(r['actor'], r['seq']) for r in reg] == [('zzz', 1)]


class TestHostDominanceParity:
    """A/B parity between the two dominance implementations: the device
    kernel (`ops/pallas_dominance.py` / the fused dispatch) and the C++
    Fenwick sweep (`amtpu_host_dominance`), which the driver selects
    per-platform (AMTPU_HOST_DOM; default host on the CPU backend).  The
    env knob is read per BATCH, so one process can drive both paths on
    identical inputs and require byte-identical patch streams."""

    def _run(self, batches, hostdom):
        import os
        prior = os.environ.get('AMTPU_HOST_DOM')
        prior_full = os.environ.get('AMTPU_HOST_FULL')
        os.environ['AMTPU_HOST_DOM'] = hostdom
        # the A/B here is device-dominance vs Fenwick-mid: both need the
        # KERNEL dispatch, which host-full (the CPU default) skips
        os.environ['AMTPU_HOST_FULL'] = '0'
        try:
            pool = native_pool()
            out = [pool.apply_batch(b) for b in batches]
            out.append(pool.get_patch(0))
            return out
        finally:
            if prior is None:
                os.environ.pop('AMTPU_HOST_DOM', None)
            else:
                os.environ['AMTPU_HOST_DOM'] = prior
            if prior_full is None:
                os.environ.pop('AMTPU_HOST_FULL', None)
            else:
                os.environ['AMTPU_HOST_FULL'] = prior_full

    @pytest.mark.parametrize('seed,structure', [
        (31, 'list'), (32, 'mixed'), (33, 'mixed'),
    ])
    def test_ab_identical_random(self, seed, structure):
        changes = WorkloadGen(seed, structure=structure).generate(24)
        rng = random.Random(seed)
        batches = []
        i = 0
        while i < len(changes):
            n = rng.randint(1, 6)
            batches.append({0: changes[i:i + n]})
            i += n
        assert self._run(batches, '1') == self._run(batches, '0')

    def test_ab_identical_interleaved_delete(self, ):
        """Concurrent insert/delete on one text: visibility deltas hit
        the Fenwick sweep's -1 path and the remove-index bookkeeping."""
        chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeText', 'obj': 't'},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
             'value': 't'}]}]
        last = '_head'
        e = 0
        live = []
        rng = random.Random(99)
        for seq in range(2, 12):
            ops = []
            for _ in range(20):
                if live and rng.random() < 0.3:
                    victim = live.pop(rng.randrange(len(live)))
                    ops.append({'action': 'del', 'obj': 't',
                                'key': victim})
                else:
                    e += 1
                    ops.append({'action': 'ins', 'obj': 't', 'key': last,
                                'elem': e})
                    ops.append({'action': 'set', 'obj': 't',
                                'key': 'a0:%d' % e, 'value': 'x'})
                    last = 'a0:%d' % e
                    live.append(last)
            chs.append({'actor': 'a0', 'seq': seq, 'deps': {},
                        'ops': ops})
        batches = [{0: [c]} for c in chs]
        a = self._run(batches, '1')
        b = self._run(batches, '0')
        assert a == b
        # and both equal the scalar oracle
        st = Backend.init()
        st, _ = Backend.apply_changes(st, chs)
        assert a[-1] == Backend.get_patch(st)

    @pytest.mark.parametrize('hostdom', ['1', '0'])
    def test_overflow_fallback_under_both_dominance_modes(self, hostdom):
        """The fused overflow -> oracle fallback with LIST dominance
        work, under both dominance modes.  The dynamic window makes
        saturation unreachable in normal operation, so AMTPU_WEFF=2
        forces a 2-wide window against 5 concurrent writers per element:
        the kernel flags overflow, amtpu_mid re-resolves the groups with
        the host oracle, and indexes come from the device kernel
        (hostdom=0) or the Fenwick sweep consuming host_registers
        (hostdom=1).  Both must match the scalar oracle byte-for-byte."""
        import os
        chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeList', 'obj': 'l'},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'list',
             'value': 'l'},
            {'action': 'ins', 'obj': 'l', 'key': '_head', 'elem': 1},
            {'action': 'set', 'obj': 'l', 'key': 'a0:1', 'value': 'base'},
            {'action': 'ins', 'obj': 'l', 'key': 'a0:1', 'elem': 2},
            {'action': 'set', 'obj': 'l', 'key': 'a0:2', 'value': 'two'},
        ]}]
        # 5 concurrent writers on BOTH elements (wide register groups on
        # list element keys -> window overflow at weff=2)
        for a in range(1, 6):
            chs.append({'actor': 'w%d' % a, 'seq': 1, 'deps': {'a0': 1},
                        'ops': [
                {'action': 'set', 'obj': 'l', 'key': 'a0:1',
                 'value': 'w%d-1' % a},
                {'action': 'set', 'obj': 'l', 'key': 'a0:2',
                 'value': 'w%d-2' % a} if a != 3 else
                {'action': 'del', 'obj': 'l', 'key': 'a0:2'},
            ]})
        st = Backend.init()
        st, _ = Backend.apply_changes(st, chs)

        prior = {k: os.environ.get(k)
                 for k in ('AMTPU_WEFF', 'AMTPU_HOST_DOM',
                           'AMTPU_HOST_FULL')}
        os.environ['AMTPU_WEFF'] = '2'
        os.environ['AMTPU_HOST_DOM'] = hostdom
        os.environ['AMTPU_HOST_FULL'] = '0'   # overflow needs the kernel
        try:
            from automerge_tpu import trace
            trace.metrics_reset()
            pool = native_pool()
            # deliver concurrent writers as ONE batch so the register
            # rows coexist in one dispatch
            pool.apply_batch({0: [chs[0]]})
            pool.apply_batch({0: chs[1:]})
            assert pool.get_patch(0) == Backend.get_patch(st)
            m = trace.metrics_snapshot()
            assert m.get('fallback.overflow_batches', 0) >= 1, m
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


class TestHostRegisterMode:
    """Host-register mode (amtpu_mid_hostreg): map-only batches whose
    register groups are mostly wider than the member window skip the
    kernel dispatch entirely and resolve at emit against the live
    mirror.  A/B vs the member-kernel + scratch-oracle path
    (AMTPU_HOST_REG=0) and vs the scalar oracle."""

    def _drive(self, batches, hostreg):
        import os
        prior = os.environ.get('AMTPU_HOST_REG')
        prior_full = os.environ.get('AMTPU_HOST_FULL')
        os.environ['AMTPU_HOST_REG'] = hostreg
        # hostreg-vs-kernel A/B: both sides run the member build, which
        # host-full (the CPU default) skips entirely
        os.environ['AMTPU_HOST_FULL'] = '0'
        try:
            from automerge_tpu import trace
            trace.metrics_reset()
            pool = native_pool()
            out = [pool.apply_batch(b) for b in batches]
            out.append(pool.get_patch(0))
            engaged = trace.metrics_snapshot().get('hostreg.batches', 0)
            if hostreg == '1':
                # the gate must actually fire, else the A/B is vacuous
                assert engaged > 0, 'hostreg gate never engaged'
            else:
                assert engaged == 0, 'hostreg ran despite AMTPU_HOST_REG=0'
            return out
        finally:
            if prior is None:
                os.environ.pop('AMTPU_HOST_REG', None)
            else:
                os.environ['AMTPU_HOST_REG'] = prior
            if prior_full is None:
                os.environ.pop('AMTPU_HOST_FULL', None)
            else:
                os.environ['AMTPU_HOST_FULL'] = prior_full

    def test_wide_groups_incremental_with_deletes(self):
        rng = random.Random(41)
        changes = []
        # 14 concurrent writers x 4 sequential changes each over a
        # shared 6-key space, with deletes -- every group wider than
        # the W=8 member window
        for seq in range(1, 5):
            for a in range(14):
                ops = []
                for k in rng.sample(range(6), 4):
                    if rng.random() < 0.2:
                        ops.append({'action': 'del', 'obj': ROOT_ID,
                                    'key': 'k%d' % k})
                    else:
                        ops.append({'action': 'set', 'obj': ROOT_ID,
                                    'key': 'k%d' % k,
                                    'value': 'w%02d-%d' % (a, seq)})
                changes.append({'actor': 'w%02d' % a, 'seq': seq,
                                'deps': {}, 'ops': ops})
        # incremental delivery in writer-interleaved order
        batches = []
        i = 0
        while i < len(changes):
            n = rng.randint(3, 9)
            batches.append({0: changes[i:i + n]})
            i += n
        on = self._drive(batches, '1')
        off = self._drive(batches, '0')
        assert on == off
        st = Backend.init()
        for b in batches:
            st, _ = Backend.apply_changes(st, b[0])
        assert on[-1] == Backend.get_patch(st)


class TestHostFullParity:
    """Full host path (the CPU-backend default) vs the kernel path:
    byte-identical patch streams on identical inputs, including list
    dominance (the in-emit Fenwick) and interleaved deletes."""

    def _drive(self, batches, hostfull):
        import os
        prior = os.environ.get('AMTPU_HOST_FULL')
        os.environ['AMTPU_HOST_FULL'] = hostfull
        try:
            from automerge_tpu import trace
            trace.metrics_reset()
            pool = native_pool()
            out = [pool.apply_batch(b) for b in batches]
            out.append(pool.get_patch(0))
            engaged = trace.metrics_snapshot().get('hostfull.batches', 0)
            if hostfull == '1':
                assert engaged > 0, 'hostfull gate never engaged'
            else:
                assert engaged == 0, 'hostfull ran despite =0'
            return out
        finally:
            if prior is None:
                os.environ.pop('AMTPU_HOST_FULL', None)
            else:
                os.environ['AMTPU_HOST_FULL'] = prior

    @pytest.mark.parametrize('seed,structure', [
        (51, 'list'), (52, 'mixed'), (53, 'map'),
    ])
    def test_ab_identical(self, seed, structure):
        changes = WorkloadGen(seed, structure=structure).generate(28)
        rng = random.Random(seed)
        batches = []
        i = 0
        while i < len(changes):
            n = rng.randint(1, 6)
            chunk = list(changes[i:i + n])
            if rng.random() < 0.3:
                rng.shuffle(chunk)
            batches.append({0: chunk})
            i += n
        a = self._drive(batches, '1')
        b = self._drive(batches, '0')
        assert a == b
        # and the scalar oracle agrees
        st = Backend.init()
        for batch in batches:
            st, _ = Backend.apply_changes(st, [dict(c) for c in batch[0]])
        assert a[-1] == Backend.get_patch(st)

    def test_undo_redo_under_hostfull(self):
        import os
        prior = os.environ.get('AMTPU_HOST_FULL')
        os.environ['AMTPU_HOST_FULL'] = '1'
        try:
            pool = native_pool()
            st = Backend.init()
            reqs = [
                {'requestType': 'change', 'actor': 'me', 'seq': 1,
                 'deps': {}, 'ops': [
                     {'action': 'makeList', 'obj': 'l'},
                     {'action': 'link', 'obj': ROOT_ID, 'key': 'xs',
                      'value': 'l'},
                     {'action': 'ins', 'obj': 'l', 'key': '_head',
                      'elem': 1},
                     {'action': 'set', 'obj': 'l', 'key': 'me:1',
                      'value': 'a'}]},
                {'requestType': 'change', 'actor': 'me', 'seq': 2,
                 'deps': {}, 'ops': [
                     {'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                      'value': 1}]},
                {'requestType': 'undo', 'actor': 'me', 'seq': 3,
                 'deps': {}},
                {'requestType': 'redo', 'actor': 'me', 'seq': 4,
                 'deps': {}},
            ]
            for r in reqs:
                st, want = Backend.apply_local_change(st, dict(r))
                got = pool.apply_local_change(0, dict(r))
                assert got == want, r['requestType']
            assert pool.get_patch(0) == Backend.get_patch(st)
        finally:
            if prior is None:
                os.environ.pop('AMTPU_HOST_FULL', None)
            else:
                os.environ['AMTPU_HOST_FULL'] = prior
