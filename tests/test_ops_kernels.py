"""Differential tests of the TPU kernels against the scalar oracle --
the kernel analogue of the reference's shadow-oracle property tests
(`/root/reference/test/skip_list_test.js:171-224`).
"""

import random

import numpy as np
import pytest

import automerge_tpu.backend.op_set as OpSet
from automerge_tpu.backend import init as backend_init
from automerge_tpu.ops.clock import (NOT_APPLIED, schedule_queue,
                                     schedule_queue_batch)
from automerge_tpu.ops.list_rank import (ceil_log2, dominance_indexes,
                                         linearize)

ROOT_ID = '00000000-0000-0000-0000-000000000000'


def oracle_schedule(clock, changes):
    """Reference fixpoint loop (op_set.js:279-295) over (actor, seq, deps)."""
    clock = dict(clock)
    order = {}
    counter = 0
    queue = list(range(len(changes)))
    while True:
        next_queue = []
        progress = False
        for i in queue:
            actor, seq, deps = changes[i]
            deps = dict(deps)
            deps[actor] = seq - 1
            if all(clock.get(a, 0) >= s for a, s in deps.items()):
                if seq <= clock.get(actor, 0):
                    order[i] = -2  # duplicate
                else:
                    clock[actor] = seq
                    order[i] = counter
                    counter += 1
                progress = True
            else:
                next_queue.append(i)
        queue = next_queue
        if not progress:
            return order, clock


class TestScheduler:
    def run_case(self, n_actors, clock0, changes):
        A = n_actors
        C = len(changes)
        actor = np.full((C,), -1, np.int32)
        seq = np.zeros((C,), np.int32)
        deps = np.zeros((C, A), np.int32)
        for i, (a, s, d) in enumerate(changes):
            actor[i] = a
            seq[i] = s
            for da, ds in d.items():
                deps[i, da] = ds
        clock = np.zeros((A,), np.int32)
        for a, s in clock0.items():
            clock[a] = s
        order, new_clock = schedule_queue(
            clock, actor, seq, deps, np.ones((C,), bool))
        order = np.asarray(order)
        new_clock = np.asarray(new_clock)

        expect_order, expect_clock = oracle_schedule(clock0, changes)
        for i in range(C):
            exp = expect_order.get(i)
            if exp is None:
                assert order[i] == int(NOT_APPLIED), (i, order[i])
            else:
                assert order[i] == exp, (i, order[i], exp)
        for a in range(A):
            assert new_clock[a] == expect_clock.get(a, 0)

    def test_in_order_single_actor(self):
        self.run_case(2, {}, [(0, 1, {}), (0, 2, {}), (0, 3, {})])

    def test_out_of_order_buffering(self):
        # seq 3 and 2 arrive before seq 1: two fixpoint passes needed
        self.run_case(2, {}, [(0, 3, {}), (0, 2, {}), (0, 1, {})])

    def test_cross_actor_deps(self):
        self.run_case(3, {}, [
            (1, 1, {0: 1}),   # blocked until actor0 seq1
            (0, 1, {}),
            (2, 1, {0: 1, 1: 1}),
        ])

    def test_duplicates_and_unresolvable(self):
        self.run_case(2, {0: 2}, [
            (0, 1, {}),          # duplicate (already applied)
            (0, 3, {}),          # fresh
            (1, 5, {}),          # gap: never ready (seq 1..4 missing)
        ])

    def test_random_schedules(self):
        rng = random.Random(7)
        for trial in range(25):
            A = rng.randint(1, 4)
            # build a valid causal history, then deliver in random order
            clocks = {a: 0 for a in range(A)}
            changes = []
            frontier = {}
            for _ in range(rng.randint(1, 24)):
                a = rng.randrange(A)
                clocks[a] += 1
                deps = {da: ds for da, ds in frontier.items() if da != a}
                changes.append((a, clocks[a], deps))
                frontier = {da: max(frontier.get(da, 0), ds)
                            for da, ds in list(frontier.items())
                            + [(a, clocks[a])]}
            rng.shuffle(changes)
            self.run_case(A, {}, changes)

    def test_vmapped_batch(self):
        A, C, D = 3, 4, 5
        actor = np.zeros((D, C), np.int32)
        seq = np.tile(np.arange(1, C + 1, dtype=np.int32), (D, 1))
        deps = np.zeros((D, C, A), np.int32)
        clock = np.zeros((D, A), np.int32)
        valid = np.ones((D, C), bool)
        order, new_clock = schedule_queue_batch(clock, actor, seq, deps, valid)
        assert np.all(np.asarray(order) == np.arange(C))
        assert np.all(np.asarray(new_clock)[:, 0] == C)


def build_forest_via_oracle(rng, n_ops, n_actors=3):
    """Random interleaved inserts through the oracle; returns the oracle's
    linear order and the columnar forest encoding."""
    state = backend_init()
    opset = state['opSet']
    opset = opset.copy_with_gen(1)

    actors = ['actor%d' % i for i in range(n_actors)]
    list_id = 'list-1'
    OpSet.apply_make(opset, {'action': 'makeList', 'obj': list_id})

    elems = []          # (elem_id, ctr, actor_rank, parent_elem_id)
    max_elem = 0
    for i in range(n_ops):
        a = rng.randrange(n_actors)
        max_elem += 1
        parent = '_head' if not elems or rng.random() < 0.2 else \
            rng.choice(elems)[0]
        op = {'action': 'ins', 'obj': list_id, 'key': parent,
              'elem': max_elem, 'actor': actors[a], 'seq': 1}
        OpSet.apply_insert(opset, op)
        elems.append(('%s:%d' % (actors[a], max_elem), max_elem, a, parent))

    # oracle linear order: walk get_next from _head
    oracle_order = []
    key = '_head'
    while True:
        key = OpSet.get_next(opset, list_id, key)
        if key is None:
            break
        oracle_order.append(key)
    return elems, oracle_order


class TestLinearize:
    @pytest.mark.parametrize('n_ops,seed', [(1, 0), (5, 1), (30, 2), (100, 3),
                                            (100, 4), (250, 5)])
    def test_matches_oracle_walk(self, n_ops, seed):
        rng = random.Random(seed)
        elems, oracle_order = build_forest_via_oracle(rng, n_ops)
        L = len(elems)
        index_of = {e[0]: i for i, e in enumerate(elems)}
        obj = np.zeros((L,), np.int32)
        parent = np.array([index_of.get(e[3], -1) for e in elems], np.int32)
        ctr = np.array([e[1] for e in elems], np.int32)
        actor = np.array([e[2] for e in elems], np.int32)
        valid = np.ones((L,), bool)
        rank = np.asarray(linearize(obj, parent, ctr, actor, valid,
                                    n_iters=ceil_log2(L) + 1))
        got_order = [None] * L
        for i in range(L):
            got_order[rank[i]] = elems[i][0]
        assert got_order == oracle_order

    def test_multiple_objects(self):
        # two independent lists in one arena: obj 0 has a->b, obj 1 has c
        obj = np.array([0, 0, 1], np.int32)
        parent = np.array([-1, 0, -1], np.int32)
        ctr = np.array([1, 2, 1], np.int32)
        actor = np.array([0, 0, 0], np.int32)
        valid = np.ones((3,), bool)
        rank = np.asarray(linearize(obj, parent, ctr, actor, valid, n_iters=3))
        assert rank.tolist() == [0, 1, 0]

    def test_padding_rows(self):
        obj = np.array([0, 0, 0, 0], np.int32)
        parent = np.array([-1, 0, -1, -1], np.int32)
        ctr = np.array([1, 2, 7, 9], np.int32)
        actor = np.array([0, 0, 0, 0], np.int32)
        valid = np.array([True, True, False, False])
        rank = np.asarray(linearize(obj, parent, ctr, actor, valid, n_iters=3))
        assert rank[0] == 0 and rank[1] == 1
        assert rank[2] == -1 and rank[3] == -1


class TestDominanceIndexes:
    def test_against_bruteforce(self):
        rng = random.Random(11)
        for trial in range(10):
            L = rng.randint(1, 40)
            T = rng.randint(1, 60)
            n_objs = rng.randint(1, 3)
            elem_obj = np.array([rng.randrange(n_objs) for _ in range(L)],
                                np.int32)
            # unique ranks per object
            elem_rank = np.zeros((L,), np.int32)
            for o in range(n_objs):
                idxs = [i for i in range(L) if elem_obj[i] == o]
                for r, i in enumerate(rng.sample(idxs, len(idxs))):
                    elem_rank[i] = r
            vis = np.array([rng.random() < 0.5 for _ in range(L)], np.float32)
            vis0 = vis.copy()

            op_elem = np.zeros((T,), np.int32)
            op_delta = np.zeros((T,), np.int32)
            expect = np.zeros((T,), np.int32)
            vis_state = vis.copy()
            for t in range(T):
                e = rng.randrange(L)
                op_elem[t] = e
                expect[t] = int(sum(
                    vis_state[i] for i in range(L)
                    if elem_obj[i] == elem_obj[e]
                    and elem_rank[i] < elem_rank[e]))
                if vis_state[e] > 0 and rng.random() < 0.5:
                    op_delta[t] = -1
                elif vis_state[e] == 0 and rng.random() < 0.7:
                    op_delta[t] = 1
                vis_state[e] += op_delta[t]

            got = np.asarray(dominance_indexes(
                elem_obj, elem_rank, vis0,
                op_elem, elem_obj[op_elem], elem_rank[op_elem],
                op_delta, np.ones((T,), bool), chunk=8))
            assert got.tolist() == expect.tolist(), trial

    def test_grouped_matches_flat(self):
        """dominance_grouped == dominance_indexes on random single-object
        batches (the grouped kernel's batch axis IS the object axis)."""
        from automerge_tpu.ops.list_rank import dominance_grouped
        rng = random.Random(23)
        K = 8
        n_objs = 4
        Lp, Tp = 32, 24
        v0 = np.zeros((n_objs, Lp), np.float32)
        er = np.full((n_objs, Lp), -1, np.int32)
        oe = np.full((n_objs, Tp), -1, np.int32)
        orank = np.full((n_objs, Tp), -1, np.int32)
        od = np.zeros((n_objs, Tp), np.int32)
        ov = np.zeros((n_objs, Tp), bool)
        expect = np.zeros((n_objs, Tp), np.int32)
        for o in range(n_objs):
            L = rng.randint(1, Lp)
            T = rng.randint(1, Tp)
            ranks = list(range(L))
            rng.shuffle(ranks)
            er[o, :L] = ranks
            vis = np.array([rng.random() < 0.5 for _ in range(L)],
                           np.float32)
            v0[o, :L] = vis
            vis_state = vis.copy()
            for t in range(T):
                e = rng.randrange(L)
                oe[o, t] = e
                orank[o, t] = er[o, e]
                ov[o, t] = True
                expect[o, t] = int(sum(
                    vis_state[i] for i in range(L)
                    if er[o, i] < er[o, e]))
                if vis_state[e] > 0 and rng.random() < 0.5:
                    od[o, t] = -1
                elif vis_state[e] == 0 and rng.random() < 0.7:
                    od[o, t] = 1
                vis_state[e] += od[o, t]
        got = np.asarray(dominance_grouped(v0, er, oe, orank, od, ov,
                                           chunk=K))
        assert (got[ov] == expect[ov]).all()


class TestRegisters:
    def test_lww_partition_and_conflicts(self):
        from automerge_tpu.ops.registers import resolve_registers
        # actors A(0), B(1), C(2).  A1 and B1 set key k concurrently;
        # C1 (deps A:1, B:1) overwrites both; A2 (deps C:1) deletes.
        A = 3
        T = 4
        group = np.zeros((T,), np.int32)
        time = np.arange(T, dtype=np.int32)
        actor = np.array([0, 1, 2, 0], np.int32)
        seq = np.array([1, 1, 1, 2], np.int32)
        clock = np.zeros((T, A), np.int32)
        clock[2] = [1, 1, 0]            # C1 allDeps
        clock[3] = [1, 1, 1]            # A2 allDeps
        is_del = np.array([False, False, False, True])
        out = resolve_registers(group, time, actor, seq, clock, is_del,
                                np.ones((T,), bool))
        alive = np.asarray(out['alive_after'])
        winner = np.asarray(out['winner'])
        conflicts = np.asarray(out['conflicts'])
        visible_before = np.asarray(out['visible_before'])
        assert alive.tolist() == [1, 2, 1, 0]
        assert winner.tolist() == [0, 1, 2, -1]
        # after B1: both alive, winner B (higher actor), conflict = A's op
        assert conflicts[1, 0] == 0 and conflicts[1, 1] == -1
        assert visible_before.tolist() == [False, True, True, True]
        assert not np.asarray(out['overflow']).any()

    def test_state_ops_superseded(self):
        from automerge_tpu.ops.registers import resolve_registers
        # state op (B, 1) persisted from a previous batch at time -1;
        # batch op (A, 2) with allDeps covering B:1 supersedes it.
        A = 2
        group = np.zeros((2,), np.int32)
        time = np.array([-1, 0], np.int32)
        actor = np.array([1, 0], np.int32)
        seq = np.array([1, 2], np.int32)
        clock = np.array([[0, 0], [1, 1]], np.int32)
        is_del = np.zeros((2,), bool)
        out = resolve_registers(group, time, actor, seq, clock, is_del,
                                np.ones((2,), bool))
        assert np.asarray(out['alive_after']).tolist() == [1, 1]
        assert np.asarray(out['winner']).tolist() == [0, 1]
        assert np.asarray(out['visible_before']).tolist() == [False, True]

    def test_concurrent_state_and_batch(self):
        from automerge_tpu.ops.registers import resolve_registers
        # state op (B, 1); batch op (A, 1) concurrent -> conflict set of 2,
        # winner is B (higher actor rank)
        A = 2
        group = np.zeros((2,), np.int32)
        time = np.array([-1, 0], np.int32)
        actor = np.array([1, 0], np.int32)
        seq = np.array([1, 1], np.int32)
        clock = np.zeros((2, A), np.int32)
        is_del = np.zeros((2,), bool)
        out = resolve_registers(group, time, actor, seq, clock, is_del,
                                np.ones((2,), bool))
        assert np.asarray(out['alive_after']).tolist() == [1, 2]
        assert np.asarray(out['winner']).tolist() == [0, 0]  # B's op index 0
        assert np.asarray(out['conflicts'])[1, 0] == 1       # A's op loses


class TestEscalationLadder:
    """escalate_overflow must equal an exact wide sliding-window dispatch
    on every overflowed group -- for antichain widths spanning several
    tiers (9, 15, 17, 33, 100+ concurrent live writers) and for the one
    shape member windows alone cannot hold (same-change dup assigns)."""

    def _concurrent_group(self, n_writers, base_time=0, gid=0, A=None):
        """Rows of one group: n_writers fully concurrent single-seq
        writers (empty clocks)."""
        rows = []
        for i in range(n_writers):
            rows.append((gid, base_time + i, i, 1, False))
        return rows

    def _dispatch(self, rows, A, dels=()):
        T = len(rows)
        group = np.array([r[0] for r in rows], np.int32)
        time = np.array([r[1] for r in rows], np.int32)
        actor = np.array([r[2] for r in rows], np.int32)
        seq = np.array([r[3] for r in rows], np.int32)
        is_del = np.array([r[4] for r in rows], bool)
        ctab = np.zeros((T, A), np.int32)
        cidx = np.arange(T, dtype=np.int32)
        return group, time, actor, seq, is_del, ctab, cidx

    @pytest.mark.parametrize('n_writers', [9, 15, 17, 33, 100, 130])
    def test_matches_wide_sliding_window(self, n_writers):
        from automerge_tpu.ops import registers as R
        cols = self._dispatch(self._concurrent_group(n_writers),
                              A=n_writers)
        group, time, actor, seq, is_del, ctab, cidx = cols
        T = len(group)
        ref = R.resolve_registers(
            group, time, actor, seq, is_del=is_del,
            alive_in=np.ones(T, bool), window=T,
            sort_idx=np.lexsort((time, group)).astype(np.int32),
            clock_table=ctab, clock_idx=cidx)
        ref = {k: np.asarray(v) for k, v in ref.items()}
        ovf = np.zeros(T, bool)
        ovf[-1] = True   # flag one saturated row; the WHOLE group escalates
        resolved, oracle_rows, tiers = R.escalate_overflow(
            group, time, actor, seq, is_del, ctab, cidx, ovf)
        assert oracle_rows.size == 0
        assert len(resolved) == T
        expect_tier = R._tier_of(n_writers - 1, R.ESCALATION_FLOOR)
        assert list(tiers) == [expect_tier], tiers
        for row, (w, confs, alive, vb) in resolved.items():
            assert w == ref['winner'][row]
            assert confs == [c for c in ref['conflicts'][row] if c >= 0]
            assert alive == ref['alive_after'][row]
            assert vb == bool(ref['visible_before'][row])

    def test_dup_assign_same_change(self):
        """A change assigning one key twice (same actor+seq rows): the
        fixed member build can't hold it; the ladder's dup-extended
        streams must."""
        from automerge_tpu.ops import registers as R
        rows = self._concurrent_group(10)
        rows.append((0, 10, 4, 1, False))   # actor 4 assigns again, seq 1
        rows.append((0, 11, 4, 1, False))   # ...and a third time
        cols = self._dispatch(rows, A=10)
        group, time, actor, seq, is_del, ctab, cidx = cols
        T = len(group)
        ref = R.resolve_registers(
            group, time, actor, seq, is_del=is_del,
            alive_in=np.ones(T, bool), window=T,
            sort_idx=np.lexsort((time, group)).astype(np.int32),
            clock_table=ctab, clock_idx=cidx)
        ref = {k: np.asarray(v) for k, v in ref.items()}
        resolved, oracle_rows, _ = R.escalate_overflow(
            group, time, actor, seq, is_del, ctab, cidx,
            np.ones(T, bool))
        assert oracle_rows.size == 0
        for row, (w, confs, alive, _vb) in resolved.items():
            assert w == ref['winner'][row]
            assert confs == [c for c in ref['conflicts'][row] if c >= 0]
            assert alive == ref['alive_after'][row]

    def test_multi_group_multi_tier_and_oracle_residue(self):
        """Groups of different widths bucket into different tiers in one
        call; a group wider than max_tier comes back as oracle rows."""
        from automerge_tpu.ops import registers as R
        rows = []
        rows += self._concurrent_group(9, base_time=0, gid=0)
        rows += self._concurrent_group(33, base_time=100, gid=1)
        rows += self._concurrent_group(40, base_time=200, gid=2)
        cols = self._dispatch(rows, A=40)
        group, time, actor, seq, is_del, ctab, cidx = cols
        T = len(group)
        resolved, oracle_rows, tiers = R.escalate_overflow(
            group, time, actor, seq, is_del, ctab, cidx,
            np.ones(T, bool), max_tier=32)
        # gid 2 needs W=64 > max_tier -> oracle residue, whole group
        assert sorted(oracle_rows.tolist()) == list(range(42, 82))
        assert set(tiers) == {16, 32}
        assert len(resolved) == 42
        # unflagged groups are untouched
        resolved2, oracle2, tiers2 = R.escalate_overflow(
            group, time, actor, seq, is_del, ctab, cidx,
            np.zeros(T, bool))
        assert not resolved2 and not oracle2.size and not tiers2

    def test_scratch_budget_chunks_and_oracle_residue(self):
        """The [Tn, W+1, W+1] scratch budget: a tier of many groups is
        CHUNKED into several dispatches (all still resolved), while a
        single group too large for any chunking takes the oracle."""
        import os

        from automerge_tpu.ops import registers as R
        # six groups of 300 rows each (12 actors x 25 sequential rounds:
        # width stays 12 -> tier 16, but the row count is what the
        # budget must chunk); clocks make each actor's later write
        # supersede its earlier ones
        rows = []
        t = 0
        for g in range(6):
            for s in range(1, 26):
                for a in range(12):
                    rows.append((g, t, a, s, False))
                    t += 1
        group = np.array([r[0] for r in rows], np.int32)
        time = np.array([r[1] for r in rows], np.int32)
        actor = np.array([r[2] for r in rows], np.int32)
        seq = np.array([r[3] for r in rows], np.int32)
        is_del = np.zeros(len(rows), bool)
        T = len(rows)
        ctab = np.zeros((T, 12), np.int32)
        ctab[np.arange(T), actor] = seq - 1
        cidx = np.arange(T, dtype=np.int32)
        prior = os.environ.get('AMTPU_ESCALATE_BUDGET_MB')
        os.environ['AMTPU_ESCALATE_BUDGET_MB'] = '1'
        try:
            # one group fits a dispatch; two do not -> the tier chunks
            assert R._dispatch_cost(300, 16) <= 1 << 20
            assert R._dispatch_cost(600, 16) > 1 << 20
            resolved, oracle_rows, tiers = R.escalate_overflow(
                group, time, actor, seq, is_del, ctab, cidx,
                np.ones(T, bool))
            assert oracle_rows.size == 0
            assert len(resolved) == T          # every row still resolved
            assert tiers == {16: T}
            ref = R.resolve_registers(
                group, time, actor, seq, is_del=is_del,
                alive_in=np.ones(T, bool), window=16,
                sort_idx=np.lexsort((time, group)).astype(np.int32),
                clock_table=ctab, clock_idx=cidx)
            refw = np.asarray(ref['winner'])
            refa = np.asarray(ref['alive_after'])
            for row, (w, _c, a_, _vb) in resolved.items():
                assert w == refw[row]
                assert a_ == refa[row]
            # a single group whose own padded cost exceeds the budget
            # is memory-unboundable -> oracle residue, not an OOM
            wide = self._dispatch(self._concurrent_group(600), A=600)
            g2, t2, a2, s2, d2, ct2, ci2 = wide
            r2, oracle2, tiers2 = R.escalate_overflow(
                g2, t2, a2, s2, d2, ct2, ci2, np.ones(600, bool))
            assert not r2 and not tiers2
            assert oracle2.size == 600
        finally:
            if prior is None:
                os.environ.pop('AMTPU_ESCALATE_BUDGET_MB', None)
            else:
                os.environ['AMTPU_ESCALATE_BUDGET_MB'] = prior

    def test_packed_word_codec_round_trip(self):
        """pack_register_word (kernel side) and NativeDocPool's
        _unpack_packed (host side) are the two ends of the packed
        transfer: encode/decode must round-trip at the edges -- no
        winner (0xffffff), alive saturation at PACKED_ALIVE_MAX, and
        the overflow bit."""
        from automerge_tpu.native import NativeDocPool
        from automerge_tpu.ops import registers as R
        winner = np.array([-1, 0, 123456, (1 << 24) - 2], np.int32)
        alive = np.array([0, 1, 63, 1000], np.int32)
        ovf = np.array([0, 1, 0, 1], np.uint8)
        word = R.pack_register_word(winner, alive, ovf)
        w2, a2, o2 = NativeDocPool._unpack_packed(word)
        assert w2.tolist() == winner.tolist()
        assert a2.tolist() == [0, 1, 63, R.PACKED_ALIVE_MAX]
        assert o2.tolist() == ovf.tolist()

    def test_escalated_merge_writes_decodable_words(self):
        """The packed member epilogue merges tier results INTO the packed
        word (native _collect_member_packed); the merged words must
        decode to the wide-window reference -- winner exact, alive
        saturated, overflow bit CLEAR for every ladder-resolved row even
        though the row entered flagged."""
        from automerge_tpu.native import NativeDocPool
        from automerge_tpu.ops import registers as R
        n = 70    # survivors > PACKED_ALIVE_MAX: saturation engaged
        cols = self._dispatch(self._concurrent_group(n), A=n)
        group, time, actor, seq, is_del, ctab, cidx = cols
        ref = R.resolve_registers(
            group, time, actor, seq, is_del=is_del,
            alive_in=np.ones(n, bool), window=n,
            sort_idx=np.lexsort((time, group)).astype(np.int32),
            clock_table=ctab, clock_idx=cidx)
        pending, oracle_rows, _tiers = R.escalate_overflow_dispatch(
            group, time, actor, seq, is_del, ctab, cidx,
            np.ones(n, bool))
        assert oracle_rows.size == 0
        # the merge the native driver performs, on a base word that
        # entered with the member-overflow route (flag conceptually set)
        packed = np.full(n, -1, np.int32)     # poisoned base words
        for ch in R.escalate_overflow_collect_arrays(pending):
            packed[ch.rows] = R.pack_register_word(ch.winner, ch.alive)
        w2, a2, o2 = NativeDocPool._unpack_packed(packed)
        assert w2.tolist() == np.asarray(ref['winner']).tolist()
        assert a2.tolist() == np.minimum(
            np.asarray(ref['alive_after']), R.PACKED_ALIVE_MAX).tolist()
        assert (o2 == 0).all()

    def test_packed_word_saturates_alive(self):
        """Widened packed layout: alive saturates at 63 (bits 24..29),
        overflow rides bit 30, winner keeps its 24 bits."""
        from automerge_tpu.ops import registers as R
        n = 70   # survivors > PACKED_ALIVE_MAX
        cols = self._dispatch(self._concurrent_group(n), A=n)
        group, time, actor, seq, is_del, ctab, cidx = cols
        out = R.resolve_registers(
            group, time, actor, seq, is_del=is_del,
            alive_in=np.ones(n, bool), window=n,
            sort_idx=np.lexsort((time, group)).astype(np.int32),
            clock_table=ctab, clock_idx=cidx)
        packed = np.asarray(out['packed'])
        alive = np.asarray(out['alive_after'])
        last = int(np.argmax(alive))          # row with all 70 alive
        assert alive[last] == n
        assert (packed[last] >> 24) & 0x3f == R.PACKED_ALIVE_MAX
        assert (packed[last] & 0xffffff) == np.asarray(out['winner'])[last]
        assert (packed[last] >> 30) & 1 == 0


class TestPallasDominance:
    """The Pallas TPU kernel must equal the XLA kernel bit-for-bit; on the
    CPU test mesh it runs through the Pallas interpreter."""

    def _random_case(self, seed, W=8, L=128, T=128):
        rng = random.Random(seed)
        v0 = np.zeros((W, L), np.float32)
        er = np.full((W, L), -1, np.int32)
        oe = np.full((W, T), -1, np.int32)
        orank = np.full((W, T), -1, np.int32)
        od = np.zeros((W, T), np.int32)
        ov = np.zeros((W, T), bool)
        for o in range(W):
            n = rng.randint(1, L)
            t = rng.randint(1, T)
            ranks = list(range(n))
            rng.shuffle(ranks)
            er[o, :n] = ranks
            v0[o, :n] = [rng.random() < 0.5 for _ in range(n)]
            for k in range(t):
                e = rng.randrange(n)
                oe[o, k] = e
                orank[o, k] = er[o, e]
                od[o, k] = rng.choice([-1, 0, 1])
                ov[o, k] = True
        return v0, er, oe, orank, od, ov

    @pytest.mark.parametrize('seed,W', [(3, 8), (4, 8), (5, 24)])
    def test_interpreter_matches_xla(self, seed, W):
        # W=24 covers grid > 1: per-program VMEM scratch re-init
        from automerge_tpu.ops.list_rank import dominance_grouped
        from automerge_tpu.ops.pallas_dominance import \
            dominance_grouped_pallas
        args = self._random_case(seed, W=W)
        want = np.asarray(dominance_grouped(*args, chunk=128))
        got = np.asarray(dominance_grouped_pallas(*args, chunk=128,
                                                  interpret=True))
        ov = args[-1]
        assert (got[ov] == want[ov]).all()

    def test_auto_dispatch_fallback(self):
        # off-TPU the dispatcher must route to the XLA kernel
        from automerge_tpu.ops.list_rank import dominance_grouped
        from automerge_tpu.ops.pallas_dominance import \
            dominance_grouped_auto
        args = self._random_case(9, W=4, L=48, T=64)
        want = np.asarray(dominance_grouped(*args, chunk=64))
        got = np.asarray(dominance_grouped_auto(*args, chunk=64))
        ov = args[-1]
        assert (got[ov] == want[ov]).all()


class TestClockTablePath:
    def test_table_matches_dense(self):
        """resolve_registers(clock_table, clock_idx) must equal the dense
        clock path on identical inputs."""
        from automerge_tpu.ops.registers import resolve_registers
        rng = random.Random(17)
        T, A, C = 32, 4, 6
        group = np.array([rng.randrange(4) for _ in range(T)], np.int32)
        time = np.arange(T, dtype=np.int32)
        actor = np.array([rng.randrange(A) for _ in range(T)], np.int32)
        seq = np.array([rng.randint(1, 5) for _ in range(T)], np.int32)
        table = np.array([[rng.randint(0, 5) for _ in range(A)]
                          for _ in range(C)], np.int32)
        idx = np.array([rng.randrange(C) for _ in range(T)], np.int32)
        is_del = np.array([rng.random() < 0.2 for _ in range(T)])
        alive = np.ones((T,), bool)
        dense = resolve_registers(group, time, actor, seq, table[idx],
                                  is_del, alive)
        tabled = resolve_registers(group, time, actor, seq, is_del=is_del,
                                   alive_in=alive, clock_table=table,
                                   clock_idx=idx)
        for k in ('winner', 'alive_after', 'conflicts', 'overflow',
                  'packed'):
            assert (np.asarray(dense[k]) == np.asarray(tabled[k])).all(), k

    def test_requires_exactly_one_clock_form(self):
        from automerge_tpu.ops.registers import resolve_registers
        z = np.zeros((4,), np.int32)
        with pytest.raises(ValueError):
            resolve_registers(z, z, z, z, is_del=z.astype(bool),
                              alive_in=np.ones(4, bool))


class TestPallasRegisters:
    """The Pallas sliding-window register kernel must equal the XLA
    kernel bit-for-bit (interpret mode on the CPU test mesh)."""

    def _random_case(self, seed, T=256, A=16, n_groups=24, window=4):
        rng = random.Random(seed)
        group = np.full((T,), -1, np.int32)
        time = np.zeros((T,), np.int32)
        actor = np.zeros((T,), np.int32)
        seq = np.zeros((T,), np.int32)
        is_del = np.zeros((T,), bool)
        # deduplicated clock rows, one per (actor, seq)
        rows = {}
        table = [np.zeros((A,), np.int32)]
        idx = np.zeros((T,), np.int32)
        n_real = rng.randint(T // 2, T)
        # per-actor current seq; clocks grow monotonically per actor with
        # random cross-actor knowledge -- realistic causal structure
        seqs = [0] * A
        known = [np.zeros((A,), np.int32) for _ in range(A)]
        for i in range(n_real):
            g = rng.randrange(n_groups)
            a = rng.randrange(A)
            if rng.random() < 0.6:
                seqs[a] += 1
                # learn some other actor's frontier before authoring
                o = rng.randrange(A)
                known[a] = np.maximum(known[a], known[o])
                known[a][a] = seqs[a] - 1
            s = max(seqs[a], 1)
            seqs[a] = s
            group[i] = g
            time[i] = i
            actor[i] = a
            seq[i] = s
            is_del[i] = rng.random() < 0.1
            key = (a, s)
            if key not in rows:
                clk = known[a].copy()
                clk[a] = s - 1
                rows[key] = len(table)
                table.append(clk)
            idx[i] = rows[key]
        # a few state rows (negative times) for early groups
        for g in range(min(4, n_groups)):
            i = n_real - 1 - g
            if i > 0:
                time[i] = -(g + 1)
        clock_table = np.stack(table)
        sort_idx = np.lexsort((time, group)).astype(np.int32)
        return (group, time, actor, seq, is_del, sort_idx,
                clock_table, idx)

    @pytest.mark.parametrize('seed,window', [(1, 4), (2, 8), (7, 2)])
    def test_interpreter_matches_xla(self, seed, window):
        from automerge_tpu.ops.pallas_registers import \
            resolve_registers_pallas
        from automerge_tpu.ops.registers import resolve_registers
        (group, time, actor, seq, is_del, sort_idx,
         clock_table, idx) = self._random_case(seed, window=window)
        want = resolve_registers(
            group, time, actor, seq, is_del=is_del,
            alive_in=np.ones_like(is_del), window=window,
            sort_idx=sort_idx, clock_table=clock_table, clock_idx=idx)
        got = resolve_registers_pallas(
            group, time, actor, seq, is_del, sort_idx,
            clock_table, idx, window=window, interpret=True)
        for k in ('winner', 'alive_after', 'conflicts', 'visible_before',
                  'overflow', 'packed'):
            assert (np.asarray(got[k]) == np.asarray(want[k])).all(), k

    def test_kernel_error_raises_every_time(self, monkeypatch):
        """Where the dispatcher picks Pallas, a kernel failure raises --
        here Mosaic refusing a CPU backend -- and nothing latches the
        kernel off: the next call picks Pallas again."""
        from automerge_tpu import telemetry
        from automerge_tpu.ops import pallas_registers
        monkeypatch.setattr(pallas_registers, 'pallas_enabled',
                            lambda: True)
        (group, time, actor, seq, is_del, sort_idx,
         clock_table, idx) = self._random_case(3)
        telemetry.metrics_reset()
        for _ in range(2):
            with pytest.raises(Exception):
                pallas_registers.resolve_registers_auto(
                    group, time, actor, seq, is_del, np.ones_like(is_del),
                    sort_idx, clock_table, idx, window=4)
        assert not [k for k in telemetry.metrics_snapshot()
                    if 'pallas' in k]

    def test_gate_sends_wide_actor_sets_to_the_twin(self, monkeypatch):
        """Past the VMEM gate (256 actors at W=8 overflows Mosaic's
        scoped VMEM on a v5e) the dispatcher picks the XLA twin even
        where Pallas is on; at 16 actors it picks the kernel, which
        cannot run here and raises."""
        from automerge_tpu.ops import pallas_registers
        monkeypatch.setattr(pallas_registers, 'pallas_enabled',
                            lambda: True)
        assert pallas_registers.widest_actors(8) < 256
        for A in (16, 256):
            (group, time, actor, seq, is_del, sort_idx,
             clock_table, idx) = self._random_case(4, A=A, window=8)
            args = (group, time, actor, seq, is_del, np.ones_like(is_del),
                    sort_idx, clock_table, idx)
            if A == 16:
                with pytest.raises(Exception):
                    pallas_registers.resolve_registers_auto(*args, window=8)
            else:
                got = pallas_registers.resolve_registers_auto(*args,
                                                              window=8)
                assert np.asarray(got['packed']).shape == (256,)

    def test_pool_window_batch_takes_the_kernel(self, monkeypatch):
        """A registers-only batch with no key wider than the window goes
        from make_pool() to the Pallas kernel (interpreted here), with
        oracle-identical patches: the route chip_smoke.py's window phase
        drives on the chip."""
        from automerge_tpu import backend as Backend
        from automerge_tpu.native import make_pool
        from automerge_tpu.ops import pallas_registers
        kernel = pallas_registers.resolve_registers_pallas
        windows = []

        def interpreted(*args, **kw):
            windows.append(kw['window'])
            return kernel(*args, interpret=True, **kw)
        monkeypatch.setattr(pallas_registers, 'pallas_enabled',
                            lambda: True)
        monkeypatch.setattr(pallas_registers, 'resolve_registers_pallas',
                            interpreted)
        monkeypatch.setenv('AMTPU_HOST_FULL', '0')
        rng = random.Random(5)
        batch = {'m%d' % d: [
            {'actor': 'a%d' % a, 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k%d' % k,
                 'value': a} for k in rng.sample(range(8), 4)]}
            for a in range(8)] for d in range(8)}
        pool = make_pool()
        pool.apply_batch(batch)
        assert windows and max(windows) <= 8
        for d, chs in batch.items():
            st, _ = Backend.apply_changes(Backend.init(), chs)
            assert pool.get_patch(d) == Backend.get_patch(st), d

    def test_auto_dispatch_fallback(self):
        # off-TPU the dispatcher must route to the XLA kernel
        from automerge_tpu.ops.pallas_registers import \
            resolve_registers_auto
        from automerge_tpu.ops.registers import resolve_registers
        (group, time, actor, seq, is_del, sort_idx,
         clock_table, idx) = self._random_case(11)
        want = resolve_registers(
            group, time, actor, seq, is_del=is_del,
            alive_in=np.ones_like(is_del), window=4,
            sort_idx=sort_idx, clock_table=clock_table, clock_idx=idx)
        got = resolve_registers_auto(
            group, time, actor, seq, is_del, np.ones_like(is_del),
            sort_idx, clock_table, idx, window=4)
        for k in ('winner', 'packed'):
            assert (np.asarray(got[k]) == np.asarray(want[k])).all(), k
