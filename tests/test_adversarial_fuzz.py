"""Adversarial differential fuzz (VERDICT r3 #8): schedules built to hit
the engine's cliffs rather than its fast paths.

Each scenario drives the scalar oracle and the C++ native pool, on the
CPU default (`host`) and on the chip's kernel path (`kernel`), with
IDENTICAL inputs and requires byte-identical patches at every delivery
-- the same contract as tests/test_engine_differential.py, pointed at:

  * wide antichains: >8 concurrent writer streams per key (member-window
    overflow -> tiered kernel escalation, hostreg on the CPU backend; the
    host oracle only as parity referee), on maps AND list elements;
  * deep cross-doc causal chains delivered fully reversed (the causal
    queue fixpoint, not the in-order fast path);
  * undo/redo interleaved with remote merges (undo-stack capture against
    registers that remote batches keep rewriting);
  * save/load mid-stream (checkpoint/restore of every mirror the engine
    maintains, then continued ingestion on the restored state).

Seeds are fixed for CI reproducibility; AMTPU_FUZZ_SEED overrides to
widen the search (same convention as TestRotatingFuzz).
"""

import os
import random

import pytest

from automerge_tpu import backend as Backend
from automerge_tpu.native import NativeDocPool
from tests.conftest import LanePool, native_lanes
from tests.test_engine_differential import deliver_and_compare

ROOT_ID = '00000000-0000-0000-0000-000000000000'


def seed_base(default):
    env = os.environ.get('AMTPU_FUZZ_SEED')
    return int(env) if env else default


def deliver_all(change_batches, n_docs=1):
    """Oracle vs the native pool of the test's lane (the `pool_lane`
    fixture sets the lane's env for the whole test), patch-equal at
    every step and at the end.  Every scenario runs on the CPU default
    (`host`) and on the chip's kernel path (`kernel`)."""
    deliver_and_compare(change_batches, n_docs, pools=[NativeDocPool()])


class TestWideAntichains:
    """Register groups wider than the base kernel window: every width
    must resolve through the escalation ladder (n writers -> n-1
    candidates: 9/15/17 land in the w16 tier, 33 in w32),
    byte-identical to the oracle in both execution modes."""

    @pytest.mark.parametrize('n_writers', [9, 12, 15, 17, 20, 33])
    def test_map_hot_keys(self, n_writers, pool_lane):
        rng = random.Random(seed_base(501) + n_writers)
        changes = []
        for seq in range(1, 4):
            for a in range(n_writers):
                ops = []
                for k in rng.sample(range(5), 3):
                    if rng.random() < 0.15:
                        ops.append({'action': 'del', 'obj': ROOT_ID,
                                    'key': 'k%d' % k})
                    else:
                        ops.append({'action': 'set', 'obj': ROOT_ID,
                                    'key': 'k%d' % k,
                                    'value': 'w%02d.%d' % (a, seq)})
                changes.append({'actor': 'w%02d' % a, 'seq': seq,
                                'deps': {}, 'ops': ops})
        rng.shuffle(changes)
        # causally safe shuffle: per-actor order restored
        changes.sort(key=lambda c: c['seq'])
        batches = []
        i = 0
        while i < len(changes):
            n = rng.randint(2, 9)
            batches.append({0: changes[i:i + n]})
            i += n
        deliver_all(batches)

    def test_list_element_antichain(self, pool_lane):
        """14 writers concurrently assign the SAME list element (and one
        deletes it): a wide antichain on an element register, which must
        route through the overflow fallback WITH dominance work."""
        base = {'actor': 'base', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeList', 'obj': 'l'},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'list',
             'value': 'l'},
            {'action': 'ins', 'obj': 'l', 'key': '_head', 'elem': 1},
            {'action': 'set', 'obj': 'l', 'key': 'base:1', 'value': 'v0'},
            {'action': 'ins', 'obj': 'l', 'key': 'base:1', 'elem': 2},
            {'action': 'set', 'obj': 'l', 'key': 'base:2', 'value': 'v1'},
        ]}
        writers = []
        for a in range(14):
            op = ({'action': 'del', 'obj': 'l', 'key': 'base:1'}
                  if a == 7 else
                  {'action': 'set', 'obj': 'l', 'key': 'base:1',
                   'value': 'w%02d' % a})
            writers.append({'actor': 'w%02d' % a, 'seq': 1,
                            'deps': {'base': 1}, 'ops': [op]})
        deliver_all([{0: [base]}, {0: writers}])


class TestEscalationFallbackFree:
    """The ISSUE-2 acceptance lanes: the kernel path must be oracle-free
    on every width the ladder serves -- including the table-adversarial
    shape (same-change dup assigns) that produced the recorded 8,532
    oracle-fallback rows, and a 100+ concurrent-live-writer antichain.
    The `host` lane resolves every register in C++ emit: it must be
    oracle-free too, with every batch on the full host path."""

    def _assert_kernel_fallback_free(self, run, pool_lane,
                                     expect_escalated=True):
        from automerge_tpu import telemetry
        telemetry.metrics_reset()
        run()
        snap = telemetry.metrics_snapshot()
        assert snap.get('fallback.oracle', 0) == 0, snap
        if pool_lane.lane == 'host':
            assert snap.get('hostfull.batches', 0) > 0, snap
        elif expect_escalated:
            assert any(k.startswith('fallback.escalated.w') and v > 0
                       for k, v in snap.items()), (pool_lane.lane, snap)

    @pytest.mark.parametrize('n_writers', [9, 15, 17, 33, 100, 120])
    def test_concurrent_live_writers_one_key(self, n_writers, pool_lane):
        """n fully concurrent live writers on one key in ONE batch: the
        widest antichain shape, resolved without a single oracle row."""
        writers = [{'actor': 'w%03d' % a, 'seq': 1, 'deps': {},
                    'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                             'value': 'w%03d' % a}]}
                   for a in range(n_writers)]
        self._assert_kernel_fallback_free(
            lambda: deliver_all([{0: writers}]), pool_lane)

    def test_table_shape_dup_assigns(self, pool_lane):
        """Config-4-shaped rows: concurrent field updates where changes
        assign the SAME row key twice (the member-window-unholdable
        shape) -- previously all-oracle, now all-ladder."""
        rng = random.Random(seed_base(60603))
        n_actors = 9
        rows = ['row-%d' % i for i in range(6)]
        setup = {'actor': 'setup', 'seq': 1, 'deps': {}, 'ops':
                 [{'action': 'makeTable', 'obj': 'tb'},
                  {'action': 'link', 'obj': ROOT_ID, 'key': 'rows',
                   'value': 'tb'}] +
                 [op for r in rows for op in (
                     {'action': 'makeMap', 'obj': r},
                     {'action': 'set', 'obj': r, 'key': 'n', 'value': -1},
                     {'action': 'link', 'obj': 'tb', 'key': r,
                      'value': r})]}
        updates = []
        for a in range(n_actors):
            ops = []
            for _ in range(8):   # 8 picks of 6 rows: dup assigns certain
                r = rows[rng.randrange(len(rows))]
                ops.append({'action': 'set', 'obj': r, 'key': 'n',
                            'value': rng.randrange(1000)})
            updates.append({'actor': 'a%d' % a, 'seq': 1,
                            'deps': {'setup': 1}, 'ops': ops})
        self._assert_kernel_fallback_free(
            lambda: deliver_all([{0: [setup]}, {0: updates}]), pool_lane)

    def test_oracle_referee_parity(self, pool_lane):
        """AMTPU_ESCALATE=0 pins the referee: the host oracle must
        produce byte-identical patches to the ladder (and the kernel
        lane must actually take the oracle path -- fallback.oracle > 0;
        the `host` lane has no ladder to switch off and stays on its
        full host path, oracle-free)."""
        from automerge_tpu import telemetry
        prior = os.environ.get('AMTPU_ESCALATE')
        os.environ['AMTPU_ESCALATE'] = '0'
        try:
            telemetry.metrics_reset()
            writers = [{'actor': 'w%02d' % a, 'seq': 1, 'deps': {},
                        'ops': [{'action': 'set', 'obj': ROOT_ID,
                                 'key': 'k', 'value': a}]}
                       for a in range(20)]
            deliver_all([{0: writers}])
            snap = telemetry.metrics_snapshot()
            if pool_lane.lane == 'host':
                assert snap.get('fallback.oracle', 0) == 0, snap
                assert snap.get('hostfull.batches', 0) > 0, snap
            else:
                assert snap.get('fallback.oracle', 0) > 0, snap
        finally:
            if prior is None:
                os.environ.pop('AMTPU_ESCALATE', None)
            else:
                os.environ['AMTPU_ESCALATE'] = prior

    def test_wide_antichain_with_list_dominance(self, pool_lane):
        """30 concurrent writers on ONE list element register: escalation
        must compose with the dominance stage, not just map emits."""
        base = {'actor': 'base', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeList', 'obj': 'l'},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'list',
             'value': 'l'},
            {'action': 'ins', 'obj': 'l', 'key': '_head', 'elem': 1},
            {'action': 'set', 'obj': 'l', 'key': 'base:1', 'value': 'v0'}]}
        writers = []
        for a in range(30):
            op = ({'action': 'del', 'obj': 'l', 'key': 'base:1'}
                  if a == 13 else
                  {'action': 'set', 'obj': 'l', 'key': 'base:1',
                   'value': 'w%02d' % a})
            writers.append({'actor': 'w%02d' % a, 'seq': 1,
                            'deps': {'base': 1}, 'ops': [op]})
        self._assert_kernel_fallback_free(
            lambda: deliver_all([{0: [base]}, {0: writers}]), pool_lane)


@pytest.fixture(params=['packed', 'unpacked'])
def packed_epilogue(request):
    """Both member epilogues face the same schedules: the packed
    transfer (default) and the full-matrix readback
    (AMTPU_PACKED_EPILOGUE=0) -- byte parity between them is the
    ISSUE-3 acceptance bar."""
    prior = os.environ.get('AMTPU_PACKED_EPILOGUE')
    os.environ['AMTPU_PACKED_EPILOGUE'] = \
        '1' if request.param == 'packed' else '0'
    yield request.param
    if prior is None:
        os.environ.pop('AMTPU_PACKED_EPILOGUE', None)
    else:
        os.environ['AMTPU_PACKED_EPILOGUE'] = prior


class TestPackedEpilogueParity:
    """ISSUE-3 fuzz lane: the packed member epilogue (ONE i32 per
    register row + sparse CSR conflicts + in-packed escalation merge)
    must be byte-identical to the full-matrix readback it replaced,
    against the scalar-oracle referee, in both execution modes.

    The workload is built to hit every packed-path branch at once:
    member mode (hot keys deeper than the sliding window), host-flagged
    overflow escalating through wider tiers (>8 concurrent streams AND
    same-change dup assigns), base-kernel conflict rows OUTSIDE the
    flagged groups (the sparse CSR gather), deletes, and registers that
    resolve to a single survivor."""

    def _workload(self, rng, n_actors=11, n_keys=6, n_rounds=3):
        batches = []
        setup = {'actor': 'setup', 'seq': 1, 'deps': {}, 'ops':
                 [{'action': 'set', 'obj': ROOT_ID, 'key': 'k%d' % k,
                   'value': 'base'} for k in range(n_keys)]}
        batches.append({0: [setup]})
        for rnd in range(n_rounds):
            changes = []
            for a in range(n_actors):
                ops = []
                # hot key k0: every actor, every round (member mode +
                # >8 concurrent streams -> escalation)
                ops.append({'action': 'set', 'obj': ROOT_ID, 'key': 'k0',
                            'value': 'a%d-r%d' % (a, rnd)})
                if a == 3:
                    # same-change dup assign: the member-window
                    # unholdable shape
                    ops.append({'action': 'set', 'obj': ROOT_ID,
                                'key': 'k0', 'value': 'dup-%d' % rnd})
                # narrow keys: 2-3 writers each (conflicts survive on
                # the BASE kernel path, outside any flagged group)
                k = 1 + (a + rnd) % (n_keys - 1)
                if a < 3:
                    op = {'action': 'set', 'obj': ROOT_ID,
                          'key': 'k%d' % k, 'value': a * 100 + rnd}
                    if a == 2 and rnd == 1:
                        op = {'action': 'del', 'obj': ROOT_ID,
                              'key': 'k%d' % k}
                    ops.append(op)
                # deep sequential history on one key: member mode with a
                # single surviving stream
                if a == 5:
                    for i in range(4):
                        ops.append({'action': 'set', 'obj': ROOT_ID,
                                    'key': 'k5',
                                    'value': 'seq-%d-%d' % (rnd, i)})
                # private keys: unflagged member rows keep the batch off
                # the hostreg route (2 * pre_ovf < T), so the KERNEL
                # member path -- the epilogue under test -- serves it
                for i in range(3):
                    ops.append({'action': 'set', 'obj': ROOT_ID,
                                'key': 'p%d' % a,
                                'value': 'p-%d-%d-%d' % (a, rnd, i)})
                changes.append({'actor': 'f%02d' % a, 'seq': rnd + 1,
                                'deps': {'setup': 1},
                                'ops': ops})
            rng.shuffle(changes)
            batches.append({0: changes})
        return batches

    def test_member_epilogue_byte_parity(self, packed_epilogue,
                                         pool_lane):
        from automerge_tpu import telemetry
        telemetry.metrics_reset()
        rng = random.Random(seed_base(70707))
        # pin routing: hostreg would bypass the epilogue under test on
        # the CPU backend (the counters below assert which path served)
        prior = os.environ.get('AMTPU_HOST_REG')
        os.environ['AMTPU_HOST_REG'] = '0'
        try:
            deliver_all(self._workload(rng))
        finally:
            if prior is None:
                os.environ.pop('AMTPU_HOST_REG', None)
            else:
                os.environ['AMTPU_HOST_REG'] = prior
        snap = telemetry.metrics_snapshot()
        assert snap.get('fallback.oracle', 0) == 0, snap
        if pool_lane.lane == 'kernel':
            # the toggle must actually select the epilogue under test
            if packed_epilogue == 'packed':
                assert snap.get('collect.packed_member_batches', 0) > 0, \
                    snap
                assert snap.get('collect.full_matrix_readback', 0) == 0, \
                    snap
            else:
                assert snap.get('collect.full_matrix_readback', 0) > 0, \
                    snap
                assert snap.get('collect.packed_member_batches', 0) == 0, \
                    snap

    @pytest.mark.parametrize('seed', [1, 2, 3])
    def test_rotating_hot_key_fuzz(self, seed, packed_epilogue,
                                   pool_lane):
        """Randomized widths/depths: writer counts rotate through the
        base window, the first tier, and multi-tier territory."""
        rng = random.Random(seed_base(81000 + seed))
        n_actors = rng.choice([9, 12, 17])
        batches = self._workload(rng, n_actors=n_actors,
                                 n_keys=rng.randrange(3, 7),
                                 n_rounds=2)
        deliver_all(batches)


class TestReversedCausalChains:
    def test_deep_chain_reversed(self, pool_lane):
        """120-deep cross-actor dependency chain delivered fully
        reversed: every change but the first buffers, then one fixpoint
        admits the whole chain."""
        rng = random.Random(seed_base(601))
        actors = ['a%d' % i for i in range(4)]
        seqs = {a: 0 for a in actors}
        chain = []
        frontier = {}
        for i in range(120):
            a = actors[i % 4]
            seqs[a] += 1
            ops = [{'action': 'set', 'obj': ROOT_ID,
                    'key': 'k%d' % rng.randrange(6), 'value': i}]
            deps = {x: s for x, s in frontier.items() if x != a}
            chain.append({'actor': a, 'seq': seqs[a], 'deps': deps,
                          'ops': ops})
            frontier[a] = seqs[a]
        reversed_chain = list(reversed(chain))
        # reversed in small batches: deps stay missing until the last
        # batch arrives, then everything cascades
        batches = []
        i = 0
        while i < len(reversed_chain):
            n = rng.randint(1, 7)
            batches.append({0: reversed_chain[i:i + n]})
            i += n
        deliver_all(batches)

    def test_cross_doc_reversed_streams(self, pool_lane):
        """Several docs' chains interleaved, each doc's stream reversed
        independently within one multi-doc batch sequence."""
        rng = random.Random(seed_base(602))
        streams = {}
        for d in range(3):
            chain = []
            for i in range(40):
                a = 'd%d-a%d' % (d, i % 3)
                chain.append({'actor': a, 'seq': i // 3 + 1,
                              'deps': ({'d%d-a%d' % (d, (i - 1) % 3):
                                        (i - 1) // 3 + 1} if i else {}),
                              'ops': [{'action': 'set', 'obj': ROOT_ID,
                                       'key': 'x', 'value': i}]})
            streams[d] = list(reversed(chain))
        batches = []
        pos = {d: 0 for d in streams}
        while any(pos[d] < len(streams[d]) for d in streams):
            batch = {}
            for d in streams:
                if pos[d] < len(streams[d]):
                    n = rng.randint(1, 5)
                    batch[d] = streams[d][pos[d]:pos[d] + n]
                    pos[d] += n
            batches.append(batch)
        deliver_all(batches, n_docs=3)


class TestUndoRedoUnderMerge:
    def test_undo_redo_interleaved_with_remote_batches(self):
        """Local change/undo/redo interleaved with remote deliveries:
        the undo stack captures registers that remote merges keep
        rewriting, and redo must replay against the merged state --
        all three backends byte-identical at every step."""
        rng = random.Random(seed_base(701))
        oracle = Backend.init()
        pools = native_lanes()
        local_seq = 0
        remote_seqs = {}
        can_undo = 0

        for step in range(40):
            roll = rng.random()
            if roll < 0.4:
                local_seq += 1
                req = {'requestType': 'change', 'actor': 'local',
                       'seq': local_seq, 'deps': {}, 'ops': [
                           {'action': 'set', 'obj': ROOT_ID,
                            'key': 'k%d' % rng.randrange(3),
                            'value': 'L%d' % step}]}
                can_undo += 1
            elif roll < 0.6 and can_undo:
                local_seq += 1
                req = {'requestType': 'undo', 'actor': 'local',
                       'seq': local_seq, 'deps': {}}
                can_undo -= 1
            elif roll < 0.7 and oracle['opSet']['redoStack']:
                local_seq += 1
                req = {'requestType': 'redo', 'actor': 'local',
                       'seq': local_seq, 'deps': {}}
            else:
                # remote delivery touching the same keys
                a = 'r%d' % rng.randrange(3)
                remote_seqs[a] = remote_seqs.get(a, 0) + 1
                ch = {'actor': a, 'seq': remote_seqs[a], 'deps': {},
                      'ops': [{'action': 'set', 'obj': ROOT_ID,
                               'key': 'k%d' % rng.randrange(3),
                               'value': '%s.%d' % (a, step)}]}
                oracle, want = Backend.apply_changes(oracle, [dict(ch)])
                for pool in pools:
                    got = pool.apply_batch({0: [dict(ch)]})[0]
                    assert got == want, (step, pool.lane)
                continue
            oracle, want = Backend.apply_local_change(oracle, dict(req))
            for pool in pools:
                got = pool.apply_local_change(0, dict(req))
                assert got == want, (step, req['requestType'],
                                     pool.lane)

        final = Backend.get_patch(oracle)
        for pool in pools:
            assert pool.get_patch(0) == final, pool.lane


class TestSaveLoadMidStream:
    """Checkpoint semantics match the reference: save() serializes the
    APPLIED document (opSet.history, src/automerge.js:45-52) -- changes
    still buffered in the causal queue at checkpoint time are NOT part
    of the doc and are recovered by the sync layer re-shipping anything
    the restored clock doesn't cover.  (This very suite found that an
    arbitrary mid-stream cut can leave a change buffered at save time,
    so the restored-side oracle below is built from the actual save
    blob, and continuation is driven the way the protocol does it:
    redeliver everything, duplicates no-op.)"""

    @pytest.mark.parametrize('seed', [801, 802])
    def test_checkpoint_restore_continue(self, seed):
        import msgpack

        from tests.test_engine_differential import WorkloadGen
        rng = random.Random(seed)
        changes = WorkloadGen(seed, n_actors=4,
                              structure='mixed').generate(40)
        half = len(changes) // 2
        pools = native_lanes()
        for pool in pools:
            pool.apply_batch({0: [dict(c) for c in changes[:half]]})

        restored = []
        blobs = []
        for pool in pools:
            blob = pool.save(0)
            blobs.append(blob)
            fresh = LanePool(pool.lane)
            fresh.load(0, blob)
            restored.append(fresh)
        # both backends checkpoint the same applied history
        assert blobs[0] == blobs[1]

        # restored-side oracle: replay the saved history itself
        # (container-format agnostic: the v2 columnar container decodes
        # through the storage helpers, docs/STORAGE.md)
        from automerge_tpu import storage
        oracle = Backend.init()
        oracle, _ = Backend.apply_changes(
            oracle, [msgpack.unpackb(r, raw=False)
                     for r in storage.checkpoint_raw_changes(blobs[0])])
        for pool in restored:
            assert pool.get_patch(0) == Backend.get_patch(oracle), \
                pool.lane

        # continuation via the redelivery protocol: EVERYTHING shuffled
        # (first half again + second half); applied changes dedup as
        # no-ops, changes dropped from the queue at checkpoint re-apply
        redelivery = [dict(c) for c in changes]
        rng.shuffle(redelivery)
        for ch in redelivery:
            oracle, want = Backend.apply_changes(oracle, [dict(ch)])
            for pool in restored:
                got = pool.apply_batch({0: [dict(ch)]})[0]
                assert got == want, pool.lane
        final = Backend.get_patch(oracle)
        for pool in restored:
            assert pool.get_patch(0) == final, pool.lane
        # nothing left buffered anywhere
        for pool in restored:
            assert pool.get_missing_deps(0) == {}


class TestTableAdversarial:
    """Table-shaped cliffs (round 5): the emit hot paths this round
    rewrote -- path-cache invalidation keyed on inbound[0] erasure,
    two-way obj/type caches, link inbound maintenance, cross-probe
    decode -- all face concurrent row lifecycles here.

    Shapes (reference Table semantics, frontend/table.js:26-196):
      * concurrent add/update/unlink/relink of the SAME rows by many
        actors, shuffled causal delivery;
      * rows linked under TWO parents, then the first parent's link
        removed (inbound[0] erase -> cached paths must re-render);
      * nested maps inside rows written before AND after the row is
        linked (null -> real path transitions that are never cached).
    """

    def test_concurrent_row_lifecycle(self, pool_lane):
        # every per-actor row object is created up front; afterwards six
        # actors concurrently update/unlink/relink the same rows with NO
        # causal ordering (deps={}), delivered fully shuffled -- maximal
        # concurrency on the table's link registers and inbound lists
        rng = random.Random(seed_base(60601))
        n_actors = 6
        objs = ['row-%d-a%d' % (i, a)
                for i in range(10) for a in range(n_actors)]
        setup = {'actor': 'setup', 'seq': 1, 'deps': {}, 'ops':
                 [{'action': 'makeTable', 'obj': 'tb'},
                  {'action': 'link', 'obj': ROOT_ID, 'key': 'rows',
                   'value': 'tb'}] +
                 [op for o in objs for op in (
                     {'action': 'makeMap', 'obj': o},
                     {'action': 'set', 'obj': o, 'key': 'n', 'value': -1},
                     {'action': 'link', 'obj': 'tb', 'key': o,
                      'value': o})]}
        changes = []
        for a in range(n_actors):
            actor = 'a%d' % a
            for seq in range(1, 7):
                ops = []
                for o in rng.sample(objs, 5):
                    kind = rng.random()
                    if kind < 0.3:
                        ops.append({'action': 'del', 'obj': 'tb',
                                    'key': o})
                    elif kind < 0.6:
                        ops.append({'action': 'link', 'obj': 'tb',
                                    'key': o, 'value': o})
                    else:
                        ops.append({'action': 'set', 'obj': o, 'key': 'n',
                                    'value': seq * 100 + a})
                changes.append({'actor': actor, 'seq': seq, 'deps': {},
                                'ops': ops})
        rng.shuffle(changes)
        deliver_all([{0: [setup]}] + [{0: [ch]} for ch in changes])

    def test_two_parent_row_first_link_removed(self, pool_lane):
        # row under two tables; removing the FIRST link (inbound[0])
        # must flip emitted paths to the second parent
        batches = [
            {0: [{'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeTable', 'obj': 't1'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'p1',
                 'value': 't1'},
                {'action': 'makeTable', 'obj': 't2'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'p2',
                 'value': 't2'},
                {'action': 'makeMap', 'obj': 'shared'},
                {'action': 'link', 'obj': 't1', 'key': 'shared',
                 'value': 'shared'},
                {'action': 'link', 'obj': 't2', 'key': 'shared',
                 'value': 'shared'},
                {'action': 'set', 'obj': 'shared', 'key': 'v',
                 'value': 1}]}]},
            {0: [{'actor': 'a', 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'del', 'obj': 't1', 'key': 'shared'},
                {'action': 'set', 'obj': 'shared', 'key': 'v',
                 'value': 2}]}]},
            {0: [{'actor': 'a', 'seq': 3, 'deps': {}, 'ops': [
                {'action': 'del', 'obj': 't2', 'key': 'shared'},
                {'action': 'set', 'obj': 'shared', 'key': 'v',
                 'value': 3}]}]},
        ]
        deliver_all(batches)

    def test_nested_map_written_around_link(self, pool_lane):
        rng = random.Random(seed_base(60602))
        ops = [{'action': 'makeTable', 'obj': 'tb'},
               {'action': 'link', 'obj': ROOT_ID, 'key': 'rows',
                'value': 'tb'}]
        for i in range(12):
            row, child = 'r%d' % i, 'c%d' % i
            ops += [{'action': 'makeMap', 'obj': row},
                    {'action': 'makeMap', 'obj': child},
                    # child written while BOTH are unreachable
                    {'action': 'set', 'obj': child, 'key': 'x',
                     'value': i},
                    {'action': 'link', 'obj': row, 'key': 'kid',
                     'value': child},
                    # child written while row is still unreachable
                    {'action': 'set', 'obj': child, 'key': 'x',
                     'value': i * 10},
                    {'action': 'link', 'obj': 'tb', 'key': row,
                     'value': row},
                    # and now fully reachable
                    {'action': 'set', 'obj': child, 'key': 'x',
                     'value': i * 100}]
        # split into changes of 5 ops, delivered in order then the
        # whole stream redelivered shuffled (dedup no-ops)
        chs = [{'actor': 'a', 'seq': s + 1, 'deps': {},
                'ops': ops[s * 5:(s + 1) * 5]}
               for s in range((len(ops) + 4) // 5)]
        chs = [c for c in chs if c['ops']]
        deliver_all([{0: chs}])
        redeliver = [dict(c) for c in chs]
        rng.shuffle(redeliver)
        deliver_all([{0: chs}, {0: redeliver}])
