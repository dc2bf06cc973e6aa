"""Pins the ONE deliberate conflict-tie-order deviation from the
reference, so the "byte-identical patches" claim is scoped precisely
(VERDICT r3 #6).

Input class where we deviate: a single change in which ONE actor assigns
the SAME (obj, key) more than once.  The reference frontend can never
emit such a change (`ensureSingleAssignment`,
`/root/reference/frontend/index.js:53` dedupes assignments per change),
and for hand-built changes the reference backend's own tie order is
unstable: `sortBy(actor).reverse()` (`/root/reference/backend/op_set.js`)
reverses a stable sort, so same-actor ties flip depending on how many
times the register was re-sorted -- i.e. the reference's own order for
this input oscillates between applications and is not a convergent
contract.

Our rule (`automerge_tpu/backend/op_set.py::apply_assign`): among
same-actor ties, most-recently-APPLIED op first; across actors, actor id
descending (identical to the reference).  This file pins:

  1. the exact patch our backends emit for the degenerate input,
  2. that the scalar oracle and the C++ native pool, on the CPU default
     and on the chip's kernel path, agree with each other on it, and
  3. that our rule is delivery-order independent even for this input
     (stronger than the reference, whose order is history-dependent).

For every frontend-shaped change stream (one assign per key per change)
all backends remain byte-identical to the reference; that claim is
carried by tests/test_backend.py + tests/test_golden_corpus.py.
"""

from automerge_tpu import backend as Backend
from tests.conftest import native_lanes

ROOT_ID = '00000000-0000-0000-0000-000000000000'


def _dup_change(actor, seq, values, deps=None):
    return {'actor': actor, 'seq': seq, 'deps': deps or {}, 'ops': [
        {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': v}
        for v in values]}


def _oracle_patches(changes):
    state = Backend.init()
    patches = []
    for ch in changes:
        state, p = Backend.apply_changes(state, [ch])
        patches.append(p)
    return Backend.get_patch(state), patches


class TestSameActorDuplicateAssign:
    def test_pinned_tie_order_single_change(self):
        """Most-recently-applied wins; earlier same-actor assign becomes
        the conflict entry.  This is OUR contract for the degenerate
        input (the reference has no stable one)."""
        final, patches = _oracle_patches([_dup_change('dup', 1, [1, 2])])
        assert patches[0]['diffs'][-1] == {
            'action': 'set', 'type': 'map', 'obj': ROOT_ID, 'key': 'k',
            'path': [], 'value': 2,
            'conflicts': [{'actor': 'dup', 'value': 1}]}
        assert final['diffs'] == [
            {'action': 'set', 'type': 'map', 'obj': ROOT_ID, 'key': 'k',
             'value': 2,
             'conflicts': [{'actor': 'dup', 'value': 1}]}]

    def test_three_backends_agree_on_degenerate_input(self):
        """The deviation is uniform: the scalar oracle and the native
        pool on its `host` and `kernel` lanes emit the SAME bytes for
        duplicate-assign changes (so the deviation cannot cause
        cross-backend drift)."""
        changes = [
            _dup_change('alice', 1, [1, 2]),
            _dup_change('bob', 1, [3, 4, 5]),
            _dup_change('alice', 2, ['x'], deps={'bob': 1}),
        ]
        want_final, want_patches = _oracle_patches(changes)

        for pool in native_lanes():
            for ch, want in zip(changes, want_patches):
                got = pool.apply_batch({0: [ch]})[0]
                assert got == want, pool.lane
            assert pool.get_patch(0) == want_final, pool.lane

    def test_delivery_order_independent(self):
        """Two concurrent degenerate changes produce the same register
        order whichever replica delivery order applied them -- our
        most-recent-first + stable actor-desc sort converges where the
        reference's re-sorted tie order is history-dependent."""
        a = _dup_change('alice', 1, [1, 2])
        b = _dup_change('bob', 1, [3, 4])
        final_ab, _ = _oracle_patches([a, b])
        final_ba, _ = _oracle_patches([b, a])
        assert final_ab == final_ba
        # actor-desc across actors, most-recent-first within an actor
        assert final_ab['diffs'] == [
            {'action': 'set', 'type': 'map', 'obj': ROOT_ID, 'key': 'k',
             'value': 4,
             'conflicts': [{'actor': 'bob', 'value': 3},
                           {'actor': 'alice', 'value': 2},
                           {'actor': 'alice', 'value': 1}]}]
