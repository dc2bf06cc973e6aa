"""Batched replica catch-up: convergence, fault tolerance, oracle parity.

Semantics under test mirror the reference's Connection behavior
(`/root/reference/src/connection.js:58-73`) and its multi-node test DSL's
fault model (`/root/reference/test/connection_test.js:17-66`): dropped
messages heal on later rounds, duplicate deliveries are no-ops.
"""

import random

from automerge_tpu.backend import apply_changes as oracle_apply
from automerge_tpu.backend import get_patch as oracle_get_patch
from automerge_tpu.backend import init as oracle_init
from automerge_tpu.sync.replica_set import BatchedReplicaSet, patch_to_tree

ROOT = '00000000-0000-0000-0000-000000000000'


def partitioned_history(n_replicas, n_docs, rounds=3, seed=5):
    """Each replica authors one actor's changes per doc: the classic
    fully-partitioned backlog (nobody has anyone else's stream)."""
    rng = random.Random(seed)
    by_replica = [dict() for _ in range(n_replicas)]
    all_changes = {}
    for d in range(n_docs):
        doc = 'doc%d' % d
        all_changes[doc] = []
        for r in range(n_replicas):
            actor = 'a%d' % r
            for seq in range(1, rounds + 1):
                change = {'actor': actor, 'seq': seq, 'deps': {},
                          'ops': [{'action': 'set', 'obj': ROOT,
                                   'key': 'k%d' % rng.randrange(4),
                                   'value': '%s-%d' % (actor, seq)}]}
                by_replica[r].setdefault(doc, []).append(change)
                all_changes[doc].append(change)
    return by_replica, all_changes


def test_partitioned_backlog_converges(pool_lane):
    rs = BatchedReplicaSet(4, pool_factory=pool_lane)
    by_replica, all_changes = partitioned_history(4, 3)
    for r, by_doc in enumerate(by_replica):
        rs.apply_batch(r, by_doc)
    assert not rs.converged()
    rounds = rs.catch_up()
    assert rs.converged()
    assert rounds[-1] == 0
    # byte parity across replicas AND against the oracle fed the union
    for doc, changes in all_changes.items():
        patch = rs.assert_identical(doc)
        state = oracle_init()
        state, _ = oracle_apply(state, changes)
        want = oracle_get_patch(state)
        assert patch['clock'] == want['clock']
        assert patch_to_tree(patch) == patch_to_tree(want)


def test_dropped_shipments_heal_on_later_rounds():
    dropped = []

    def drop(sender, receiver, doc_id):
        # drop the first 5 shipments outright
        if len(dropped) < 5:
            dropped.append((sender, receiver, doc_id))
            return True
        return False

    rs = BatchedReplicaSet(3, drop=drop)
    by_replica, all_changes = partitioned_history(3, 2)
    for r, by_doc in enumerate(by_replica):
        rs.apply_batch(r, by_doc)
    rs.catch_up()
    assert rs.converged()
    assert len(dropped) == 5
    for doc in all_changes:
        rs.assert_identical(doc)


def test_duplicate_deliveries_are_noops():
    rs = BatchedReplicaSet(3)
    by_replica, all_changes = partitioned_history(3, 2)
    for r, by_doc in enumerate(by_replica):
        rs.apply_batch(r, by_doc)
        # deliver the same batch again: seq dedup must no-op
        patches = rs.apply_batch(r, by_doc)
        assert all(p['diffs'] == [] for p in patches.values())
    rs.catch_up()
    assert rs.converged()
    for doc in all_changes:
        rs.assert_identical(doc)


def test_causal_gap_buffers_until_stream_arrives():
    """A change referencing another actor's unseen change queues, then
    applies once catch-up ships the dependency."""
    rs = BatchedReplicaSet(2)
    rs.apply_changes(0, 'd', [
        {'actor': 'a0', 'seq': 1, 'deps': {},
         'ops': [{'action': 'set', 'obj': ROOT, 'key': 'x', 'value': 1}]}])
    # replica 1 authors a change DEPENDING on a0's change it has...
    rs.apply_changes(1, 'd', [
        {'actor': 'a0', 'seq': 1, 'deps': {},
         'ops': [{'action': 'set', 'obj': ROOT, 'key': 'x', 'value': 1}]}])
    rs.apply_changes(1, 'd', [
        {'actor': 'a1', 'seq': 1, 'deps': {'a0': 1},
         'ops': [{'action': 'set', 'obj': ROOT, 'key': 'y', 'value': 2}]}])
    # replica 0 receives a1's change OUT OF ORDER relative to... it already
    # has a0:1, so ship a1's stream via catch-up and confirm convergence
    rs.catch_up()
    assert rs.converged()
    patch = rs.assert_identical('d')
    keys = {d['key'] for d in patch['diffs']}
    assert keys == {'x', 'y'}


def test_sixteen_replica_text_backlog():
    """Mid-size RGA stress: 16 replicas, concurrent text edits, full
    catch-up converges byte-identically."""
    n = 16
    rs = BatchedReplicaSet(n)
    # seed change shared by all replicas (the doc's creation)
    seed = {'actor': 'a0', 'seq': 1, 'deps': {},
            'ops': [{'action': 'makeText', 'obj': 'T'},
                    {'action': 'ins', 'obj': 'T', 'key': '_head',
                     'elem': 1},
                    {'action': 'set', 'obj': 'T', 'key': 'a0:1',
                     'value': 'x'},
                    {'action': 'link', 'obj': ROOT, 'key': 'text',
                     'value': 'T'}]}
    all_changes = [seed]
    for r in range(n):
        rs.apply_changes(r, 'd', [dict(seed)])
    for r in range(n):
        actor = 'a%d' % r
        seq0 = 2 if r == 0 else 1
        ops = []
        for i in range(4):
            elem = 100 + r * 10 + i
            prev = 'a0:1' if i == 0 else '%s:%d' % (actor, elem - 1)
            ops.append({'action': 'ins', 'obj': 'T', 'key': prev,
                        'elem': elem})
            ops.append({'action': 'set', 'obj': 'T',
                        'key': '%s:%d' % (actor, elem),
                        'value': chr(97 + (r + i) % 26)})
        change = {'actor': actor, 'seq': seq0, 'deps': {'a0': 1},
                  'ops': ops}
        rs.apply_changes(r, 'd', [change])
        all_changes.append(change)
    rs.catch_up()
    assert rs.converged()
    patch = rs.assert_identical('d')
    state = oracle_init()
    state, _ = oracle_apply(state, all_changes)
    want = oracle_get_patch(state)
    assert patch['clock'] == want['clock']
    assert patch_to_tree(patch) == patch_to_tree(want)
