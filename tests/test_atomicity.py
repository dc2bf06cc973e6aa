"""Failed applies must leave the pool untouched.

The reference backend is immutable: a change that throws mid-apply leaves the
caller holding the old state, so the change is neither recorded nor shipped
(`/root/reference/backend/index.js:144-155` -- the caller's binding keeps the
pre-call value on throw).  The long-lived pools must match: validation runs
read-only BEFORE clock/states/arenas commit, and the causal queue is rolled
back on error.
"""

import pytest

from automerge_tpu.errors import AutomergeError
from automerge_tpu.native import NativeDocPool, ShardedNativePool

ROOT = '00000000-0000-0000-0000-000000000000'

#: every pool lane (tests/conftest.py): the CPU default, the chip's
#: kernel path, and a two-shard pool
LANES = pytest.mark.parametrize('pool_lane', ['host', 'kernel', 'sharded'],
                                indirect=True)


def good(seq, key='k', value=1):
    return {'actor': 'A', 'seq': seq, 'deps': {},
            'ops': [{'action': 'set', 'obj': ROOT, 'key': key,
                     'value': value}]}


@LANES
def test_failed_batch_fully_rolls_back(pool_lane):
    pool = pool_lane()
    bad = {'actor': 'A', 'seq': 2, 'deps': {},
           'ops': [{'action': 'set', 'obj': 'nonexistent', 'key': 'x',
                    'value': 1}]}
    with pytest.raises(AutomergeError, match='unknown object'):
        pool.apply_changes('d', [good(1), bad])
    # NOTHING from the failed batch committed: the valid first change must
    # re-apply (it would be dropped as a duplicate if the clock advanced)
    assert pool.get_patch('d')['clock'] == {}
    assert pool.get_missing_changes('d', {}) == []
    patch = pool.apply_changes('d', [good(1)])
    assert [d['key'] for d in patch['diffs']] == ['k']
    assert pool.get_patch('d')['clock'] == {'A': 1}


@LANES
def test_failed_batch_restores_causal_queue(pool_lane):
    pool = pool_lane()
    # queue a change with an unmet dependency, then fail a later batch
    future = good(2, key='later')
    pool.apply_changes('d', [future])
    assert pool.get_missing_deps('d') == {'A': 1}
    bad = {'actor': 'B', 'seq': 1, 'deps': {},
           'ops': [{'action': 'set', 'obj': 'nonexistent', 'key': 'x',
                    'value': 1}]}
    with pytest.raises(AutomergeError, match='unknown object'):
        pool.apply_changes('d', [bad])
    # the queued change survived the failed batch
    assert pool.get_missing_deps('d') == {'A': 1}
    patch = pool.apply_changes('d', [good(1)])
    assert pool.get_patch('d')['clock'] == {'A': 2}
    assert {d['key'] for d in patch['diffs']} == {'k', 'later'}


@LANES
def test_missing_list_element_fails_before_commit(pool_lane):
    pool = pool_lane()
    pool.apply_changes('d', [
        {'actor': 'A', 'seq': 1, 'deps': {},
         'ops': [{'action': 'makeText', 'obj': 'T'},
                 {'action': 'link', 'obj': ROOT, 'key': 't',
                  'value': 'T'}]}])
    bad = {'actor': 'A', 'seq': 2, 'deps': {},
           'ops': [{'action': 'set', 'obj': 'T', 'key': 'A:99',
                    'value': 'x'}]}
    with pytest.raises(AutomergeError, match='Missing index entry'):
        pool.apply_changes('d', [bad])
    assert pool.get_patch('d')['clock'] == {'A': 1}
    # a del on a missing element is silently dropped, not an error
    patch = pool.apply_changes('d', [
        {'actor': 'A', 'seq': 2, 'deps': {},
         'ops': [{'action': 'del', 'obj': 'T', 'key': 'A:99'}]}])
    assert patch['diffs'] == []
    assert pool.get_patch('d')['clock'] == {'A': 2}


@LANES
def test_inconsistent_seq_reuse_rejected_without_commit(pool_lane):
    pool = pool_lane()
    pool.apply_changes('d', [good(1)])
    with pytest.raises(AutomergeError, match='Inconsistent reuse'):
        pool.apply_changes('d', [good(1, value=999)])
    # exact duplicate still tolerated afterwards
    assert pool.apply_changes('d', [good(1)])['diffs'] == []


@LANES
def test_multi_error_batches_surface_first_error_in_op_order(pool_lane):
    """When a batch contains several invalid ops, the FIRST one in
    application order wins -- the oracle applies ops strictly in order, so
    every backend must report the same error for the same input."""
    pool = pool_lane()
    pool.apply_changes('d', [
        {'actor': 'A', 'seq': 1, 'deps': {},
         'ops': [{'action': 'makeText', 'obj': 'T'},
                 {'action': 'link', 'obj': ROOT, 'key': 't',
                  'value': 'T'}]}])
    bad = {'actor': 'A', 'seq': 2, 'deps': {},
           'ops': [{'action': 'set', 'obj': 'T', 'key': 'A:99',
                    'value': 'x'},          # error 1: absent list element
                   {'action': 'makeText', 'obj': 'T'}]}  # error 2: dup
    with pytest.raises(AutomergeError, match='Missing index entry'):
        pool.apply_changes('d', [bad])
    assert pool.get_patch('d')['clock'] == {'A': 1}


@LANES
def test_assign_before_insert_in_same_change_rejected(pool_lane):
    """An assign referencing an element inserted LATER in the same change
    must error: the oracle applies ops in order, so the element does not
    exist yet when the assign runs."""
    pool = pool_lane()
    bad = {'actor': 'A', 'seq': 1, 'deps': {},
           'ops': [{'action': 'makeText', 'obj': 'T'},
                   {'action': 'set', 'obj': 'T', 'key': 'A:1',
                    'value': 'x'},
                   {'action': 'ins', 'obj': 'T', 'key': '_head',
                    'elem': 1}]}
    with pytest.raises(AutomergeError, match='Missing index entry'):
        pool.apply_changes('d', [bad])
    assert pool.get_patch('d')['clock'] == {}


def test_queries_do_not_materialize_phantom_docs():
    pool = NativeDocPool()
    assert pool.get_patch('never-created')['diffs'] == []
    assert pool.get_missing_deps('never-created') == {}
    assert pool.get_missing_changes('never-created', {}) == []
    assert pool.get_changes_for_actor('never-created', 'A') == []
    assert pool.get_register('never-created', ROOT, 'k') == []
    # the doc must still be creatable with full semantics afterwards
    patch = pool.apply_changes('never-created', [good(1)])
    assert [d['key'] for d in patch['diffs']] == ['k']


@pytest.mark.parametrize('make_pool', [NativeDocPool,
                                       lambda: ShardedNativePool(n_shards=2)])
def test_out_of_range_elem_counter_rejected(make_pool):
    """Arena columns are i32 (the kernel layout): inserts with counters
    outside that range are rejected atomically, never silently truncated."""
    pool = make_pool()
    pool.apply_changes('d', [
        {'actor': 'A', 'seq': 1, 'deps': {},
         'ops': [{'action': 'makeText', 'obj': 'T'}]}])
    for elem in (-1, 2 ** 31, 2 ** 40):
        with pytest.raises(AutomergeError, match='out of range'):
            pool.apply_changes('d', [
                {'actor': 'A', 'seq': 2, 'deps': {},
                 'ops': [{'action': 'ins', 'obj': 'T', 'key': '_head',
                          'elem': elem}]}])
    assert pool.get_patch('d')['clock'] == {'A': 1}
    # a huge counter inside an elemId string is malformed, not a wrap
    with pytest.raises(AutomergeError, match='Missing index entry'):
        pool.apply_changes('d', [
            {'actor': 'A', 'seq': 2, 'deps': {},
             'ops': [{'action': 'ins', 'obj': 'T',
                      'key': 'A:99999999999999999999', 'elem': 1}]}])
