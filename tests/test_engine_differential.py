"""Differential tests: the native pool must emit patches byte-identical
to the scalar oracle for the same change streams, on the CPU default
(full C++ host path) and on the chip's dispatch (C++ host plus the JAX
kernels) -- the project's generalization of the reference's hand-built
change/patch JSON contract (its `test/backend_test.js`).
"""

import random

import pytest

import automerge_tpu as am
from automerge_tpu import backend as Backend
from tests.conftest import native_lanes

ROOT_ID = '00000000-0000-0000-0000-000000000000'


def deliver_and_compare(change_batches, n_docs=1, pools=None):
    """Oracle vs native `host` vs native `kernel` (or vs `pools`) on
    identical change batches: patch-equal at every delivery and
    getPatch-equal at the end."""
    oracle_states = {d: Backend.init() for d in range(n_docs)}
    pools = native_lanes() if pools is None else pools

    for batch in change_batches:
        # batch: {doc: [changes]}
        expected = {}
        for doc, changes in batch.items():
            oracle_states[doc], patch = Backend.apply_changes(
                oracle_states[doc], [dict(c) for c in changes])
            expected[doc] = patch
        for pool in pools:
            got = pool.apply_batch(batch)
            for doc in batch:
                assert got[doc] == expected[doc], (
                    '%r patch mismatch for doc %r:\nexpected %r\ngot'
                    '      %r' % (pool, doc, expected[doc], got[doc]))

    for doc in range(n_docs):
        want = Backend.get_patch(oracle_states[doc])
        for pool in pools:
            got = pool.get_patch(doc)
            assert got == want, (
                '%r getPatch mismatch:\nexpected %r\ngot      %r'
                % (pool, want, got))


class TestMapParity:
    def test_simple_sets(self):
        actor = 'actor-a'
        deliver_and_compare([
            {0: [{'actor': actor, 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'bird',
                 'value': 'magpie'}]}]},
            {0: [{'actor': actor, 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'bird',
                 'value': 'jay'},
                {'action': 'del', 'obj': ROOT_ID, 'key': 'bird'}]}]},
        ])

    def test_concurrent_conflict(self):
        deliver_and_compare([
            {0: [{'actor': 'a1', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 1}]}]},
            {0: [{'actor': 'a2', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'k', 'value': 2}]}]},
            {0: [{'actor': 'a3', 'seq': 1, 'deps': {'a1': 1, 'a2': 1},
                  'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                           'value': 3}]}]},
        ])

    def test_nested_maps_and_links(self):
        actor = 'actor-a'
        deliver_and_compare([
            {0: [{'actor': actor, 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeMap', 'obj': 'obj-1'},
                {'action': 'set', 'obj': 'obj-1', 'key': 'wrens', 'value': 3},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'birds',
                 'value': 'obj-1'}]}]},
            {0: [{'actor': actor, 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'del', 'obj': 'obj-1', 'key': 'wrens'},
                {'action': 'set', 'obj': 'obj-1', 'key': 'sparrows',
                 'value': 15}]}]},
        ])

    def test_out_of_order_buffering(self):
        actor = 'actor-a'
        c1 = {'actor': actor, 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'a', 'value': 1}]}
        c2 = {'actor': actor, 'seq': 2, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'b', 'value': 2}]}
        deliver_and_compare([{0: [c2]}, {0: [c1]}])

    def test_timestamps(self):
        deliver_and_compare([
            {0: [{'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': ROOT_ID, 'key': 'now',
                 'value': 1234567890123, 'datatype': 'timestamp'}]}]},
        ])


class TestListParity:
    def test_create_and_insert(self):
        actor = 'actor-a'
        deliver_and_compare([
            {0: [{'actor': actor, 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeList', 'obj': 'list-1'},
                {'action': 'ins', 'obj': 'list-1', 'key': '_head', 'elem': 1},
                {'action': 'set', 'obj': 'list-1', 'key': '%s:1' % actor,
                 'value': 'chaffinch'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'birds',
                 'value': 'list-1'}]}]},
            {0: [{'actor': actor, 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'set', 'obj': 'list-1', 'key': '%s:1' % actor,
                 'value': 'greenfinch'}]}]},
            {0: [{'actor': actor, 'seq': 3, 'deps': {}, 'ops': [
                {'action': 'del', 'obj': 'list-1', 'key': '%s:1' % actor}]}]},
        ])

    def test_interleaved_inserts_deletes(self):
        actor = 'actor-a'
        deliver_and_compare([
            {0: [{'actor': actor, 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeText', 'obj': 'text-1'},
                {'action': 'ins', 'obj': 'text-1', 'key': '_head', 'elem': 1},
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
                {'action': 'ins', 'obj': 'list-1', 'key': '_head', 'elem': 1},
                {'action': 'set', 'obj': 'list-1', 'key': 'aa:1',
                 'value': 'base'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'l',
                 'value': 'list-1'}]}]},
            # two actors concurrently insert after 'aa:1'
            {0: [{'actor': 'aa', 'seq': 2, 'deps': {}, 'ops': [
                {'action': 'ins', 'obj': 'list-1', 'key': 'aa:1', 'elem': 2},
                {'action': 'set', 'obj': 'list-1', 'key': 'aa:2',
                 'value': 'from-aa'}]}]},
            {0: [{'actor': 'zz', 'seq': 1, 'deps': {'aa': 1}, 'ops': [
                {'action': 'ins', 'obj': 'list-1', 'key': 'aa:1', 'elem': 2},
                {'action': 'set', 'obj': 'list-1', 'key': 'zz:2',
                 'value': 'from-zz'}]}]},
        ])

    def test_concurrent_set_and_delete_resurrection(self):
        deliver_and_compare([
            {0: [{'actor': 'aa', 'seq': 1, 'deps': {}, 'ops': [
                {'action': 'makeList', 'obj': 'list-1'},
                {'action': 'ins', 'obj': 'list-1', 'key': '_head', 'elem': 1},
                {'action': 'set', 'obj': 'list-1', 'key': 'aa:1',
                 'value': 'x'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'l',
                 'value': 'list-1'}]}]},
            {0: [
                {'actor': 'aa', 'seq': 2, 'deps': {}, 'ops': [
                    {'action': 'del', 'obj': 'list-1', 'key': 'aa:1'}]},
                {'actor': 'bb', 'seq': 1, 'deps': {'aa': 1}, 'ops': [
                    {'action': 'set', 'obj': 'list-1', 'key': 'aa:1',
                     'value': 'resurrected'}]},
            ]},
        ])


class WorkloadGen:
    """Random valid multi-actor workload built through the real frontend,
    then replayed change-by-change into both backends."""

    def __init__(self, seed, n_actors=3, structure='mixed'):
        self.rng = random.Random(seed)
        self.structure = structure
        self.actors = sorted('actor-%02d' % i for i in range(n_actors))

    def generate(self, n_rounds):
        rng = self.rng
        docs = {a: am.init(a) for a in self.actors}
        seen = {a: am.init('obs-' + a) for a in self.actors}  # change trackers
        log = {a: [] for a in self.actors}

        def mutate(doc):
            def cb(d):
                choice = rng.random()
                if self.structure in ('mixed', 'map') and choice < 0.45:
                    key = 'k%d' % rng.randrange(4)
                    d[key] = rng.randrange(100)
                elif self.structure in ('mixed', 'list'):
                    if 'items' not in d:
                        d['items'] = []
                    items = d['items']
                    n = len(items)
                    action = rng.random()
                    if n == 0 or action < 0.6:
                        items.insert_at(rng.randrange(n + 1),
                                        'v%d' % rng.randrange(50))
                    elif action < 0.8 and n > 0:
                        items[rng.randrange(n)] = 'w%d' % rng.randrange(50)
                    elif n > 0:
                        items.delete_at(rng.randrange(n))
                else:
                    d['x'] = rng.randrange(10)
            return cb

        for _ in range(n_rounds):
            a = rng.choice(self.actors)
            docs[a] = am.change(docs[a], mutate(docs[a]))
            # occasionally sync actor pairs
            if rng.random() < 0.5:
                b = rng.choice([x for x in self.actors if x != a])
                docs[b] = am.merge(docs[b], docs[a])

        # full convergence at the end
        for a in self.actors:
            for b in self.actors:
                if a != b:
                    docs[b] = am.merge(docs[b], docs[a])

        # extract every actor's changes from one converged doc
        final = docs[self.actors[0]]
        return am.get_changes(am.init('empty-observer'), final)


class TestRandomWorkloads:
    @pytest.mark.parametrize('seed,structure', [
        (1, 'map'), (2, 'map'), (3, 'list'), (4, 'list'),
        (5, 'mixed'), (6, 'mixed'), (7, 'mixed'),
    ])
    def test_in_order_delivery(self, seed, structure):
        changes = WorkloadGen(seed, structure=structure).generate(20)
        deliver_and_compare([{0: [c]} for c in changes])

    @pytest.mark.parametrize('seed', [11, 12, 13])
    def test_shuffled_delivery(self, seed):
        rng = random.Random(seed)
        changes = WorkloadGen(seed, structure='mixed').generate(16)
        shuffled = list(changes)
        rng.shuffle(shuffled)
        deliver_and_compare([{0: shuffled}])

    @pytest.mark.parametrize('seed', [21, 22])
    def test_batched_delivery(self, seed):
        rng = random.Random(seed)
        changes = WorkloadGen(seed, structure='mixed').generate(18)
        batches = []
        i = 0
        while i < len(changes):
            k = rng.randint(1, 5)
            batches.append({0: changes[i:i + k]})
            i += k
        deliver_and_compare(batches)

    def test_multi_doc_batch(self):
        all_changes = [WorkloadGen(30 + i, structure='mixed').generate(10)
                       for i in range(4)]
        # deliver each doc's full stream in one multi-doc batch
        deliver_and_compare(
            [{d: all_changes[d] for d in range(4)}], n_docs=4)


class TestRotatingFuzz:
    """Seed-rotating nightly-style fuzz (VERDICT round-1 item 9): larger
    workloads than the fixed-seed suites, driving the NATIVE pool too.
    The seed rotates daily (or comes from AMTPU_FUZZ_SEED) and is printed
    on failure so any run is reproducible."""

    @staticmethod
    def base_seed():
        import datetime
        import os
        env = os.environ.get('AMTPU_FUZZ_SEED')
        if env:
            return int(env)
        return int(datetime.date.today().strftime('%Y%m%d'))

    @pytest.mark.parametrize('lane', range(3))
    def test_rotating_three_backend_fuzz(self, lane):
        seed = self.base_seed() * 10 + lane
        print('fuzz seed: %d (override with AMTPU_FUZZ_SEED)' % seed)
        rng = random.Random(seed)
        structure = ('map', 'list', 'mixed')[lane]
        changes = WorkloadGen(seed, n_actors=4,
                              structure=structure).generate(60)
        # random batching, sometimes shuffled within a batch
        batches = []
        i = 0
        while i < len(changes):
            k = rng.randint(1, 8)
            chunk = list(changes[i:i + k])
            if rng.random() < 0.3:
                rng.shuffle(chunk)
            batches.append({0: chunk})
            i += k
        deliver_and_compare(batches)

    def test_rotating_multi_doc_fuzz(self):
        seed = self.base_seed()
        print('fuzz seed: %d (override with AMTPU_FUZZ_SEED)' % seed)
        streams = [WorkloadGen(seed + 100 + d, structure='mixed')
                   .generate(25) for d in range(6)]
        deliver_and_compare(
            [{d: streams[d] for d in range(6)}], n_docs=6)
