"""Whole runs of each cell on the CPU at a size a test can hold: the
harness's look for a chip is skipped and everything else runs, with the
timed path sound and then broken underneath.  `correct` must be true for
the sound run and false for the control and for every fault the cells
can have:

  control   one doc in 16 of each flush acknowledged and not applied
  stale     a flush that leaves the state as it was and answers anyway
  half      half of each flush's docs left out of the batch
  altered   every answer altered where the pool produces it
"""

import json
import os
import time

import pytest

from harness import runner, spec

ROOT = spec.HERE.rsplit('/', 1)[0]

SMALL = {
    'text_docs_10k.catchup': ({'docs': 64},
                              {'slots_per_backlog': 4, 'warm_requests': 2,
                               'generators': 1, 'lookahead': 4}),
    'map_docs_1k.catchup': ({'docs': 32},
                            {'slots_per_backlog': 4, 'warm_requests': 2,
                             'generators': 1, 'lookahead': 4}),
}


def _cells():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return [w['name'] for w in json.load(f)['workloads']]


def _run(name, fault, seed=2 ** 33 + 17):
    cell = spec.load(ROOT, name)
    cfg, mix = SMALL[name]
    cell['config'].update(cfg)
    cell['traffic'].update(mix)
    return runner.run(cell, seed, 2.0, False, time.monotonic(),
                      allow_cpu=True, fault=fault)


@pytest.mark.parametrize('name', _cells())
@pytest.mark.parametrize('fault', [None, 'control', 'stale', 'half',
                                   'altered'])
def test_correct_only_when_sound(name, fault):
    res = _run(name, fault)
    assert res['correct'] is (fault is None), res['compared']
    assert list(res)[-1] == 'compared'
    assert all(v['limit'] == 0 for v in res['compared'].values())
    assert res['metrics']['setup_s']['value'] > 0
