"""The four-chip cell's whole run on the CPU at a size a test can hold,
over the doc-sharded pool on four virtual devices (a CPU process keeps
the single pool unless ``AMTPU_MESH`` asks for the mesh): `correct` must
be true for the sound run and false for the control and for every fault
`test_faults` plants, and every flush must have gone through the mesh.
"""

import time

import pytest

from automerge_tpu import telemetry
from automerge_tpu.utils.jaxenv import ensure_cpu_devices
from harness import runner, spec

ROOT = spec.HERE.rsplit('/', 1)[0]
CELL = 'text_docs_10k_dp4.catchup'

# binds only before anything starts JAX's backend: collection imports
# this module before any test runs
ensure_cpu_devices(4)


@pytest.mark.parametrize('fault', [None, 'control', 'stale', 'half',
                                   'altered'])
def test_dp4_cell_correct_only_when_sound(monkeypatch, fault):
    monkeypatch.setenv('AMTPU_MESH', '4')
    cell = spec.load(ROOT, CELL)
    cell['config'].update({'docs': 64})
    cell['traffic'].update({'slots_per_backlog': 4, 'warm_requests': 2,
                            'generators': 1, 'lookahead': 4})
    shards = telemetry.metrics_snapshot().get('mesh.shards', 0)
    res = runner.run(cell, 2 ** 33 + 17, 2.0, False, time.monotonic(),
                     allow_cpu=True, fault=fault)
    assert res['correct'] is (fault is None), res['compared']
    assert res['device']['count'] >= 4
    assert telemetry.metrics_snapshot()['mesh.shards'] > shards
