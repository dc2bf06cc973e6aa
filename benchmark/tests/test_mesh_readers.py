"""The readers of the doc-sharded pool's span and counters, on a window
written out by hand: each reads its span or counter per million ops
completed, and nothing where the program has no mesh (the one-chip
pool, or a parent without these numbers)."""

import pytest

from harness import spec

OPS = 4_000_000

READS = {'mesh_run_s_per_mop.catchup': ('spans', 'shard.run'),
         'mesh_wait_s_per_mop.catchup': ('counters',
                                         'mesh.collective_wait_s'),
         'mesh_skew_s_per_mop.catchup': ('counters',
                                         'mesh.encode_shard_skew_s')}


def _ctx(spans=None, counters=None, ops=OPS):
    return {'client': {'ops_done': ops, 'window_s': 10.0},
            'program': {'spans': spans or {}, 'counters': counters or {},
                        'pool_batch': {'s': 0.0, 'n': 0}}}


def _recorded(metric, value):
    where, name = READS[metric]
    if where == 'spans':
        return _ctx(spans={name: {'s': value, 'n': 30},
                           'scheduler.flush': {'s': 9.0, 'n': 30}},
                    counters={'mesh.shards': 120.0})
    return _ctx(spans={'shard.run': {'s': 5.0, 'n': 30}},
                counters={name: value, 'mesh.shards': 120.0})


@pytest.mark.parametrize('metric', sorted(READS))
def test_mesh_reader_per_mop(metric):
    assert spec.reader(metric)(_recorded(metric, 2.0)) \
        == pytest.approx(0.5)


@pytest.mark.parametrize('metric', sorted(READS))
def test_mesh_reader_absent_reads_nothing(metric):
    # the one-chip pool, and the parent, have none of these
    ctx = _ctx(spans={'scheduler.flush': {'s': 9.0, 'n': 17},
                      'host.begin': {'s': 3.0, 'n': 34}},
               counters={'transfer.h2d_bytes': 1e6})
    assert spec.reader(metric)(ctx) is None
    assert spec.reader(metric)(_recorded(metric, 2.0) | {
        'client': {'ops_done': 0, 'window_s': 10.0}}) is None


def test_mesh_wait_reads_zero_when_no_chip_waited():
    # the window's delta drops a counter that never moved: a mesh whose
    # device outputs were always ready when claimed waited 0 s
    ctx = _ctx(spans={'shard.run': {'s': 5.0, 'n': 30}},
               counters={'mesh.shards': 120.0,
                         'mesh.encode_shard_skew_s': 1.0})
    assert spec.reader('mesh_wait_s_per_mop.catchup')(ctx) == 0.0
