"""Test configuration: force JAX onto a virtual 8-device CPU mesh so the
multi-chip sharding paths (dp/sp over a Mesh) are exercised without TPU
hardware, per the build contract."""

import contextlib
import functools
import os
import re
import sys

import pytest

os.environ['JAX_PLATFORMS'] = 'cpu'
# force 8 virtual devices even if the env presets a different count
flags = re.sub(r'--xla_force_host_platform_device_count=\d+', '',
               os.environ.get('XLA_FLAGS', ''))
os.environ['XLA_FLAGS'] = (
    flags + ' --xla_force_host_platform_device_count=8').strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: The env each pool lane runs under (None: the flag is unset).  `host`
#: is the CPU default: the full C++ host path, no kernel dispatch.
#: `kernel` is the chip's dispatch: the C++ host plus the register, rank
#: and dominance kernels, with wide register groups on the escalation
#: ladder (the CPU-only hostreg shortcut off).  `sharded` is a two-shard
#: pool on the default path.  The flags are read per batch, so lanes can
#: alternate in one process.
_DEFAULT = {'AMTPU_HOST_FULL': None, 'AMTPU_HOST_DOM': None,
            'AMTPU_HOST_REG': None}
LANE_ENV = {
    'host': _DEFAULT,
    'kernel': {'AMTPU_HOST_FULL': '0', 'AMTPU_HOST_DOM': '0',
               'AMTPU_HOST_REG': '0'},
    'sharded': _DEFAULT,
}


def _set_lane_env(mp, lane):
    for k, v in LANE_ENV[lane].items():
        if v is None:
            mp.delenv(k, raising=False)
        else:
            mp.setenv(k, v)


@contextlib.contextmanager
def lane_env(lane):
    """The lane's env for the length of the block."""
    with pytest.MonkeyPatch.context() as mp:
        _set_lane_env(mp, lane)
        yield


def new_lane_pool(lane):
    """A fresh pool of the lane; the caller holds the lane's env."""
    from automerge_tpu.native import NativeDocPool, ShardedNativePool
    if lane == 'sharded':
        return ShardedNativePool(n_shards=2)
    return NativeDocPool()


class LanePool(object):
    """A pool that runs every call under its lane's env, so pools of two
    lanes can take turns inside one test."""

    def __init__(self, lane):
        self.lane = lane
        with lane_env(lane):
            self._pool = new_lane_pool(lane)

    def __repr__(self):
        return 'LanePool(%r)' % self.lane

    def __getattr__(self, name):
        attr = getattr(self._pool, name)

        def call(*args, **kwargs):
            with lane_env(self.lane):
                return attr(*args, **kwargs)
        return call


def native_lanes():
    """One native pool per lane the chip-vs-CPU parity rests on: the
    CPU default (`host`) and the chip's dispatch (`kernel`)."""
    return [LanePool('host'), LanePool('kernel')]


@pytest.fixture(params=['host', 'kernel'])
def pool_lane(request, monkeypatch):
    """A pool factory for one lane, with the lane's env set for the
    whole test.  A file widens or narrows the lanes with
    ``@pytest.mark.parametrize('pool_lane', [...], indirect=True)``."""
    _set_lane_env(monkeypatch, request.param)
    factory = functools.partial(new_lane_pool, request.param)
    factory.lane = request.param
    return factory
