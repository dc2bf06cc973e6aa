"""Pool-level mesh execution lanes (ISSUE 7): `MeshDocPool` must be a
drop-in for `NativeDocPool` -- byte-identical patches across dp widths
on real workloads, resilience pass-through at per-doc granularity, the
AMTPU_MESH latch guard, and the sp-axis fence's routing policy.

The suite process runs on 8 virtual CPU devices (conftest), so dp
placement is real multi-device; the latch lane runs in a subprocess
because the latch-at-first-batch snapshot is process-global.
"""

import os
import subprocess
import sys

import msgpack
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from automerge_tpu import faults, resilience, telemetry  # noqa: E402
from automerge_tpu.native import NativeDocPool, make_pool  # noqa: E402
from automerge_tpu.native.mesh_pool import MeshDocPool  # noqa: E402
from automerge_tpu.parallel import mesh_encode  # noqa: E402

ROOT = '00000000-0000-0000-0000-000000000000'


def _real_workload(n_docs=24):
    """Mixed real wire-format changes: long-ish text histories plus
    map- and table-shaped docs -- the three demo classes the mesh
    encoder/tests already pin against the pool."""
    docs = dict(mesh_encode.demo_text_workload(n_docs // 2))
    for d, chs in mesh_encode.demo_map_workload(n_docs // 4).items():
        docs['m-%d' % d] = chs
    for d, chs in mesh_encode.demo_table_workload(n_docs // 4).items():
        docs['tb-%d' % d] = chs
    return {NativeDocPool._doc_key(str(d)): chs for d, chs in docs.items()}


def _payload(docs):
    return msgpack.packb(docs, use_bin_type=True)


def _per_doc(raw):
    out = msgpack.unpackb(raw, raw=False, strict_map_key=False)
    return {d: msgpack.packb(p, use_bin_type=True) for d, p in out.items()}


@pytest.fixture(scope='module')
def workload_and_reference():
    docs = _real_workload()
    payload = _payload(docs)
    want = _per_doc(NativeDocPool().apply_batch_bytes(payload))
    return docs, payload, want


@pytest.mark.parametrize('dp', [1, 2, 4])
def test_mesh_pool_byte_parity_across_dp(dp, workload_and_reference):
    docs, payload, want = workload_and_reference
    pool = MeshDocPool(dp=dp)
    got = _per_doc(pool.apply_batch_bytes(payload))
    assert set(got) == set(want)
    bad = [d for d in want if got[d] != want[d]]
    assert not bad, 'dp=%d lost byte parity on %r' % (dp, bad[:3])
    # per-doc queries route to the owning chip and agree with the
    # single-device pool
    ref = NativeDocPool()
    ref.apply_batch_bytes(payload)
    for d in list(docs)[:4]:
        assert pool.get_patch(d) == ref.get_patch(d)
        assert pool.get_clock(d) == ref.get_clock(d)


def test_mesh_pool_places_chips_on_distinct_devices():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip('needs 4 devices')
    pool = MeshDocPool(dp=4)
    devs = [p.device for p in pool.pools]
    assert len(set(devs)) == 4, devs


def test_mesh_pool_device_shortfall_raises():
    """A mesh that needs more devices than exist is refused: several
    chips' work never shares one device."""
    import jax
    n = len(jax.devices())
    telemetry.metrics_reset()
    pool = MeshDocPool(dp=n + 1)
    with pytest.raises(RuntimeError, match='only %d exist' % n):
        pool.pools
    assert telemetry.metrics_snapshot().get('mesh.device_shortfall') == 1


def test_mesh_pool_serves_kernel_path_with_zero_oracle(
        workload_and_reference):
    _docs, payload, _want = workload_and_reference
    telemetry.metrics_reset()
    MeshDocPool(dp=2).apply_batch_bytes(payload)
    snap = telemetry.metrics_snapshot()
    assert snap.get('mesh.batches', 0) >= 1, snap
    assert snap.get('mesh.shards', 0) >= 2, snap
    assert snap.get('fallback.oracle', 0) == 0, snap


def test_mesh_pool_poison_doc_quarantines_only_that_doc(
        workload_and_reference):
    docs, payload, want = workload_and_reference
    poison = sorted(docs)[len(docs) // 2]
    telemetry.metrics_reset()
    faults.arm('device.dispatch', 'permanent', 1.0, match=poison)
    try:
        got = _per_doc(MeshDocPool(dp=4).apply_batch_bytes_resilient(
            payload))
    finally:
        faults.disarm()
    snap = telemetry.metrics_snapshot()
    quarantined = [
        d for d in got
        if resilience.is_quarantined(
            msgpack.unpackb(got[d], raw=False, strict_map_key=False))]
    assert quarantined == [poison], quarantined
    assert snap.get('resilience.quarantined', 0) == 1, snap
    bad = [d for d in want if d != poison and got[d] != want[d]]
    assert not bad, 'healthy docs lost parity under poison: %r' % bad[:3]


def test_make_pool_factory_honors_amtpu_mesh(monkeypatch):
    monkeypatch.setenv('AMTPU_MESH', '2')
    pool = make_pool()
    assert isinstance(pool, MeshDocPool)
    assert (pool.dp, pool.sp) == (2, 1)
    monkeypatch.setenv('AMTPU_MESH', '2,4')
    pool = make_pool()
    assert (pool.dp, pool.sp) == (2, 4)
    monkeypatch.delenv('AMTPU_MESH')
    assert type(make_pool()) is NativeDocPool
    monkeypatch.setenv('AMTPU_MESH', '0')
    assert type(make_pool()) is NativeDocPool
    monkeypatch.setenv('AMTPU_MESH', 'banana')
    with pytest.raises(ValueError):
        make_pool()


def test_sp_fence_routing_policy(monkeypatch):
    """The sp-axis triage (ISSUE 7 satellite): sp sharding routes only
    past the measured long-list crossover, never onto devices the dp
    axis owns, and never on a malformed topology."""
    import jax

    from automerge_tpu.native import resident
    if len(jax.devices()) < 2:
        pytest.skip('needs 2 devices')
    crossover = resident.SP_CROSSOVER_ELEMS
    monkeypatch.delenv('AMTPU_MESH', raising=False)
    monkeypatch.delenv('AMTPU_MESH_SP_MIN', raising=False)
    # legacy auto policy: long arenas shard, short ones are fenced;
    # only the dispatch-site call (count_fenced) records the counter
    assert resident._sp_sharding(crossover) is not None
    telemetry.metrics_reset()
    assert resident._sp_sharding(8192) is None
    assert telemetry.metrics_snapshot().get('mesh.sp_fenced', 0) == 0
    assert resident._sp_sharding(8192, count_fenced=True) is None
    assert telemetry.metrics_snapshot().get('mesh.sp_fenced', 0) == 1
    # dp-only mesh: every device is a dp chip -- sp never engages
    monkeypatch.setenv('AMTPU_MESH', '4')
    assert resident._sp_sharding(crossover) is None
    # explicit dp=1,sp topology: sharding over exactly sp devices
    monkeypatch.setenv('AMTPU_MESH', '1,2')
    sh = resident._sp_sharding(crossover)
    assert sh is not None and sh.mesh.size == 2
    # still fenced below the crossover even when opted in
    assert resident._sp_sharding(8192) is None
    # crossover override opens the short arena up
    monkeypatch.setenv('AMTPU_MESH_SP_MIN', '4096')
    assert resident._sp_sharding(8192) is not None
    # malformed topology never shards
    monkeypatch.setenv('AMTPU_MESH', 'dp=oops')
    assert resident._sp_sharding(crossover) is None


SP_FROM_AXES = r"""
import os, sys
sys.path.insert(0, REPO_PATH)
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu import telemetry, backend as Backend
from automerge_tpu.native import make_pool
from automerge_tpu.native.mesh_pool import MeshDocPool
ROOT = '00000000-0000-0000-0000-000000000000'
telemetry.enable()
ops, prev = [{'action': 'makeText', 'obj': 't'},
             {'action': 'link', 'obj': ROOT, 'key': 'text', 'value': 't'}], '_head'
for e in range(1, 301):
    ops.append({'action': 'ins', 'obj': 't', 'key': prev, 'elem': e})
    ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%d' % e, 'value': 'x'})
    prev = 'a0:%d' % e
chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': ops}]
st, _ = Backend.apply_changes(Backend.init(), chs)
assert make_pool().dp == 4
engaged = []
for sp in (1, 2):
    pool = MeshDocPool(dp=1, sp=sp)
    before = telemetry.metrics_snapshot().get('mesh.sp_engaged', 0)
    pool.apply_changes('doc', chs)
    engaged.append(telemetry.metrics_snapshot().get('mesh.sp_engaged', 0)
                   > before)
    assert pool.get_patch('doc') == Backend.get_patch(st), sp
assert engaged == [False, True], engaged
assert not telemetry.metrics_snapshot().get('mesh.latch_flip_ignored')
print('SP-FROM-AXES-OK')
""".replace('REPO_PATH', repr(REPO))


def test_sp_follows_the_pools_own_axes():
    """MeshDocPool(dp=1, sp=2) shards a long arena over its sp devices
    while AMTPU_MESH names another topology (dp=4, never flipped), and
    sp=1 does not: the axes given are the axes used."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_MESH='4',
               AMTPU_RESIDENT='1', AMTPU_RESIDENT_MIN='16',
               AMTPU_MESH_SP_MIN='16',
               XLA_FLAGS='--xla_force_host_platform_device_count=8')
    out = subprocess.run([sys.executable, '-c', SP_FROM_AXES], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'SP-FROM-AXES-OK' in out.stdout


MESH_LATCH = r"""
import os, sys, warnings
sys.path.insert(0, REPO_PATH)
os.environ['JAX_PLATFORMS'] = 'cpu'
from automerge_tpu import telemetry
from automerge_tpu.native import NativeDocPool
ROOT = '00000000-0000-0000-0000-000000000000'
pool = NativeDocPool()
pool.apply_changes('d', [{'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
    {'action': 'set', 'obj': ROOT, 'key': 'k', 'value': 'v'}]}])
os.environ['AMTPU_MESH'] = '4'            # after the first batch: latched
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter('always')
    pool.apply_changes('d', [{'actor': 'a', 'seq': 2, 'deps': {}, 'ops': [
        {'action': 'set', 'obj': ROOT, 'key': 'k', 'value': 'w'}]}])
msgs = [str(x.message) for x in w if issubclass(x.category, RuntimeWarning)]
assert any('AMTPU_MESH' in m for m in msgs), msgs
snap = telemetry.metrics_snapshot()
assert snap.get('mesh.latch_flip_ignored', 0) >= 1, snap
# warned once per (key, value): a repeat flip stays counted, not re-warned
with warnings.catch_warnings(record=True) as w2:
    warnings.simplefilter('always')
    pool.apply_changes('d', [{'actor': 'a', 'seq': 3, 'deps': {}, 'ops': [
        {'action': 'set', 'obj': ROOT, 'key': 'k', 'value': 'x'}]}])
assert not [x for x in w2 if 'AMTPU_MESH' in str(x.message)]
print('MESH-LATCH-OK')
""".replace('REPO_PATH', repr(REPO))


def test_amtpu_mesh_latch_flip_warns_once():
    """AMTPU_MESH flips after the first batch warn + count
    mesh.latch_flip_ignored (the PR-6 latch-guard machinery, extended
    to the mesh topology knobs)."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('AMTPU_MESH', None)
    out = subprocess.run([sys.executable, '-c', MESH_LATCH], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'MESH-LATCH-OK' in out.stdout
