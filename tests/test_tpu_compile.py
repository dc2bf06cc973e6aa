"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e (no chip attached): what Mosaic or XLA:TPU would refuse fails here,
at no chip time.  Nothing runs, so a pass says nothing about results or
times.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.

The chip entry points' own tests sit here too (the compile-cache helper,
a parent that stays off JAX); they run on the CPU.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from automerge_tpu.utils import jaxenv


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip):
    """Shape factory on the described chip, with the persistent cache
    off: a chip compile written there could not be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()

    def shape(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    yield shape
    jax.config.update('jax_enable_compilation_cache', was)


@pytest.fixture
def tpu_branches(monkeypatch):
    """The accelerator branches of ops/registers.py are chosen from
    jax.default_backend() at trace time; here they see a TPU."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')


@pytest.mark.parametrize('T', [4096, 65536])
def test_pallas_registers_compiles(compile_for_chip, T):
    from automerge_tpu.ops.pallas_registers import resolve_registers_pallas
    S, A, W = compile_for_chip, 16, 8
    compiled = resolve_registers_pallas.lower(
        S((T,)), S((T,)), S((T,)), S((T,)), S((T,), jnp.bool_), S((T,)),
        S((1024, A)), S((T,)), window=W).compile()
    assert 'tpu_custom_call' in compiled.as_text()


@pytest.mark.parametrize('W', [2, 4, 8])
def test_pallas_registers_compiles_at_gate_edge(compile_for_chip, W):
    """The largest actor count `resolve_registers_auto` routes to the
    kernel at each window Mosaic still fits in VMEM: the routing rule
    and the compile are pinned together."""
    from automerge_tpu.ops import pallas_registers as pr
    A = pr.widest_actors(W)
    assert A >= 144
    S, T = compile_for_chip, 4096
    compiled = pr.resolve_registers_pallas.lower(
        S((T,)), S((T,)), S((T,)), S((T,)), S((T,), jnp.bool_), S((T,)),
        S((1024, A)), S((T,)), window=W).compile()
    assert 'tpu_custom_call' in compiled.as_text()


def test_pallas_dominance_compiles(compile_for_chip):
    from automerge_tpu.ops.pallas_dominance import dominance_grouped_pallas
    S = compile_for_chip
    W, L, T = 64, 4096, 2048
    compiled = dominance_grouped_pallas.lower(
        S((W, L), jnp.float32), S((W, L)), S((W, T)), S((W, T)),
        S((W, T)), S((W, T), jnp.bool_), chunk=128).compile()
    assert 'tpu_custom_call' in compiled.as_text()


def test_resolve_rank_dominate_compiles(compile_for_chip, tpu_branches):
    """The fused resolver at the shapes of a config-3 wave (10,000 Text
    docs x 16 actors, as chip_smoke.py sends it)."""
    from automerge_tpu.ops import registers
    S = compile_for_chip
    T, L, W, K = 262144, 262144, 6144, 64
    i32 = [S((T,)) for _ in range(4)]
    compiled = registers.resolve_rank_dominate.lower(
        *i32, S((131072, 16)), S((T,)), S((T,), jnp.bool_),
        S((T,), jnp.bool_), S((T,)),
        S((L,)), S((L,)), S((L,)), S((L,)), S((L,), jnp.bool_), S((L,)), 7,
        S((W, K), jnp.float32), S((W, K)), S((W, K)), S((W, K)),
        S((W, K)), S((W, K), jnp.bool_), window=2).compile()
    assert compiled.memory_analysis() is not None


def test_donated_tier_kernels_compile(compile_for_chip, tpu_branches):
    """The escalation tier's donated member kernel and the donated
    packed-word merge, as the accelerator branch builds them."""
    from automerge_tpu.ops import registers
    S = compile_for_chip
    Tn, W, A = 4096, 16, 16
    tier = registers.members_tier_jit()
    assert tier is not registers.resolve_registers_members
    tier.lower(S((Tn,)), S((Tn,)), S((Tn,)), S((Tn, W)), S((Tn,), jnp.bool_),
               S((4096, A)), S((Tn,)), window=W,
               want_visible_before=False).compile()
    merge = registers.merge_packed_rows_jit()
    merge.lower(S((262144,)), S((Tn,)), S((Tn,)), S((Tn,))).compile()


# ---------------------------------------------------------------------------
# chip entry points (CPU)
# ---------------------------------------------------------------------------

def test_entry_point_imports_take_no_backend():
    """`bench.py --all` runs each config in a child that holds the chip,
    so importing the harness (and the smoke) must not start a backend."""
    out = subprocess.run(
        [sys.executable, '-c',
         'import bench, chip_smoke\n'
         'from jax._src import xla_bridge\n'
         'print(xla_bridge.backends_are_initialized())'],
        cwd=jaxenv.REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == 'False', out.stderr[-2000:]


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    assert jaxenv.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
    assert jaxenv.compile_cache_dir() == os.path.join(jaxenv.REPO_ROOT,
                                                      '.jax_cache')


@pytest.mark.parametrize('env_dir', [None, '/elsewhere/cache'])
def test_enable_compile_cache_sets_no_dir_of_its_own(monkeypatch, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set no directory is configured;
    unset, the fixed <repo>/.jax_cache is.  Config updates are recorded,
    not applied, so the test process keeps no cache."""
    updates = {}
    monkeypatch.setattr(jax.config, 'update',
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.delenv('JAX_PLATFORMS')
    if env_dir is None:
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        want = os.path.join(jaxenv.REPO_ROOT, '.jax_cache')
        assert updates == {} and jaxenv.enable_compile_cache() == want
        assert updates['jax_compilation_cache_dir'] == want
    else:
        monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', env_dir)
        assert jaxenv.enable_compile_cache() == env_dir
        assert 'jax_compilation_cache_dir' not in updates
    assert updates['jax_persistent_cache_min_compile_time_secs'] == 0


def test_cpu_run_keeps_no_compile_cache(monkeypatch):
    updates = {}
    monkeypatch.setattr(jax.config, 'update',
                        lambda k, v: updates.__setitem__(k, v))
    assert os.environ['JAX_PLATFORMS'] == 'cpu'
    assert jaxenv.enable_compile_cache() is None and updates == {}
