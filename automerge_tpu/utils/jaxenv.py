"""JAX process setup shared by the entry points.

`JAX_PLATFORMS=cpu` in the environment is all a CPU run needs; these
helpers cover the rest: virtual CPU devices for mesh runs without a
chip, the Gloo collectives of multi-process CPU runs, and the
persistent compile cache of the chip entry points.
"""

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_cpu_devices(n_devices):
    """Asks for at least ``n_devices`` virtual CPU devices.  The count
    binds only before the CPU backend initializes; after that it is
    frozen, and a caller that needs more devices than exist raises
    (`MeshDocPool`) or tears the backend down (the dryrun)."""
    import jax
    if jax.config.jax_num_cpu_devices < n_devices:
        try:
            jax.config.update('jax_num_cpu_devices', n_devices)
        except RuntimeError:
            pass


def enable_cpu_collectives():
    """Gloo collectives, so `multihost_utils.process_allgather` works
    across CPU processes.  Must run before `jax.distributed.initialize`."""
    import jax
    jax.config.update('jax_cpu_collectives_implementation', 'gloo')


def compile_cache_dir():
    """Where the persistent compile cache lives: JAX's own
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache`` --
    a fixed path, because the path is part of what a later run must
    find again."""
    return (os.environ.get('JAX_COMPILATION_CACHE_DIR')
            or os.path.join(REPO_ROOT, '.jax_cache'))


def enable_compile_cache():
    """Turns the persistent compile cache on for a chip entry point
    (`chip_smoke.py`, `bench.py`, the sidecar server's `main`); call it
    before the first compile.  With ``JAX_COMPILATION_CACHE_DIR`` set,
    JAX has already read it and no directory is set here.  The
    thresholds are zero so the many small kernel programs are cached
    too.  A ``JAX_PLATFORMS=cpu`` run -- the tests and the CPU gates --
    keeps no cache.  Returns the directory in use, or None."""
    if os.environ.get('JAX_PLATFORMS') == 'cpu':
        return None
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', compile_cache_dir())
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return compile_cache_dir()
