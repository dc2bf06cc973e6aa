"""First-class mesh execution: the doc-partitioned device-mesh pool
(ISSUE 7 tentpole; docs/ARCHITECTURE.md mesh section).

`MeshDocPool` promotes the multichip dryrun into a production execution
mode: document batches partition across a real device mesh's **dp**
axis (the FNV doc hash the payload splitter already uses), each dp
shard served by a `MeshChipPool` -- a `NativeDocPool` whose every
kernel dispatch, pool-resident clock table, and escalation tier is
pinned to ONE mesh device via `jax.default_device` (thread-local, so
concurrent chips never fight over placement).  The pool speaks the
same `apply_batch`/`apply_batch_bytes` + patches contract as
`NativeDocPool`, so the scheduler gateway, the resilience
retry/bisect/quarantine path, and the sidecar serve it unchanged.
`native.make_pool` builds it with dp = every chip of a multi-chip TPU
process (``AMTPU_MESH=dp[,sp]`` overrides, as a test seam).

The dryrun's scaling losses are attacked structurally:

* host-side decode/begin runs in one thread PER CHIP (ctypes releases
  the GIL around the C++ runtime), so per-step host work -- the
  dominant cost on the CPU stand-in -- stops serializing
  (``mesh.encode_shard_skew_s`` records the per-chip begin imbalance);
* PR 6's device-resident pool state is per chip by construction: each
  chip pool owns its own `PoolClockCache`/`ResidentCache`, created
  under the chip's device context, with per-chip generation tracking
  and delta scatters (donated off-CPU, exactly the single-device
  rules); escalation tiers dispatch on the chip that owns the
  overflowing docs instead of re-gathering to device 0;
* there is NO barrier between the phases: every chip thread, after
  publishing its own phase-a context, joins a shared ready-first
  collector (`_collect_one_ready_first`) that claims chips whose
  device outputs already resolved (jax.Array.is_ready) -- one slow
  chip neither serializes nor barriers the others
  (``mesh.collective_wait_s`` records time blocked with nothing
  ready);
* the sp (sequence-parallel) axis is FENCED: `resident._sp_sharding`
  routes element-axis sharding only past a measured long-list
  crossover (AMTPU_MESH_SP_MIN) and only for the dp=1, sp>1 topology
  (the pool's own axes, given or from ``AMTPU_MESH=1,sp``) -- the
  dryrun's 2.2x sp=2 regression can no longer ship silently (ISSUE 7
  satellite; the crossover probe is recorded in the MULTICHIP bench
  line).

Error semantics are the sharded pool's: chips commit independently; a
failed chip's sub-payload re-applies through the resilience layer on
that chip alone (retry -> bisect -> per-doc quarantine), healthy
chips' results stand.
"""

import ctypes
import threading
import time

from .. import trace
from ..utils.common import env_raw, parse_mesh_env
from ..utils.jaxenv import ensure_cpu_devices
from . import (NativeDocPool, ShardedNativePool, _ctx_pending_arrays,
               _ctx_ready, _run_phase_b_entry, _read_map_header,
               _state_layout, lib)


class MeshChipPool(NativeDocPool):
    """One dp shard of the mesh: a `NativeDocPool` pinned to a device.

    Placement rides `jax.default_device` (thread-local config) around
    both phases, so everything the batch path stages -- register
    columns, the resident clock table, escalation tier chunks, the
    resident arena -- lands on this chip, and donation/delta rules
    apply per chip exactly as on a single device.

    The chip forces the KERNEL path: the mesh exists to use the
    devices, so the CPU backend's full-host default would reduce
    ``AMTPU_MESH`` to plain host sharding with idle chips.  An
    explicit ``AMTPU_HOST_FULL=1`` still wins (parity A/B arms).
    `sp_cap` is the number of devices its long arenas may shard over
    (0: none)."""

    def __init__(self, device, sp_cap=0):
        super().__init__()
        self.device = device
        self._resident.sp_cap = sp_cap

    def _device_ctx(self):
        import jax
        return jax.default_device(self.device)

    def _ensure_mode_flags(self):
        if not self._mode_set:
            env = env_raw('AMTPU_HOST_FULL')
            host_full = env is not None and env not in ('', '0')
            lib().amtpu_pool_set_hostfull(self._pool,
                                          1 if host_full else 0)
            self._mode_set = True

    def _phase_a(self, payload, overlapped=False):
        with self._device_ctx():
            return super()._phase_a(payload, overlapped=overlapped)

    def _phase_b(self, ctx):
        with self._device_ctx():
            return super()._phase_b(ctx)

    def apply_local_change(self, doc_id, request):
        with self._device_ctx():
            return super().apply_local_change(doc_id, request)


def _collect_one_ready_first(produced, state, cv, on_result, on_error):
    """One claim+collect round of the shared mesh collector: under the
    condition variable, wait for a produced entry (or for production to
    end), claim the first READY one (jax.Array.is_ready; oldest when
    nothing resolved yet), then -- outside the lock -- wait out its
    device outputs if needed and run phase b through the SAME
    `_run_phase_b_entry` protocol as the serial collector.  Returns
    False when there is nothing left to collect."""
    with cv:
        while not produced and state['outstanding'] > 0:
            cv.wait()
        if not produced:
            return False
        pick = None
        for i, (_k, _p, ctx) in enumerate(produced):
            if _ctx_ready(ctx):
                pick = i
                break
        if pick is None:
            pick = 0
            trace.metric('collect.wait_in_order')
        elif pick > 0:
            trace.metric('collect.ready_reorder')
        key, pool, ctx = produced.pop(pick)
    if not _ctx_ready(ctx):
        # device still computing: block OUTSIDE the lock so other chip
        # threads keep draining ready entries, and account the block as
        # collective/device wait
        t0 = time.perf_counter()
        for arr in _ctx_pending_arrays(ctx):
            try:
                arr.block_until_ready()
            except Exception:
                pass    # phase b will surface the real error
        trace.metric('mesh.collective_wait_s', time.perf_counter() - t0)
    _run_phase_b_entry(key, pool, ctx, on_result, on_error)
    return True


class MeshDocPool(ShardedNativePool):
    """Doc-partitioned pool over a device mesh: dp chips, each a
    device-pinned `MeshChipPool`; drop-in for `NativeDocPool` on the
    batch/query surface (see module docstring for the drive)."""

    _batch_label = 'mesh'

    def __init__(self, dp=None, sp=None):
        env = parse_mesh_env()
        if dp is None:
            if env is None:
                raise ValueError(
                    'MeshDocPool needs dp (constructor arg or '
                    'AMTPU_MESH=dp[,sp])')
            dp, sp = env
        if sp is None:
            sp = 1
        if dp < 1 or sp < 1:
            raise ValueError('mesh axes must be >= 1, got dp=%r sp=%r'
                             % (dp, sp))
        # a CPU backend without a chip gets dp*sp virtual devices, if
        # nothing initialized it yet; device enumeration stays lazy
        ensure_cpu_devices(dp * sp)
        super().__init__(n_shards=dp)
        self.dp = dp
        self.sp = sp
        self._devices = None

    def _resolve_devices(self):
        """One device per dp chip, resolved at first use.  A mesh that
        needs more devices than exist raises: several chips' work on
        one device would pass for a mesh it is not."""
        if self._devices is None:
            import jax
            devs = jax.devices()
            want = self.dp * self.sp
            if len(devs) < want:
                trace.metric('mesh.device_shortfall')
                raise RuntimeError(
                    'AMTPU_MESH wants %d devices (dp=%d x sp=%d) but '
                    'only %d exist' % (want, self.dp, self.sp, len(devs)))
            # chip s owns devices [s*sp, (s+1)*sp); its primary device
            # (kernel placement) is the first -- the rest belong to the
            # chip's sp sub-mesh when the sp fence routes a long list
            self._devices = [devs[s * self.sp] for s in range(self.dp)]
            _state_layout('MeshDocPool', self.dp, (
                devs[0].platform, devs[0].device_kind, len(devs)))
        return self._devices

    @property
    def pools(self):
        if self._pools is None:
            n = self.n_shards
            devices = self._resolve_devices()
            with self._pools_lock:
                if self._pools is None:
                    # sp only for the dp=1 topology, as
                    # resident._sp_device_cap rules for AMTPU_MESH
                    sp_cap = self.sp if self.dp == 1 and self.sp > 1 else 0
                    self._pools = [MeshChipPool(devices[s], sp_cap)
                                   for s in range(n)]
        return self._pools

    def _run(self, subs):
        """The mesh drive: one thread per chip runs that chip's phase a
        (parallel C++ decode/begin + the chip's async kernel dispatch),
        publishes the context, and immediately joins a SHARED ready-
        first collector -- no barrier between the phases, so an early
        chip's host mid/emit overlaps a late chip's begin and a slow
        chip's device wait (ISSUE 7 tentpole a+c).  Ready-order claims
        use the same jax.Array.is_ready predicate and phase-b failure
        protocol as the single-device pipelined collector."""
        pools = self.pools
        results = [None] * self.n_shards
        errors = []
        live = [s for s in range(self.n_shards) if subs[s] is not None]
        trace.metric('mesh.batches')
        trace.metric('mesh.shards', len(live))
        chip_docs = []
        for s in live:
            try:
                head = ctypes.string_at(subs[s][0], min(subs[s][1], 16))
                chip_docs.append(_read_map_header(head)[0])
            except (ValueError, IndexError):
                chip_docs.append(0)
        if chip_docs:
            trace.metric('mesh.chip_docs', sum(chip_docs))
            trace.metric('mesh.occupancy_skew',
                         max(chip_docs) - min(chip_docs))

        produced = []                    # phase-a outputs awaiting collect
        state = {'outstanding': len(live)}
        cv = threading.Condition()
        t_a = {}

        def keep(s, result):
            results[s] = result          # per-slot writes: no lock

        def err(s, e):
            with cv:
                errors.append((s, e))

        def chip(s):
            # one span per chip thread: its phase a and the collects it
            # takes from the shared collector
            with trace.span('mesh.chip'):
                try:
                    t0 = time.perf_counter()
                    ctx = pools[s]._phase_a(subs[s])
                    t_a[s] = time.perf_counter() - t0
                except Exception as e:
                    with cv:
                        errors.append((s, e))
                        state['outstanding'] -= 1
                        cv.notify_all()
                else:
                    with cv:
                        produced.append((s, pools[s], ctx))
                        state['outstanding'] -= 1
                        cv.notify_all()
                while _collect_one_ready_first(produced, state, cv, keep,
                                               err):
                    pass

        if len(live) <= 1:
            for s in live:
                chip(s)
        else:
            threads = [threading.Thread(target=chip, args=(s,))
                       for s in live]
            with trace.span('mesh.drive'):
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
        if len(t_a) > 1:
            trace.metric('mesh.encode_shard_skew_s',
                         max(t_a.values()) - min(t_a.values()))
        return results, errors
