"""Device-resident arena cache -- incremental state across batches.

SURVEY hard part 5: the reference keeps its opSet state incrementally
between calls (`/root/reference/backend/op_set.js:310-322`); the TPU
analogue is arena columns that LIVE ON DEVICE between `apply_batch`
calls, with the host uploading only per-batch deltas (appended elements,
per-op arrays, register rows) instead of re-encoding and re-uploading
O(arena) bytes every batch.

The cache keys on (doc id, object sid).  Entries hold four long-lived
device arrays -- parent/ctr/actor-rank (i32) and visibility (f32) -- at
the dom block's padded capacity.  Consistency contract:

* Appends are detected by length: rows [cached_n, current_n) upload as
  one scatter; a shrink (batch rollback) or capacity change (pow2 bucket
  growth) triggers a full re-upload.
* Visibility is synced AFTER emit from the C++ arena's own `visible`
  column (only the batch's touched elements -- O(batch)); the C++ state
  is ground truth, so overflow fallbacks and undo flows stay exact.
* Element actor ranks must preserve actor-STRING order across batches
  (linearize tie-breaks siblings by actor descending), so ranks come
  from a pool-lifetime sorted registry; an actor whose name sorts into
  the middle of the known set shifts existing ranks and drops the cache
  (rare -- one full re-upload).
* An entry whose batch failed between dispatch and sync is `dirty` and
  re-uploads in full on next touch.
"""

import bisect
import ctypes
from functools import lru_cache, partial

import numpy as np

from .. import trace
from ..utils.common import env_int, parse_mesh_env

#: Default long-list crossover for the sp (sequence-parallel) axis, in
#: arena elements (ISSUE 7 satellite: sp-axis triage).  Below this the
#: linearization all-gather + per-device dispatch overhead outweighs
#: the sharded dominance win and sp REGRESSES hard -- measured on the
#: 2-core CI stand-in (steady-state resident edit batches, sp=2,
#: interleaved A/B; bench.py --multichip re-records the probe per
#: host): 3.4x slower at 8k elements, ~2x at 32k, ~1.3x at 64k,
#: break-even (0.85-1.1x, noise-dominated) at 128k+.  The stand-in can
#: never show a WIN -- its virtual devices share the two cores XLA's
#: intra-op parallelism already saturates at sp=1 -- so the default
#: threshold marks where sharding stops HURTING; real multi-chip
#: hardware (where sp buys actual extra silicon and O(L/sp) resident
#: memory) is expected to move it down, and the hardware-day run
#: re-measures it.  AMTPU_MESH_SP_MIN overrides; arenas below the
#: threshold stay on the single-chip resident kernel and count
#: ``mesh.sp_fenced``.
SP_CROSSOVER_ELEMS = 1 << 17


def _sp_min():
    """Element threshold under which sp sharding is fenced off."""
    return env_int('AMTPU_MESH_SP_MIN', SP_CROSSOVER_ELEMS)


#: `_sp_sharding`'s default cap: read ``AMTPU_MESH`` (a pool built
#: from explicit mesh axes passes its own)
FROM_ENV = object()


def _sp_device_cap():
    """How many devices the sp axis may claim: None = every local
    device (legacy auto policy, no AMTPU_MESH set), 0 = fenced off
    entirely, else the explicit sp extent of ``AMTPU_MESH=dp,sp``.

    With dp > 1 every device belongs to a dp chip, and a global sp
    mesh would shard one chip's arena across devices other chips own
    -- so mesh mode enables sp only for the dp=1 topology (the
    single-big-doc showcase the sp axis exists for); composing per-
    chip sp sub-meshes is deferred until the path validates on real
    hardware."""
    try:
        env = parse_mesh_env()
    except ValueError:
        return 0          # malformed AMTPU_MESH: never shard on a typo
    if env is None:
        return None
    dp, sp = env
    if sp <= 1 or dp > 1:
        return 0
    return sp


class ResidentArena:
    __slots__ = ('capacity', 'n', 'par', 'ctr', 'act', 'ev', 'dirty')

    def __init__(self, capacity):
        self.capacity = capacity
        self.n = 0
        self.par = None
        self.ctr = None
        self.act = None
        self.ev = None
        self.dirty = False


def arena_scatter(col, idx, vals):
    """One resident column's delta: pad slots carry idx == capacity (out
    of bounds) and drop.  Jitted, its XLA module is `jit_arena_scatter`."""
    return col.at[idx].set(vals, mode='drop')


@lru_cache(maxsize=None)
def _jit_scatter(sharding=None):
    import jax
    jitted = jax.jit(arena_scatter, out_shardings=sharding)

    def dispatch(col, idx, vals):
        trace.metric('transfer.h2d_bytes', idx.nbytes + vals.nbytes)
        return jitted(col, idx, vals)
    return dispatch


@lru_cache(maxsize=None)
def _jit_kernel(n_iters, window, chunk):
    import jax

    from ..ops import registers as register_ops
    return jax.jit(partial(register_ops.resolve_rank_dominate_resident,
                           n_iters=n_iters, window=window, chunk=chunk))


@lru_cache(maxsize=None)
def _sp_mesh(n_cap=None):
    """A 1-D ('sp',) mesh over the largest power-of-two subset of local
    devices (capped at `n_cap` when the AMTPU_MESH topology reserves
    devices for dp chips), or None single-device.  The pool's resident
    dispatch shards big arenas over it -- the promotion of the
    AMTPU_BENCH_C1_MESH showcase path into the default pool entry point
    (VERDICT r2 #4).  Power-of-two so the pow2-bucketed arena
    capacities divide evenly."""
    import jax
    devices = jax.devices()
    limit = len(devices) if n_cap is None else min(n_cap, len(devices))
    n = 1
    while n * 2 <= limit:
        n *= 2
    if n < 2:
        return None
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:n]), ('sp',))


def _sp_sharding(capacity=None, count_fenced=False, cap=FROM_ENV):
    """Element-axis sharding for a resident column of `capacity` rows,
    or None when sharding is unavailable/indivisible -- or FENCED (the
    caller then keeps the column replicated and uses the unsharded
    kernel).  The fence is the sp-axis triage (ISSUE 7): sp>1 routes
    only past the measured long-list crossover (`_sp_min`), and only
    over devices the AMTPU_MESH topology has not claimed for dp chips
    (`_sp_device_cap`).  `count_fenced` records a fenced would-be
    sharding as ``mesh.sp_fenced`` -- passed ONLY by the dispatch
    decision site, so fenced counts one per dispatch exactly like its
    ``mesh.sp_engaged`` counterpart (placement/sync callers would
    otherwise inflate it 3-4x).  `cap` is `_sp_device_cap`'s value
    for a pool whose mesh axes were given, not read."""
    if cap is FROM_ENV:
        cap = _sp_device_cap()
    if cap == 0:
        return None
    mesh = _sp_mesh(cap)
    if mesh is None:
        return None
    if capacity is not None and capacity % mesh.size != 0:
        return None
    if capacity is not None and capacity < _sp_min():
        if count_fenced:
            trace.metric('mesh.sp_fenced')
        return None
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec('sp'))


@lru_cache(maxsize=None)
def _jit_kernel_sharded(n_iters, window, chunk, n_cap=None):
    """The resident resolver with the arena element axis SHARDED over the
    sp mesh: linearize all-gathers the (tiny) parent/ctr/act columns for
    pointer doubling, while the quadratic dominance stage -- the dominant
    cost for long lists -- computes only each device's local partial
    counts, completed with one psum (`ops/list_rank.dominance_indexes`
    sequence-parallel mode, same formulation as parallel/mesh.py).
    `n_cap` keys the cache on the AMTPU_MESH device cap so the compiled
    mesh always matches the sharding decision that routed here."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops import list_rank
    from ..ops import registers as register_ops
    from ..parallel.mesh import shard_map

    mesh = _sp_mesh(n_cap)
    rep = P()
    shd = P('sp')
    reg_spec = {k: rep for k in ('winner', 'conflicts', 'alive_after',
                                 'visible_before', 'overflow', 'packed')}

    def step(g, t, a, s, ctab, cidx, d, alive, si, par, ctr, act, ev,
             n_elems, oe, dom_src, ov):
        reg = register_ops._resolve(g, t, a, s, ctab, cidx, d, alive,
                                    si, None, window)
        par_f = jax.lax.all_gather(par, 'sp', tiled=True)
        ctr_f = jax.lax.all_gather(ctr, 'sp', tiled=True)
        act_f = jax.lax.all_gather(act, 'sp', tiled=True)
        C = par_f.shape[0]
        valid_f = jnp.arange(C, dtype=jnp.int32) < n_elems
        rank = list_rank.linearize(jnp.zeros((C,), jnp.int32), par_f,
                                   ctr_f, act_f, valid_f, n_iters)
        Ll = par.shape[0]
        off = jax.lax.axis_index('sp') * Ll
        er_local = jax.lax.dynamic_slice_in_dim(rank, off, Ll)
        oe1, ds1, ov1 = oe[0], dom_src[0], ov[0]
        orank, od = register_ops.dominance_op_inputs(reg, rank, oe1,
                                                     ds1, ov1)
        oobj = jnp.where(ov1, 0, -2)
        idx = list_rank.dominance_indexes(
            jnp.zeros((Ll,), jnp.int32), er_local, ev, oe1, oobj, orank,
            od, ov1, chunk=chunk, axis_name='sp', l_offset=off)
        combo = jnp.concatenate([reg['packed'], idx])
        return reg, rank, combo

    stepped = shard_map(
        step, mesh,
        in_specs=(rep,) * 9 + (shd, shd, shd, shd) + (rep,) * 4,
        out_specs=(reg_spec, rep, rep))
    return jax.jit(stepped)


def _bucket_pow2(n, floor=16):
    size = floor
    while size < n:
        size *= 2
    return size


class ResidentCache:
    def __init__(self):
        self.sp_cap = FROM_ENV   # the sp devices its arenas may shard over
        self.entries = {}        # (doc_id bytes, obj_sid) -> ResidentArena
        self.actor_order = []    # sorted actor strings (bytes)
        self.sid_str = {}        # sid -> actor string

    # -- actor ranks ----------------------------------------------------

    def _rank_of_sids(self, L, pool, sids):
        """Vector of string-order ranks for actor sids; registering a
        middle-sorting new actor invalidates every cached eact column.

        Two passes: ALL new sids register first, THEN ranks compute --
        interleaving them would hand out ranks that a later insert in
        the same call shifts (colliding eact values, divergent sibling
        tie-breaks)."""
        for sid in sids:
            if sid in self.sid_str:
                continue
            s = L.amtpu_intern_str(pool, sid)
            self.sid_str[sid] = s
            pos = bisect.bisect_left(self.actor_order, s)
            if pos != len(self.actor_order):
                # ranks of later actors shift: resident eact stale
                self.entries.clear()
                trace.count('resident.actor_invalidation')
            self.actor_order.insert(pos, s)
        out = np.empty(len(sids), np.int32)
        for i, sid in enumerate(sids):
            out[i] = bisect.bisect_left(self.actor_order,
                                        self.sid_str[sid])
        return out

    # -- entry acquisition ---------------------------------------------

    def _read_raw(self, L, pool, doc_id, obj_sid):
        ctr = ctypes.POINTER(ctypes.c_int32)()
        act = ctypes.POINTER(ctypes.c_uint32)()
        par = ctypes.POINTER(ctypes.c_int32)()
        vis = ctypes.POINTER(ctypes.c_uint8)()
        n = L.amtpu_arena_raw(pool, doc_id, obj_sid,
                              ctypes.byref(ctr), ctypes.byref(act),
                              ctypes.byref(par), ctypes.byref(vis))
        if n == 0:
            return 0, None, None, None, None
        shape = (n,)
        return (n,
                np.ctypeslib.as_array(ctr, shape=shape),
                np.ctypeslib.as_array(act, shape=shape),
                np.ctypeslib.as_array(par, shape=shape),
                np.ctypeslib.as_array(vis, shape=shape))

    def get_entry(self, L, pool, doc_id, obj_sid, n_now, capacity):
        """Returns a ResidentArena whose device columns reflect the
        arena's current rows [0, n_now), uploading as little as the
        consistency contract allows; None when the raw arena is
        unavailable."""
        import jax.numpy as jnp

        n_raw, ctr, act, par, vis = self._read_raw(L, pool, doc_id,
                                                   obj_sid)
        if n_raw < n_now:
            return None
        key = (doc_id, obj_sid)
        entry = self.entries.get(key)
        need_full = (entry is None or entry.dirty or
                     entry.capacity != capacity or entry.n > n_now)

        if need_full:
            lo = 0
        else:
            lo = entry.n
        if need_full or n_now > lo:
            # rank mapping may clear self.entries (middle-sorting actor);
            # compute ranks FIRST, then re-check the entry
            ranks = self._rank_of_sids(L, pool,
                                       act[lo:n_now].tolist())
            entry2 = self.entries.get(key)
            if entry2 is not entry or (entry2 is not None and
                                       entry2.dirty):
                need_full = True
                lo = 0
                ranks = self._rank_of_sids(L, pool, act[:n_now].tolist())
            entry = entry2 if not need_full else None

        if need_full:
            import jax
            entry = ResidentArena(capacity)
            pad = capacity - n_now
            sharding = _sp_sharding(capacity, cap=self.sp_cap)

            def up(a, dtype, fill):
                host = np.pad(np.ascontiguousarray(a[:n_now], dtype),
                              (0, pad), constant_values=fill)
                trace.metric('transfer.h2d_bytes', host.nbytes)
                arr = jnp.asarray(host)
                return (jax.device_put(arr, sharding)
                        if sharding is not None else arr)
            with trace.span('device.upload'):
                entry.par = up(par, np.int32, -1)
                entry.ctr = up(ctr, np.int32, 0)
                entry.act = up(ranks, np.int32, 0)
                entry.ev = up(vis, np.float32, 0.0)
            entry.n = n_now
            self.entries[key] = entry
            trace.count('resident.full_upload_rows', n_now)
        elif n_now > lo:
            k = n_now - lo
            kp = _bucket_pow2(k)
            idx = np.full(kp, capacity, np.int32)   # capacity = dropped
            idx[:k] = np.arange(lo, n_now, dtype=np.int32)
            scatter = _jit_scatter(_sp_sharding(capacity, cap=self.sp_cap))

            def pad(a, dtype):
                out = np.zeros(kp, dtype)
                out[:k] = a
                return out
            with trace.span('device.upload'):
                entry.par = scatter(entry.par, idx,
                                    pad(par[lo:n_now], np.int32))
                entry.ctr = scatter(entry.ctr, idx,
                                    pad(ctr[lo:n_now], np.int32))
                entry.act = scatter(entry.act, idx, pad(ranks, np.int32))
                entry.ev = scatter(entry.ev, idx,
                                   pad(vis[lo:n_now], np.float32))
            entry.n = n_now
            trace.count('resident.delta_upload_rows', k)
        else:
            trace.count('resident.no_upload')
        return entry

    def sync_after_emit(self, L, pool, entry, doc_id, obj_sid, n_now,
                        touched_eidx):
        """Post-emit visibility refresh from the C++ ground truth: only
        the batch's touched elements re-upload (O(batch))."""
        n_raw, _ctr, _act, _par, vis = self._read_raw(L, pool, doc_id,
                                                      obj_sid)
        if n_raw < n_now:          # rollback after dispatch: drop
            entry.dirty = True
            return
        if touched_eidx.size:
            kp = _bucket_pow2(touched_eidx.size)
            idx = np.full(kp, entry.capacity, np.int32)
            idx[:touched_eidx.size] = touched_eidx
            vals = np.zeros(kp, np.float32)
            vals[:touched_eidx.size] = vis[touched_eidx]
            with trace.span('device.upload'):
                entry.ev = _jit_scatter(
                    _sp_sharding(entry.capacity, cap=self.sp_cap))(
                        entry.ev, idx, vals)
        entry.n = n_now
        entry.dirty = False
