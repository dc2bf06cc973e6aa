"""Pallas TPU kernel for per-object dominance indexes.

Same contract as `list_rank.dominance_grouped` (reference semantics:
SkipList index queries, /root/reference/backend/skip_list.js:261-279,
batched as time-windowed dominance counts): for each list object, walk its
op timeline in chunks of K, counting visible lower-ranked elements per op
against a running visibility vector.

The Pallas formulation keeps the per-object visibility vector resident in
VMEM scratch across the whole timeline (the XLA version re-materializes it
through the scan carry), and drives the three inner products per chunk --
base counts, within-chunk corrections, visibility update -- as explicit
VMEM-blocked compute:

  grid = (W,)   one program per list object; per program:
    vis   [1, L]  f32  scratch, initialized from v0
    per chunk c:
      maskT [K, L] = (rank_chunk[:, None] > elem_rank[None, :])
      base  = maskT @ vis^T                      (MXU, [K, 1])
      corr  = lower-tri within-chunk correction  (VPU, [K, K])
      vis  += sum_k delta_k * onehot(elem_k)     (VPU, [K, L])

Eligibility: L and K multiples of 128/lane tiling are padded by the
caller's shape buckets; the dispatcher `dominance_grouped_auto` routes
to the XLA kernel off-TPU or for shapes outside the tiling.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace
from . import list_rank
from .pallas_common import pallas_enabled


# objects processed per grid program (the sublane tiling minimum)
_ROWS = 8


def _kernel(v0_ref, er_ref, oe_ref, orank_ref, od_ref, ov_ref, idx_ref,
            vis_ref, *, n_chunks, K, L):
    R = _ROWS
    vis_ref[:] = v0_ref[:]
    er = er_ref[:]                      # [R, L] int32
    tri = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) <
           jax.lax.broadcasted_iota(jnp.int32, (K, K), 1))

    def chunk(c, _):
        sl = c * K
        e = oe_ref[:, pl.ds(sl, K)]                    # [R, K]
        r = orank_ref[:, pl.ds(sl, K)]
        v = ov_ref[:, pl.ds(sl, K)]
        # padding rows carry d=0 into corr regardless of caller zero-fill
        d = od_ref[:, pl.ds(sl, K)].astype(jnp.float32) * \
            v.astype(jnp.float32)

        # base: visible elements with rank below, at chunk start
        # (multiply-reduce on the VPU; Mosaic rejects batched dot_general)
        maskT = (r[:, :, None] > er[:, None, :]).astype(jnp.float32)
        base = jnp.sum(maskT * vis_ref[:][:, None, :], axis=2)   # [R, K]

        # within-chunk: earlier op j toggling a lower-ranked element
        # (masks kept f32: Mosaic only broadcasts a new minor dim for
        # 32-bit types, so bool [R, K, None] inserts will not lower)
        cross = (tri[None] & (r[:, :, None] < r[:, None, :])) \
            .astype(jnp.float32)                              # [R, K, K]
        corr = jnp.sum(cross * d[:, :, None], axis=1)         # [R, K]

        idx_ref[:, pl.ds(sl, K)] = (base + corr).astype(jnp.int32)

        # visibility update: one-hot scatter as a masked broadcast-sum
        le = jax.lax.broadcasted_iota(jnp.int32, (R, K, L), 2)
        vmask = (v.astype(jnp.float32) *
                 (e >= 0).astype(jnp.float32) * d)            # [R, K]
        hot = (le == e[:, :, None]).astype(jnp.float32)
        vis_ref[:] = vis_ref[:] + jnp.sum(hot * vmask[:, :, None], axis=1)
        return 0

    jax.lax.fori_loop(0, n_chunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=('chunk', 'interpret'))
def dominance_grouped_pallas(vis0, elem_rank, op_elem, op_rank, op_delta,
                             op_valid, chunk=64, interpret=False):
    """Drop-in for `list_rank.dominance_grouped` on TPU.  `interpret=True`
    runs the kernel in the Pallas interpreter (CPU-testable)."""
    W, L = vis0.shape
    T = op_elem.shape[1]
    K = chunk
    if T % K != 0:
        raise ValueError('T=%d must be a multiple of chunk=%d' % (T, K))
    if W % _ROWS != 0:
        raise ValueError('W=%d must be a multiple of %d' % (W, _ROWS))
    n_chunks = T // K

    spec_l = pl.BlockSpec((_ROWS, L), lambda o: (o, 0))
    spec_t = pl.BlockSpec((_ROWS, T), lambda o: (o, 0))
    return pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_chunks, K=K, L=L),
        grid=(W // _ROWS,),
        out_shape=jax.ShapeDtypeStruct((W, T), jnp.int32),
        in_specs=[spec_l, spec_l, spec_t, spec_t, spec_t, spec_t],
        out_specs=spec_t,
        scratch_shapes=[pltpu.VMEM((_ROWS, L), jnp.float32)],
        interpret=interpret,
    )(vis0.astype(jnp.float32), elem_rank, op_elem, op_rank,
      op_delta.astype(jnp.int32), op_valid.astype(jnp.int32))


def dominance_grouped_auto(vis0, elem_rank, op_elem, op_rank, op_delta,
                           op_valid, chunk=64):
    """Pallas on TPU when the lane tiling fits; XLA kernel otherwise.
    Both paths compute identical outputs (pinned by unit test)."""
    W, L = vis0.shape
    T = op_elem.shape[1]
    # The pallas path always chunks by 128: Mosaic requires lane-dimension
    # slice offsets provably 128-aligned, and chunk width changes only the
    # work grouping, never the result.  VMEM budget (~16 MiB/core): two
    # live [ROWS, 128, L] f32 chunk temporaries plus six [ROWS, T] i32
    # timeline blocks must fit with headroom.
    PK = 128
    vmem_bytes = 2 * _ROWS * PK * L * 4 + 6 * _ROWS * T * 4
    if (pallas_enabled() and L % 128 == 0 and T % PK == 0
            and W % _ROWS == 0 and vmem_bytes <= 10 * 2 ** 20):
        trace.count('ops.dominance.pallas')
        return dominance_grouped_pallas(
            vis0, elem_rank, op_elem, op_rank, op_delta, op_valid,
            chunk=PK)
    trace.count('ops.dominance.xla')
    return list_rank.dominance_grouped(
        vis0, elem_rank, op_elem, op_rank, op_delta, op_valid, chunk=chunk)
