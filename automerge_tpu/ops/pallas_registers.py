"""Pallas TPU kernel for sliding-window register resolution.

Same contract as `registers.resolve_registers` (reference semantics:
partition register ops into overwritten vs concurrent, winner = max
actor, `/root/reference/backend/op_set.js:188-231`), restricted to the
sorted sliding-window form: after the host's (group, time) sort, the
candidate predecessors of sorted row i are exactly rows i-W..i-1, so
member formation is a stencil -- no gathers anywhere.

What Pallas buys over the XLA twin: the member one-hots and the
pairwise concurrency/supersession intermediates live and die in VMEM
per 128-row block instead of materializing [T, W+1, A] / [T, W+1, W+1]
through HBM -- the XLA formulation's HBM traffic is ~(W+1)x the input
volume, which is the whole cost of this bandwidth-bound kernel.

Ordering without argsort (Mosaic has no stable sort): survivor output
order is (actor desc, time desc) and times are unique, so each alive
member's output position is a PAIRWISE COUNT --
  pos(u) = #{v alive : actor_v > actor_u
                       or (actor_v == actor_u and time_v > time_u)}
-- and winner/conflicts scatter through a position one-hot.  Bit-equal
to the XLA twin's two stable argsorts (pinned by
tests/test_ops_kernels.py::TestPallasRegisters).

Auto-dispatch: `resolve_registers_auto` uses the Pallas kernel on TPU
when the input fits it (T % 128 == 0, VMEM budget, W <= 8) and the XLA
kernel otherwise (`vmem_bytes` admits A <= 144 at W=8).  A compile or
runtime failure of the kernel raises.
tests/test_tpu_compile.py compiles it for a described v5e.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace
from . import registers as xla_registers
from .pallas_common import pallas_enabled

_B = 128      # sorted rows per grid program (one lane tile)
_PADW = 128   # front pad so halo loads stay 128-aligned
_NCOL = 8     # stacked input rows: group, time, actor, seq, del, src, 2 pad
#: the gate's VMEM budget: 4 MiB under Mosaic's 16 MiB scoped limit on v5e
_VMEM_BUDGET = 12 * 2 ** 20


def vmem_bytes(n_actors, window):
    """Model of the kernel's VMEM for `n_actors` clock columns: the two
    DMA scratch slabs plus the pairwise clock products, which Mosaic
    keeps live all at once.  2 (W+1)^2 [Ap, 128] int32 arrays bound what
    it allocates on a v5e (20.18 MiB at W=8, A=256; 16.21 MiB at W=4,
    A=1024; both refused).  tests/test_tpu_compile.py compiles the
    largest A the gate admits at each window."""
    Ap = -(-n_actors // 8) * 8
    V = window + 1
    return ((_PADW + _B) * (_NCOL + Ap) + 2 * V * V * Ap * _B) * 4


def widest_actors(window):
    """The most actors `resolve_registers_auto` sends to the kernel."""
    return max(a for a in range(8, 8192, 8)
               if vmem_bytes(a, window) <= _VMEM_BUDGET)


def _rows(rows):
    """[len(rows), B] from a list of [1, B] rows (sublane select: Mosaic
    lowers selects where a sublane concatenate may not)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (len(rows), _B), 0)
    out = jnp.zeros((len(rows), _B), rows[0].dtype)
    for k, r in enumerate(rows):
        out = jnp.where(sub == k, r, out)
    return out


def _kernel(cols_ref, clk_ref, summary_ref, conflicts_ref,
            cols_s, clk_s, sems, *, W, A):
    """Lane-major: the block's 128 sorted rows lie along lanes, member
    slots (0 = self, w = w-th predecessor) along sublanes.  Every array
    is 2D and every mask is built from int32 compares, so Mosaic needs
    no reshape (it refuses both 1D -> 2D and boolean expands)."""
    b = pl.program_id(0)
    start = b * _B
    dmas = [pltpu.make_async_copy(cols_ref.at[:, pl.ds(start, _PADW + _B)],
                                  cols_s, sems.at[0]),
            pltpu.make_async_copy(clk_ref.at[:, pl.ds(start, _PADW + _B)],
                                  clk_s, sems.at[1])]
    for d in dmas:
        d.start()
    for d in dmas:
        d.wait()

    V = W + 1
    halo = cols_s[:]                                      # [8, PADW + B]

    def members(k):
        """[V, B]: row w = column k of the w-th predecessor."""
        col = halo[k:k + 1, :]
        return _rows([jax.lax.slice_in_dim(col, _PADW - w, _PADW - w + _B,
                                           axis=1) for w in range(V)])

    m_g, m_t, m_a, m_q, m_d, m_src = [members(k) for k in range(6)]
    sub = jax.lax.broadcasted_iota(jnp.int32, (V, _B), 0)
    g_cur = m_g[0:1, :]
    valid = (m_g == g_cur) & (g_cur >= 0)                 # [V, B]

    # P[u][v] = clock of member u at member v's actor: one-hot
    # multiply-reduce over the actor sublanes (int32 throughout: float32
    # would round seqs past 2^24 -- the XLA twin compares in int32)
    clk = clk_s[:]                                        # [A, PADW + B]
    asub = jax.lax.broadcasted_iota(jnp.int32, (A, _B), 0)
    onehot = [(asub == m_a[v:v + 1, :]).astype(jnp.int32) for v in range(V)]
    P = []
    for u in range(V):
        c_u = jax.lax.slice_in_dim(clk, _PADW - u, _PADW - u + _B, axis=1)
        P.append([jnp.sum(c_u * onehot[v], axis=0, keepdims=True)
                  for v in range(V)])

    # u supersedes v: u is later (u < v), not concurrent, both valid
    superseded = jnp.zeros((V, _B), jnp.int32)
    superseded_wo_self = jnp.zeros((V, _B), jnp.int32)
    for u in range(V):
        p_uv = _rows(P[u])                                # P[u][v]
        p_vu = _rows([P[v][u] for v in range(V)])         # P[v][u]
        concurrent = (p_uv < m_q) & (p_vu < m_q[u:u + 1, :])
        sup = ((sub > u) & ~concurrent & valid
               & valid[u:u + 1, :]).astype(jnp.int32)
        superseded = superseded + sup
        if u > 0:
            superseded_wo_self = superseded_wo_self + sup
    live = valid & (m_d == 0)
    alive = live & (superseded == 0)
    alive_i = alive.astype(jnp.int32)
    before = (live & (superseded_wo_self == 0) & (sub > 0)).astype(jnp.int32)

    # output position by pairwise count: (actor desc, time desc)
    pos = _rows([jnp.sum(
        (alive & ((m_a > m_a[u:u + 1, :])
                  | ((m_a == m_a[u:u + 1, :]) & (m_t > m_t[u:u + 1, :])))
         ).astype(jnp.int32), axis=0, keepdims=True) for u in range(V)])

    def pick(k):
        """src of the alive member at output position k (-1: none)."""
        return jnp.sum(jnp.where((pos == k) & alive, m_src + 1, 0),
                       axis=0, keepdims=True) - 1

    window_full = jnp.sum((valid & (sub > 0)).astype(jnp.int32), axis=0,
                          keepdims=True) == W
    summary_ref[:] = _rows([
        pick(0),
        jnp.sum(alive_i, axis=0, keepdims=True),
        (jnp.sum(before, axis=0, keepdims=True) > 0).astype(jnp.int32),
        (window_full & (g_cur >= 0)).astype(jnp.int32)])
    conflicts_ref[:] = _rows([pick(k + 1) for k in range(W)])


@functools.partial(jax.jit, static_argnames=('window', 'interpret'))
def resolve_registers_pallas(group, time, actor, seq, is_del, sort_idx,
                             clock_table, clock_idx, window=8,
                             interpret=False):
    """Drop-in for `registers.resolve_registers` (sliding-window mode).

    Same arguments as the XLA twin's (clock_table, clock_idx) form;
    `interpret=True` runs in the Pallas interpreter (CPU-testable).
    """
    T = group.shape[0]
    W = window
    A = clock_table.shape[1]
    if T % _B != 0:
        raise ValueError('T=%d must be a multiple of %d' % (T, _B))
    Ap = -(-A // 8) * 8          # actor sublanes, padded to the tiling

    src = jnp.asarray(sort_idx, jnp.int32)
    cols = jnp.stack(
        [jnp.asarray(group)[src], jnp.asarray(time)[src],
         jnp.asarray(actor)[src], jnp.asarray(seq)[src],
         jnp.asarray(is_del).astype(jnp.int32)[src], src]
        + [jnp.zeros((T,), jnp.int32)] * (_NCOL - 6)).astype(jnp.int32)
    pad_fill = jnp.array([-2, 0, 0, 0, 0, -1, 0, 0], jnp.int32)[:, None]
    cols = jnp.concatenate(
        [jnp.broadcast_to(pad_fill, (_NCOL, _PADW)), cols], axis=1)
    clk = clock_table[jnp.asarray(clock_idx)[src]].T       # [A, T]
    clk = jnp.pad(clk.astype(jnp.int32), ((0, Ap - A), (_PADW, 0)))

    summary, conflicts_s = pl.pallas_call(
        functools.partial(_kernel, W=W, A=Ap),
        grid=(T // _B,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=[pl.BlockSpec((4, _B), lambda b: (0, b)),
                   pl.BlockSpec((W, _B), lambda b: (0, b))],
        out_shape=[jax.ShapeDtypeStruct((4, T), jnp.int32),
                   jax.ShapeDtypeStruct((W, T), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((_NCOL, _PADW + _B), jnp.int32),
                        pltpu.VMEM((Ap, _PADW + _B), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(cols, clk)
    winner_s, alive_s, vb_s, ovf_s = summary

    # scatter back to original row order + the packed transfer summary
    # (same layout as the XLA twin)
    out = {
        'alive_after':
            jnp.zeros((T,), jnp.int32).at[src].set(alive_s),
        'winner': jnp.full((T,), -1, jnp.int32).at[src].set(winner_s),
        'conflicts':
            jnp.full((T, W), -1, jnp.int32).at[src].set(conflicts_s.T),
        'visible_before':
            jnp.zeros((T,), jnp.bool_).at[src].set(vb_s > 0),
        'overflow':
            jnp.zeros((T,), jnp.bool_).at[src].set(ovf_s > 0),
    }
    out['packed'] = xla_registers.pack_register_word(
        out['winner'], out['alive_after'], out['overflow'])
    return out


def resolve_registers_auto(group, time, actor, seq, is_del, alive_in,
                           sort_idx, clock_table, clock_idx, window=8):
    """Pallas on TPU when the input fits it; the XLA twin otherwise.
    Both compute identical outputs (pinned by unit test).

    The choice is made from the input alone: T a multiple of 128, a
    window of at most 8, the clock slab inside the VMEM budget, and an
    all-alive start (the kernel hardcodes it).  A Pallas failure raises:
    nothing retreats to the twin behind the caller's back.
    """
    T = group.shape[0]
    A = clock_table.shape[1]
    # the mask scan goes LAST in the conjunction: it may force a host
    # sync on a device-resident mask, so only pay it when the Pallas
    # path would otherwise engage
    if (pallas_enabled() and T % _B == 0 and window <= 8
            and vmem_bytes(A, window) <= _VMEM_BUDGET
            and bool(np.all(np.asarray(alive_in)))):
        trace.count('ops.registers.pallas')
        return resolve_registers_pallas(
            group, time, actor, seq, is_del, sort_idx,
            clock_table, clock_idx, window=window)
    trace.count('ops.registers.xla')
    return xla_registers.resolve_registers(
        group, time, actor, seq, is_del=is_del, alive_in=alive_in,
        window=window, sort_idx=sort_idx, clock_table=clock_table,
        clock_idx=clock_idx)
