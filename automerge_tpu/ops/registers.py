"""Batched LWW register resolution.

The reference resolves each assignment sequentially: partition the register's
ops into overwritten (causally superseded) vs concurrent, append the new op,
sort by actor descending; the first op is the winner, the rest are conflicts
(`/root/reference/backend/op_set.js:188-231`).

This kernel computes the same result for EVERY op of a whole multi-document
batch in one dispatch.  Key idea: after sorting ops by (register-group,
application-time), op `p` is alive at time `t` iff no later op `q` with
time_q <= t at the same register causally supersedes it
(supersedes = NOT concurrent, reference op_set.js:7-16).  Supersession is
evaluated over a fixed window of W predecessors -- register survivor sets are
concurrent antichains, which stay tiny in real workloads; a full window
(possible overflow) is flagged, and the host ESCALATES the flagged groups
through wider member-window size classes (W in {16, 32, 64, ...}) in one
re-dispatch per tier (`escalate_overflow`) -- still on device, still exact.
The scalar oracle remains the parity REFEREE (differential tests), not the
executor: only groups wider than every tier (AMTPU_MAX_TIER, default 1024
candidate rows) ever reach the host oracle, and the fuzz/bench workloads
never produce one.

All ops across all docs are flattened into one array; groups are globally
unique ids for (doc, obj, key), so no per-doc padding is needed.
"""

from collections import namedtuple
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.common import env_bool, env_int

# Window of predecessors considered per op in the base dispatch.  Conflict
# sets larger than this overflow and escalate through the tier ladder.
WINDOW = 8

# The packed transfer word carries alive_after in 6 bits (24..29),
# saturated here; every packed-path consumer only tests alive > 0 / > 1.
PACKED_ALIVE_MAX = 63

#: Bit layout of the packed register word, defined ONCE for every
#: encoder/decoder (pack_register_word, _merge_packed_rows,
#: NativeDocPool._unpack_packed; native/core.cpp mirrors it and
#: docs/ARCHITECTURE.md pins it).  Plain ints: usable from numpy and
#: traced jit code alike.
PACKED_WINNER_MASK = 0xffffff    # low 24 bits; == mask means "no winner"
PACKED_WINNER_NONE = 0xffffff
PACKED_ALIVE_SHIFT = 24
PACKED_ALIVE_MASK = 0x3f
PACKED_OVF_SHIFT = 30


def pack_register_word(winner, alive_after, overflow=None):
    """Encodes the packed [T] i32 transfer word: winner (24 bits,
    PACKED_WINNER_NONE = none) | alive_after (6 bits, saturated at
    PACKED_ALIVE_MAX) | overflow in bit PACKED_OVF_SHIFT.  Works on jnp
    and np arrays; the decode twin is NativeDocPool._unpack_packed."""
    xp = jnp if isinstance(winner, jnp.ndarray) else np
    word = (xp.where(winner >= 0, winner,
                     PACKED_WINNER_NONE).astype(xp.int32)
            | (xp.minimum(alive_after, PACKED_ALIVE_MAX).astype(xp.int32)
               << PACKED_ALIVE_SHIFT))
    if overflow is not None:
        word = word | (overflow.astype(xp.int32) << PACKED_OVF_SHIFT)
    return word


def _pairwise_clock(m_actor, clock_table=None, m_cidx=None, m_clock=None):
    """P[t, u, v] = clock of member u at the actor of member v -- the
    pairwise supersession input.  Two formulations, bit-equal:

      * one-hot einsum (batched matmul): MXU-shaped work, the right form
        on accelerators;
      * flat gather from the compact clock table (or take_along_axis on
        an already-materialized [T, W+1, A] m_clock): measured 3.5x
        faster than the int32 einsum on XLA:CPU at the config-4 shape,
        and the table form never materializes m_clock at all.

    Entries for invalid members are garbage under the gather forms (the
    clipped indexes read arbitrary real rows); every consumer masks by
    member validity, so the two forms stay bit-equal where it matters.
    """
    import jax as _jax
    on_cpu = _jax.default_backend() == 'cpu'
    if on_cpu and clock_table is not None:
        A = clock_table.shape[1]
        idx = m_cidx[:, :, None] * A + m_actor[:, None, :]
        return clock_table.reshape(-1)[idx]
    if m_clock is None:
        m_clock = clock_table[m_cidx]
    if on_cpu:
        Wp1 = m_actor.shape[1]
        idx = jnp.broadcast_to(m_actor[:, None, :],
                               (m_actor.shape[0], Wp1, Wp1))
        return jnp.take_along_axis(m_clock, idx, axis=2)
    A = m_clock.shape[2]
    onehot = jax.nn.one_hot(m_actor, A, dtype=jnp.int32)
    return jnp.einsum('tua,tva->tuv', m_clock, onehot)


def _order_by_paircount(m_actor, m_time, alive, m_src, W):
    """Winner/conflicts from member arrays without a sort: position by
    pairwise count over (actor desc, time desc) -- times are unique, so
    the order is total -- then scatter through a position one-hot.
    Returns (winner [T], conflicts [T, W]) with -1 padding."""
    a_u = m_actor[:, :, None]
    a_v = m_actor[:, None, :]
    t_u = m_time[:, :, None]
    t_v = m_time[:, None, :]
    precede = alive[:, None, :] & \
        ((a_v > a_u) | ((a_v == a_u) & (t_v > t_u)))          # v before u
    pos = jnp.sum(precede.astype(jnp.int32), axis=2)          # [T, W+1]
    src = jnp.where(alive, m_src, -1)
    winner = jnp.sum(jnp.where((pos == 0) & alive, src + 1, 0), axis=1) - 1
    kpos = jax.lax.broadcasted_iota(jnp.int32,
                                    (m_actor.shape[0], W + 1, W), 2)
    poh = (pos[:, :, None] == kpos + 1) & alive[:, :, None]
    conflicts = jnp.sum(jnp.where(poh, (src + 1)[:, :, None], 0), axis=1) - 1
    return winner, conflicts


@partial(jax.jit, static_argnames=('window', 'want_visible_before'))
def resolve_registers_members(time, actor, seq, mem_idx, is_del,
                              clock_table, clock_idx, window=WINDOW,
                              want_visible_before=True):
    """Member-explicit register resolution -- EXACT for up to `window`
    concurrent actor streams per key.

    The sliding-window variant (`resolve_registers`) sees the W rows
    immediately preceding each op, so a key written many times (hot map
    keys, 8 actors x many rounds) fills the window with DEAD sequential
    versions and overflows to the host constantly.  Here the host builds
    `mem_idx[t, w]`: the row index of the w-th candidate predecessor --
    the LATEST row of each actor stream active on the key before t (an op
    with an older same-actor successor is always superseded, so only
    per-actor-latest rows can survive; the true bound is the concurrent
    antichain width, not the write count).  -1 marks empty slots.

    Supersession among members orders by TIME (later member supersedes a
    non-concurrent earlier one); winner/conflict order is actor rank
    descending with ties newest-first, matching the batch tie rule
    (backend/op_set.py apply_assign).

    Returns the same dict as `resolve_registers`, in original row order;
    `overflow` is all-False (the host flags >window-stream groups itself
    and routes them through the escalation ladder -- a wider tier of this
    same kernel -- before dispatch; see `escalate_overflow`).

    `want_visible_before=False` drops the visible_before output AND its
    compute (a second [T, W+1, W+1] reduction chain) -- the native
    packed epilogue never reads it (C++ tracks its own running
    visibility); only the mesh step (`parallel/mesh.py`) and the fused
    dominance derivation need it.
    """
    T = time.shape[0]
    W = window

    valid_m = mem_idx >= 0                                    # [T, W]
    midx = jnp.clip(mem_idx, 0, T - 1)
    all_idx = jnp.concatenate(
        [jnp.arange(T, dtype=jnp.int32)[:, None], midx], axis=1)  # [T, W+1]
    all_valid = jnp.concatenate(
        [jnp.ones((T, 1), bool), valid_m], axis=1)
    m_actor = actor[all_idx]
    m_seq = seq[all_idx]
    m_time = time[all_idx]
    m_del = is_del[all_idx]
    # member clocks gather INDICES first, then pairwise values straight
    # from the compact deduplicated table (_pairwise_clock: flat gather
    # on CPU -- [T, W+1, A] never materializes -- one-hot einsum on
    # accelerators): [T, W+1] small gather + the pairwise lookup beat
    # materializing [T, A] and gathering the blown-up matrix
    m_cidx = clock_idx[all_idx]                               # [T, W+1]
    P = _pairwise_clock(m_actor, clock_table, m_cidx)         # [T,W+1,W+1]
    u_clock_at_v = P
    v_clock_at_u = jnp.swapaxes(P, 1, 2)
    u_seq = m_seq[:, :, None]
    v_seq = m_seq[:, None, :]
    concurrent = (u_clock_at_v < v_seq) & (v_clock_at_u < u_seq)
    later = m_time[:, :, None] > m_time[:, None, :]
    supersedes = later & ~concurrent \
        & all_valid[:, :, None] & all_valid[:, None, :]

    superseded = jnp.any(supersedes, axis=1)                  # [T, W+1]
    alive = all_valid & ~superseded & ~m_del

    visible_before = None
    if want_visible_before:
        superseded_wo_self = jnp.any(supersedes[:, 1:, :], axis=1)
        alive_before = all_valid & ~superseded_wo_self & ~m_del
        visible_before = jnp.any(alive_before[:, 1:], axis=1)

    alive_after = jnp.sum(alive, axis=1).astype(jnp.int32)

    # winner/conflicts order: actor desc, ties newest-first.  Ordering
    # WITHOUT argsort: times are unique, so each alive member's output
    # position is a PAIRWISE COUNT --
    #   pos(u) = #{v alive : actor_v > actor_u
    #              or (actor_v == actor_u and time_v > time_u)}
    # -- and winner/conflicts scatter through a position one-hot.  The
    # same formulation as the Pallas stencil kernel, bit-equal to the
    # two-stable-argsort epilogue it replaced; a stable argsort over
    # [T, W+1] was the single costliest op of this kernel on XLA:CPU.
    winner, conflicts = _order_by_paircount(m_actor, m_time, alive,
                                            all_idx, W)

    out = {
        'alive_after': alive_after,
        'winner': winner,
        'conflicts': conflicts,
        'overflow': jnp.zeros((T,), jnp.bool_),
    }
    if want_visible_before:
        out['visible_before'] = visible_before
    out['packed'] = pack_register_word(out['winner'], out['alive_after'])
    return out


@partial(jax.jit, static_argnames=('window',))
def resolve_registers(group, time, actor, seq, clock=None, is_del=None,
                      alive_in=None, window=WINDOW, sort_idx=None,
                      clock_table=None, clock_idx=None):
    """Resolves every register op of a batch.

    Args:
      group: [T] int32 -- register group id ((doc, obj, key) interned);
             -1 for padding rows.
      time:  [T] int32 -- application position (unique, total order; state
             ops carry times below every batch op).
      actor: [T] int32 -- actor rank of the op's change.
      seq:   [T] int32 -- seq of the op's change.
      clock: [T, A] int32 -- allDeps row of the op's change.
      is_del:[T] bool -- 'del' ops overwrite but never join the register.
      alive_in: [T] bool -- for pre-existing state ops: True; for batch ops:
             True (they are considered at their own time).
      sort_idx: optional [T] int32 -- precomputed np.lexsort((time, group))
             permutation; hoisted to the host by batch callers because
             XLA:CPU compiles large in-graph sorts in tens of seconds.
      clock_table/clock_idx: optional [C, A] + [T] -- deduplicated clock
             rows (ops of one change share a row): host->device traffic
             shrinks ~16x and the full [T, A] matrix materializes only
             on device.  Exactly one of `clock` or the
             (clock_table, clock_idx) pair must be given.

    Returns dict of [T]-shaped outputs (original op order):
      alive_after: int32 -- register size right after this op.
      winner:      int32 -- op index (into this batch array) of the register
                   winner after this op, or -1 if the register is empty.
      conflicts:   int32 [T, window] -- losing op indices, actor-descending,
                   -1 padded.
      visible_before: bool -- register non-empty just before this op.
      overflow:    bool -- window saturated; the host escalates this group
                   through a wider kernel tier (`escalate_overflow`).
    """
    T = group.shape[0]
    W = window
    if (clock is None) == (clock_table is None) or \
            (clock_table is None) != (clock_idx is None):
        raise ValueError('pass exactly one of clock or '
                         '(clock_table, clock_idx)')

    # sort by (group, time); padding (group == -1) sorts first and is inert
    if sort_idx is None:
        sort_idx = jnp.lexsort((time, group))
    g_s = group[sort_idx]
    t_s = time[sort_idx]
    a_s = actor[sort_idx]
    q_s = seq[sort_idx]
    d_s = is_del[sort_idx]

    # Window member w of op i lives at sorted position i - w (w in 1..W):
    # a SLIDING window, so member arrays are shifted copies, not gathers
    # (TPU: slices fuse; random gathers do not).
    def shifted(arr, w, fill):
        if w >= arr.shape[0]:
            return jnp.full(arr.shape, fill, arr.dtype)
        pad = jnp.full((w,) + arr.shape[1:], fill, arr.dtype)
        return jnp.concatenate([pad, arr[:-w]], axis=0)

    def members(arr, fill):
        """[T, W+1, ...]: slot 0 = self, slot w = w-th predecessor."""
        return jnp.stack([arr] + [shifted(arr, w, fill)
                                  for w in range(1, W + 1)], axis=1)

    m_actor = members(a_s, 0)
    m_seq = members(q_s, 0)
    m_del = members(d_s, False)
    m_group = members(g_s, -2)
    m_valid = (m_group == g_s[:, None]) & (g_s >= 0)[:, None]   # [T, W+1]

    # pairwise: does member u supersede member v?  (u applied later, and they
    # are NOT concurrent).  Member order by slot: slot 0 is the latest op,
    # larger slots are earlier.  u later than v  <=>  slot_u < slot_v.
    #
    # P[t, u, v] = clock of member u at the actor of member v; formulation
    # picked per backend in _pairwise_clock (flat table gather on CPU,
    # one-hot batched matmul on accelerators).  Invalid-member entries are
    # masked by m_valid below.
    if clock_table is not None:
        m_cidx = members(clock_idx[sort_idx], 0)                # [T, W+1]
        P = _pairwise_clock(m_actor, clock_table, m_cidx)
    else:
        P = _pairwise_clock(m_actor, m_clock=members(clock[sort_idx], 0))
    u_clock_at_v = P
    v_clock_at_u = jnp.swapaxes(P, 1, 2)
    u_seq = m_seq[:, :, None]
    v_seq = m_seq[:, None, :]
    concurrent = (u_clock_at_v < v_seq) & (v_clock_at_u < u_seq)  # [T,W+1,W+1]
    later = (jnp.arange(W + 1)[:, None] < jnp.arange(W + 1)[None, :])  # u<v slot
    supersedes = later[None, :, :] & ~concurrent \
        & m_valid[:, :, None] & m_valid[:, None, :]

    # alive after op i: member v is alive iff valid and no member u (at or
    # before time_i, i.e. any slot) supersedes it, and v is not a del
    superseded = jnp.any(supersedes, axis=1)                        # [T, W+1]
    alive = m_valid & ~superseded & ~m_del                          # [T, W+1]

    # visible before op i: drop self (slot 0), member alive considering only
    # supersessions by predecessors (exclude slot-0 superseder)
    superseded_wo_self = jnp.any(supersedes[:, 1:, :], axis=1)      # [T, W+1]
    alive_before = m_valid & ~superseded_wo_self & ~m_del
    visible_before = jnp.any(alive_before[:, 1:], axis=1)

    alive_after = jnp.sum(alive, axis=1).astype(jnp.int32)

    # winner: alive member with max actor rank; conflicts: remaining alive
    # members, actor-descending (the reference's sortBy(actor).reverse()),
    # ties newest-first (slot ascending = time descending).  Ordered by
    # pairwise count instead of a stable argsort over [T, W+1] -- the
    # Pallas kernel's formulation, bit-equal and far cheaper on XLA:CPU.
    m_t = members(t_s, 0)
    member_src = members(sort_idx, -1)                              # [T, W+1]
    winner, conflicts = _order_by_paircount(m_actor, m_t, alive,
                                            member_src, W)

    # overflow: the whole window is same-group valid AND the earliest window
    # slot is still alive -- older ops beyond the window could matter
    window_full = jnp.all(m_valid[:, 1:], axis=1)
    overflow = window_full & (g_s >= 0)

    # scatter back to original op order
    out = {
        'alive_after': jnp.zeros((T,), jnp.int32).at[sort_idx].set(alive_after),
        'winner': jnp.full((T,), -1, jnp.int32).at[sort_idx].set(winner),
        'conflicts': jnp.full((T, W), -1, jnp.int32).at[sort_idx].set(conflicts),
        'visible_before': jnp.zeros((T,), jnp.bool_).at[sort_idx].set(visible_before),
        'overflow': jnp.zeros((T,), jnp.bool_).at[sort_idx].set(overflow),
    }
    # transfer-packed summary: winner (24 bits, 0xffffff = none) | alive
    # (6 bits, SATURATED at PACKED_ALIVE_MAX -- consumers only test >0 and
    # >1; the exact count stays in the unpacked alive_after) | overflow
    # (bit 30).  One [T] i32 D2H instead of four arrays; conflicts rows
    # are fetched lazily only where alive > 1.  Callers must use the
    # unpacked outputs when T >= 2**24.
    out['packed'] = pack_register_word(out['winner'], out['alive_after'],
                                       out['overflow'])
    return out


@jax.jit
def gather_rows(mat, rows):
    """Row gather for the lazy conflicts fetch."""
    return mat[rows]


def _merge_packed_rows(base, rows_p, tier_packed, sub_p):
    """Scatters one escalation-tier chunk's packed words into the base
    packed array ON DEVICE (ISSUE 6 tentpole b): tier-local winner
    indexes translate to global batch rows through `sub_p` (the chunk's
    row map), alive bits carry over, and the overflow bit stays clear --
    the scattered rows are, by construction, resolved.  Padding slots of
    `rows_p` carry an out-of-bounds index and drop.  After the chain of
    chunk merges, ONE device->host transfer returns the packed word
    already resolved for every tier-escalated row; the host's only
    remaining merge work is the residual (oracle) flag vector."""
    win = tier_packed & PACKED_WINNER_MASK
    n = sub_p.shape[0]
    win_g = jnp.where(win == PACKED_WINNER_NONE, PACKED_WINNER_NONE,
                      sub_p[jnp.clip(win, 0, n - 1)])
    word = (((tier_packed >> PACKED_ALIVE_SHIFT) & PACKED_ALIVE_MASK)
            << PACKED_ALIVE_SHIFT) | win_g
    return base.at[rows_p].set(word, mode='drop')


_merge_packed_jit = jax.jit(_merge_packed_rows)
_merge_packed_donated = jax.jit(_merge_packed_rows, donate_argnums=(0,))


def device_merge_on():
    """AMTPU_DEVICE_MERGE=0 keeps the escalation-tier merge on the host
    (the PR-3 scatter); default on (checked per batch, not latched --
    the A/B parity lane flips it)."""
    return env_bool('AMTPU_DEVICE_MERGE', True)


def merge_packed_rows_jit():
    """`_merge_packed_rows` for this backend: the base word is DONATED
    on accelerators (each chunk merge reuses the previous buffer instead
    of allocating -- the donate_argnums pattern proven on the tier
    staging path); on CPU donation buys nothing and jit aliases anyway."""
    if jax.default_backend() == 'cpu':
        return _merge_packed_jit
    return _merge_packed_donated


def _resolve(group, time, actor, seq, clock_table, clock_idx, is_del,
             alive_in, sort_idx, mem_idx, window,
             want_visible_before=True):
    """Mode dispatch: member-explicit when the host built mem_idx (groups
    wider than the sliding window), else the sliding-window kernel.
    `want_visible_before` only prunes the member kernel (the sliding
    kernel computes it either way)."""
    if mem_idx is not None:
        return resolve_registers_members(
            time, actor, seq, mem_idx, is_del, clock_table, clock_idx,
            window=window, want_visible_before=want_visible_before)
    return resolve_registers(group, time, actor, seq, is_del=is_del,
                             alive_in=alive_in, window=window,
                             sort_idx=sort_idx, clock_table=clock_table,
                             clock_idx=clock_idx)


@partial(jax.jit, static_argnames=('window',))
def resolve_and_rank(group, time, actor, seq, clock_table, clock_idx,
                     is_del, alive_in, sort_idx,
                     eobj, epar, ectr, eact, evalid, lin_sort, n_iters,
                     window=WINDOW, mem_idx=None):
    """Register resolution + RGA linearization in ONE dispatch: the two
    computations are independent, so fusing them halves the dispatch /
    sync round trips of a batch.  Member-mode visible_before
    is pruned: this entry's consumers (the native mode='old' paths) take
    running visibility from the C++ mirrors, never from the kernel."""
    from .list_rank import linearize
    reg = _resolve(group, time, actor, seq, clock_table, clock_idx, is_del,
                   alive_in, sort_idx, mem_idx, window,
                   want_visible_before=False)
    rank = linearize(eobj, epar, ectr, eact, evalid, n_iters,
                     sort_idx=lin_sort)
    return reg, rank


def dominance_op_inputs(reg, rank, oe, dom_src, ov):
    """Per-op dominance inputs derived from the register outputs and a
    fresh rank vector: orank gathers the touched element's rank, od is
    the op's visibility delta (alive_after - visible_before of its
    register row).  Shared by the unsharded and sp-sharded resident
    kernels so the derivation cannot drift between them."""
    C = rank.shape[0]
    orank = jnp.where(ov, rank[jnp.clip(oe, 0, C - 1)], -1)
    T = reg['alive_after'].shape[0]
    row = jnp.clip(dom_src, 0, T - 1)
    od = jnp.where(dom_src >= 0,
                   (reg['alive_after'][row] > 0).astype(jnp.int32)
                   - reg['visible_before'][row].astype(jnp.int32),
                   0)
    return orank, od


def resolve_rank_dominate_resident(group, time, actor, seq, clock_table,
                                   clock_idx, is_del, alive_in, sort_idx,
                                   epar, ectr, eact, ev, n_elems,
                                   oe, dom_src, ov,
                                   n_iters=1, window=WINDOW, chunk=64):
    """The fused resolver over a DEVICE-RESIDENT single-object arena
    (SURVEY hard part 5: incremental state across batches).

    Unlike `resolve_rank_dominate`, the arena columns (epar/ectr/eact)
    and the element-visibility vector (ev, f32) are long-lived device
    arrays owned by the pool's resident cache -- the host uploads only
    per-batch deltas (appended rows, register rows, per-op arrays).
    Derivations the host used to precompute per batch happen in-graph:

      * the sibling sort (lin_sort) runs as linearize's in-graph lexsort,
      * v0 IS the resident ev,
      * er_src is the identity (single object at arena base 0),
      * orank gathers from the freshly computed rank.

    Args mirror resolve_rank_dominate where shared; epar/ectr/eact/ev are
    [C] (C = the block's padded arena size), n_elems the live count,
    oe/dom_src/ov are [1, Tp] per-op arrays.  Returns the same
    (reg, rank, combo) contract, so the packed-transfer consumer in the
    native driver is unchanged.
    """
    from .list_rank import dominance_grouped, linearize
    reg = _resolve(group, time, actor, seq, clock_table, clock_idx, is_del,
                   alive_in, sort_idx, None, window)
    C = epar.shape[0]
    valid = jnp.arange(C, dtype=jnp.int32) < n_elems
    obj0 = jnp.zeros((C,), jnp.int32)
    rank = linearize(obj0, epar, ectr, eact, valid, n_iters)
    er = jnp.where(valid, rank, -1)[None, :]
    orank, od = dominance_op_inputs(reg, rank, oe, dom_src, ov)
    idx = dominance_grouped(ev[None, :], er, oe, orank, od, ov, chunk=chunk)
    combo = jnp.concatenate([reg['packed'], idx.reshape(-1)])
    return reg, rank, combo


@partial(jax.jit, static_argnames=('window', 'chunk'))
def resolve_rank_dominate(group, time, actor, seq, clock_table, clock_idx,
                          is_del, alive_in, sort_idx,
                          eobj, epar, ectr, eact, evalid, lin_sort, n_iters,
                          v0, er_src, oe, orank_src, dom_src, ov,
                          window=WINDOW, chunk=64, mem_idx=None):
    """The full resolver in ONE device dispatch: register resolution, RGA
    linearization, AND per-op list dominance indexes.

    The reference interleaves these stages per op (apply -> skip-list
    indexOf, `/root/reference/backend/op_set.js:233-295` + skip_list.js);
    here the dominance stage's rank-dependent inputs are gathered ON
    DEVICE from the linearize output, and its visibility deltas are
    derived from the register kernel's own alive/visible outputs -- so a
    whole multi-doc batch costs a single dispatch and a single packed
    device->host transfer (winner/alive/overflow + dominance indexes),
    with no rank readback at all on the common path.

    Dominance-layout args (built by the C++ runtime at begin):
      v0:        [W, Lp] f32 -- element visibility at batch start.
      er_src:    [W, Lp] i32 -- arena-global element index, -1 padding.
      oe:        [W, Tp] i32 -- local element index per timeline op.
      orank_src: [W, Tp] i32 -- arena-global index of the touched element.
      dom_src:   [W, Tp] i32 -- register row of the timeline op, -1 pad.
      ov:        [W, Tp] bool.

    Returns (reg dict, rank [L], combo [T + W*Tp] i32) where combo is the
    packed register summary concatenated with the dominance indexes --
    fetch it with ONE transfer; rank stays device-resident unless the
    overflow fallback needs it.
    """
    from .list_rank import dominance_grouped, linearize
    reg = _resolve(group, time, actor, seq, clock_table, clock_idx, is_del,
                   alive_in, sort_idx, mem_idx, window)
    rank = linearize(eobj, epar, ectr, eact, evalid, n_iters,
                     sort_idx=lin_sort)
    L = rank.shape[0]
    er = jnp.where(er_src >= 0, rank[jnp.clip(er_src, 0, L - 1)], -1)
    orank = jnp.where(orank_src >= 0, rank[jnp.clip(orank_src, 0, L - 1)],
                      -1)
    T = reg['alive_after'].shape[0]
    row = jnp.clip(dom_src, 0, T - 1)
    od = jnp.where(dom_src >= 0,
                   (reg['alive_after'][row] > 0).astype(jnp.int32)
                   - reg['visible_before'][row].astype(jnp.int32),
                   0)
    idx = dominance_grouped(v0, er, oe, orank, od, ov, chunk=chunk)
    combo = jnp.concatenate([reg['packed'], idx.reshape(-1)])
    return reg, rank, combo


# ---------------------------------------------------------------------------
# tiered escalation ladder (host driver)
#
# The base dispatch runs at WINDOW; groups it flags as overflowed are
# re-encoded into a flat padded member-window layout and re-dispatched
# through power-of-two size classes W in {16, 32, 64, ...} -- ONE device
# pass per tier present in the batch, never one host replay per group.
# Member candidates are the per-actor-LATEST rows of each stream (only
# those can survive: an op with a newer same-actor successor is always
# superseded), extended with every row of an actor's latest seq so that
# same-change duplicate assigns -- the one shape the fixed member build
# in native/core.cpp routes to overflow -- bucket into a (slightly
# wider) tier instead of the oracle.  A group only reaches the host
# oracle when its candidate width exceeds every tier (AMTPU_MAX_TIER).
# ---------------------------------------------------------------------------

#: smallest escalation tier; the ladder is floor, 2*floor, 4*floor, ...
ESCALATION_FLOOR = 16

#: widest tier before a group falls back to the host oracle
#: (AMTPU_MAX_TIER overrides)
DEFAULT_MAX_TIER = 1024

#: cap on ONE tier dispatch's dominant device intermediate -- the
#: [Tn, W+1, W+1] pairwise supersession tensor (i32).  Groups whose own
#: padded cost exceeds this are memory-unboundable at any chunking and
#: take the host oracle (counted fallback.oracle); multi-group tiers are
#: CHUNKED into as many dispatches as the budget requires.  256 MB
#: matches the dominance kernel's slab cap.  AMTPU_ESCALATE_BUDGET_MB
#: overrides.
DEFAULT_ESCALATION_BUDGET = 256 << 20


#: row cap per tier-chunk dispatch (AMTPU_ESC_CHUNK overrides).  Shape
#: bucketing pads each chunk to the next power of two, so one huge chunk
#: wastes up to ~2x its rows in padding compute (config 4: 80k flagged
#: rows padded to 131k); capping chunks at a power-of-two row count
#: bounds the waste to the LAST chunk, keeps the jit cache on one shape
#: per tier, and turns the tier into several async dispatches that
#: overlap the driver's other host work.  A lone group wider than the
#: cap still dispatches alone (groups are indivisible).
DEFAULT_ESC_CHUNK = 32768


def _esc_chunk_rows():
    n = env_int('AMTPU_ESC_CHUNK', DEFAULT_ESC_CHUNK)
    return n if n > 0 else DEFAULT_ESC_CHUNK


def _escalation_budget():
    # unset -> the built-in default; an EXPLICIT 0 is a zero-byte
    # budget, forcing every overflowed group to the host oracle (the
    # A/B hook the parity lanes use) -- distinct sentinels keep that
    mb = env_int('AMTPU_ESCALATE_BUDGET_MB', -1)
    return (mb << 20) if mb >= 0 else DEFAULT_ESCALATION_BUDGET


def escalation_enabled():
    """AMTPU_ESCALATE=0 disables the ladder (every overflowed group then
    takes the host oracle, the pre-escalation behaviour) -- an A/B and
    parity-test hook, checked per batch."""
    return env_bool('AMTPU_ESCALATE', True)


def _tier_of(n, floor=ESCALATION_FLOOR):
    w = floor
    while w < n:
        w *= 2
    return w


def _dispatch_cost(n_rows, W):
    """Bytes of the dominant [Tn, W+1, W+1] i32 intermediate of one
    member-kernel dispatch, at the PADDED row count."""
    return _tier_of(n_rows, ESCALATION_FLOOR) * (W + 1) * (W + 1) * 4


def escalate_overflow(group, time, actor, seq, is_del, clock_table,
                      clock_idx, overflow, floor=ESCALATION_FLOOR,
                      max_tier=None):
    """Resolves every row of every overflow-flagged register group through
    wider member-window kernel tiers (synchronous composition of
    `escalate_overflow_dispatch` + `escalate_overflow_collect`; pipelined
    callers split the two so tier kernels overlap other host work).

    Args (host numpy, original row order; padding rows carry group == -1):
      group/time/actor/seq/is_del: the register columns fed to the base
          dispatch.
      clock_table, clock_idx: deduplicated clock rows (callers with a
          dense [T, A] clock pass it as the table with clock_idx=arange).
      overflow: [T] bool -- the base kernel's overflow flags (sliding
          mode) or the host-computed member flags.  The WHOLE group of any
          flagged row is re-resolved (flags may cover only the saturated
          suffix).

    Returns (resolved, oracle_rows, tier_rows):
      resolved:   {row: (winner_row, [conflict_rows...], alive_after,
                  visible_before)} -- indices are GLOBAL rows, covering
                  every row of every escalated group.
      oracle_rows: np.int32 [n] -- rows of groups wider than every tier
                  OR too large for the device-scratch budget; the caller
                  must resolve these with the host oracle.
      tier_rows:  {W: row count} -- rows resolved per tier (the caller's
                  telemetry source).
    """
    pending, oracle_rows, tier_rows = escalate_overflow_dispatch(
        group, time, actor, seq, is_del, clock_table, clock_idx,
        overflow, floor=floor, max_tier=max_tier)
    return escalate_overflow_collect(pending), oracle_rows, tier_rows


def _member_windows(rows, actor, seq):
    """Member-candidate windows for ONE escalated group, vectorized.

    `rows` are the group's global row ids in (group, time) order.  Row
    j's candidacy ends at the first later row of the same actor with a
    DIFFERENT seq (a same-actor successor supersedes it; same-change
    duplicate assigns share a seq and accumulate) -- and the superseding
    row itself still SEES j, because member lists are built before the
    stream update.  So j is a member of row i's window iff
    j < i <= kill(j), which turns the whole build into interval
    expansion instead of per-row Python list copies (the old streams
    loop was O(rows * width) of interpreter work per group).

    Returns a CSR group record (rows, lens [k], vals, width): row i's
    candidates are the next lens[i] entries of vals (group-LOCAL
    indexes), the same layout the C++ escalation layout (amtpu_esc_*)
    emits.
    """
    k = len(rows)
    a = np.asarray(actor[rows])
    s = np.asarray(seq[rows])
    # kill[j]: reverse scan over each actor's time-ordered rows (the
    # stable argsort groups actors while preserving time order within)
    order = np.argsort(a, kind='stable')
    kill = np.full(k, k, np.int64)
    for x in range(k - 2, -1, -1):
        j, nxt = order[x], order[x + 1]
        if a[j] == a[nxt]:
            kill[j] = nxt if s[j] != s[nxt] else kill[nxt]
    # per-row window width without materializing the pair list:
    # lens(i) = #{j : j < i <= kill(j)} via a difference array
    delta = np.zeros(k + 2, np.int64)
    delta[1:k + 1] += 1
    np.subtract.at(delta, kill + 1, 1)
    lens_i = np.cumsum(delta)[:k]
    width = int(lens_i.max(initial=0))
    if width == 0:
        return (rows, lens_i, np.zeros(0, np.int64), 0)
    # expand each j into its target rows [j+1, min(kill(j), k-1)] as
    # (i, j) pairs (kill == k marks never-killed candidates); sorted by
    # i, the j's are exactly the CSR value runs
    jlens = np.minimum(kill, k - 1) - np.arange(k)
    total = int(jlens.sum())
    j_rep = np.repeat(np.arange(k, dtype=np.int64), jlens)
    cum = np.concatenate(([0], np.cumsum(jlens)[:-1]))
    i_tgt = j_rep + 1 + (np.arange(total) - np.repeat(cum, jlens))
    ordp = np.argsort(i_tgt, kind='stable')
    return (rows, lens_i, j_rep[ordp], width)


def _tier_alloc(Tn, W):
    return {
        'mem': np.empty((Tn, W), np.int32),
        'time': np.empty((Tn,), np.int32),
        'actor': np.empty((Tn,), np.int32),
        'seq': np.empty((Tn,), np.int32),
        'isdel': np.empty((Tn,), bool),
        'cidx': np.empty((Tn,), np.int32),
    }


def _tier_buffers(Tn, W):
    # Every dispatch gets FRESH staging arrays.  An earlier revision
    # reused thread-local buffers on the CPU backend, assuming the
    # dispatch-time host->device copy is synchronous -- it is not: jax's
    # CPU backend ZERO-COPIES 64-byte-aligned numpy inputs and dispatch
    # is async, so refilling a reused buffer for chunk B while chunk A's
    # kernel is still consuming the same memory silently corrupts A's
    # inputs (alignment-dependent, nondeterministic).  On accelerators
    # the fresh arrays additionally feed donate_argnums.
    return _tier_alloc(Tn, W)


_members_donated = jax.jit(
    resolve_registers_members,
    static_argnames=('window', 'want_visible_before'),
    donate_argnums=(0, 1, 2, 3, 4, 6))


def members_tier_jit():
    """The tier-chunk kernel for this backend.  On accelerators the
    per-row inputs are DONATED: XLA reuses their freshly transferred
    device buffers for outputs instead of allocating per dispatch (the
    host staging arrays are numpy and stay owned by _tier_buffers).
    clock_table (argument 5) is shared across chunks and never donated."""
    if jax.default_backend() == 'cpu':
        return resolve_registers_members
    return _members_donated


def escalate_overflow_dispatch(group, time, actor, seq, is_del,
                               clock_table, clock_idx, overflow,
                               floor=ESCALATION_FLOOR, max_tier=None,
                               want_visible_before=True):
    """The dispatch half of the ladder: host member-window build + one
    ASYNC kernel dispatch per tier chunk.  Only the O(Tn) outputs start
    device->host copies (packed epilogue); the [Tn, W] conflicts matrix
    stays device-resident for the collect half's sparse row gather.
    Returns (pending, oracle_rows, tier_rows) where `pending` is fed to
    `escalate_overflow_collect_arrays` -- callers with a phased pipeline
    dispatch here (phase a) and collect after their other host work
    (phase b), so tier kernels overlap it.

    `want_visible_before=False` (the native drivers) drops that output
    and its kernel compute; collected chunks then carry all-False vb."""
    group = np.asarray(group)
    time = np.asarray(time)
    actor = np.asarray(actor)
    seq = np.asarray(seq)
    is_del = np.asarray(is_del)
    clock_idx = np.asarray(clock_idx, np.int32)

    flagged = np.asarray(overflow, bool) & (group >= 0)
    ovf_gids = np.unique(group[flagged])
    if ovf_gids.size == 0:
        return [], np.zeros((0,), np.int32), {}

    # all rows of the flagged groups, in (group, time) order
    sel = np.isin(group, ovf_gids)
    sel_rows = np.nonzero(sel)[0]
    order = np.lexsort((time[sel_rows], group[sel_rows]))
    sel_rows = sel_rows[order]
    bounds = np.nonzero(np.diff(group[sel_rows]))[0] + 1
    groups = [_member_windows(rows, actor, seq)
              for rows in np.split(sel_rows, bounds)]
    return escalate_dispatch_groups(
        groups, time, actor, seq, is_del, clock_table, clock_idx,
        floor=floor, max_tier=max_tier,
        want_visible_before=want_visible_before)


def escalate_dispatch_groups(groups, time, actor, seq, is_del,
                             clock_table, clock_idx,
                             floor=ESCALATION_FLOOR, max_tier=None,
                             want_visible_before=True):
    """Dispatch half over PREBUILT CSR group records
    (rows, lens, vals, width) -- either `_member_windows` output or the
    C++ escalation layout (amtpu_esc_*), which the native driver reads
    instead of re-deriving windows host-side.  Same return contract as
    `escalate_overflow_dispatch`."""
    from .. import faults, telemetry

    if faults.ARMED:
        # tier dispatch is pure device work over a still-live batch
        # handle: a fault here propagates to the phase-a/b handlers,
        # which roll the pool back -- retry/bisect stay byte-safe
        faults.fire('escalation.tier')
    if max_tier is None:
        max_tier = env_int('AMTPU_MAX_TIER', DEFAULT_MAX_TIER)
    time = np.asarray(time)
    actor = np.asarray(actor)
    seq = np.asarray(seq)
    is_del = np.asarray(is_del)
    clock_idx = np.asarray(clock_idx, np.int32)

    budget = _escalation_budget()
    pending = []
    tier_rows = {}
    tiers = {}        # W -> [group record]
    oracle_rows = []
    for grp in groups:
        rows, width = grp[0], grp[3]
        W = _tier_of(max(width, 1), floor)
        if W > max_tier or _dispatch_cost(len(rows), W) > budget:
            # wider than every tier, or memory-unboundable at any
            # chunking: the one remaining host-oracle route
            oracle_rows.extend(int(r) for r in rows)
            continue
        tiers.setdefault(W, []).append(grp)
        telemetry.ESCALATION_TIER.observe(W)

    chunk_cap = _esc_chunk_rows()
    for W, entries in sorted(tiers.items()):
        # chunk the tier so each dispatch's [Tn, W+1, W+1] intermediate
        # stays under the scratch budget (a lone group always fits: the
        # bucketing above sent oversized ones to the oracle) AND under
        # the row cap (padding-waste bound, see DEFAULT_ESC_CHUNK)
        chunks, cur, cur_rows = [], [], 0
        for entry in entries:
            n_rows = len(entry[0])
            if cur and (_dispatch_cost(cur_rows + n_rows, W) > budget
                        or cur_rows + n_rows > chunk_cap):
                chunks.append(cur)
                cur, cur_rows = [], 0
            cur.append(entry)
            cur_rows += n_rows
        chunks.append(cur)
        for chunk in chunks:
            sub_rows = np.concatenate([g[0] for g in chunk])
            n = len(sub_rows)
            Tn = _tier_of(n, ESCALATION_FLOOR)  # shape-bucketed padding
            bufs = _tier_buffers(Tn, W)
            mem = bufs['mem']
            mem[:] = -1
            # CSR -> padded window matrix, vectorized per CHUNK: row and
            # member indexes are group-local; adding each group's chunk
            # offset makes them chunk-local
            offs = np.concatenate(
                ([0], np.cumsum([len(g[0]) for g in chunk])))
            lens_cat = np.concatenate([g[1] for g in chunk])
            total = int(lens_cat.sum())
            if total:
                vals_cat = np.concatenate(
                    [g[2] + off for g, off in zip(chunk, offs)])
                ii = np.repeat(np.arange(n), lens_cat)
                starts = np.concatenate(([0], np.cumsum(lens_cat)[:-1]))
                slot = np.arange(total) - np.repeat(starts, lens_cat)
                mem[ii, slot] = vals_cat

            def pad(name, col, fill):
                out = bufs[name]
                out[:n] = col[sub_rows]
                out[n:] = fill
                return out

            with telemetry.span('device.escalate', tier=W, rows=n):
                out = telemetry.h2d_call(
                    members_tier_jit(),
                    pad('time', time, 0), pad('actor', actor, 0),
                    pad('seq', seq, 0), mem, pad('isdel', is_del, False),
                    clock_table, pad('cidx', clock_idx, 0), window=W,
                    want_visible_before=want_visible_before)
                for key in ('packed', 'winner', 'alive_after',
                            'visible_before'):
                    if key in out and hasattr(out[key],
                                              'copy_to_host_async'):
                        out[key].copy_to_host_async()
            pending.append((W, sub_rows, out))
            tier_rows[W] = tier_rows.get(W, 0) + n
            telemetry.metric('fallback.escalated.w%d' % W, n)

    return pending, np.asarray(oracle_rows, np.int32), tier_rows


#: one collected tier chunk: `rows` are global batch rows; `winner` /
#: `conflicts` carry GLOBAL row ids (-1 padded); `conf_rows` indexes
#: into `rows` (only rows that kept >1 member have a conflicts row)
EscalatedChunk = namedtuple(
    'EscalatedChunk',
    ['rows', 'winner', 'conf_rows', 'conflicts', 'alive',
     'visible_before'])


def escalate_overflow_collect_arrays(pending, need_winner=True):
    """The collect half, vectorized: awaits each tier chunk's O(Tn)
    outputs and translates tier-local indices to global batch rows.
    Conflicts are row-gathered ON DEVICE only where a register kept >1
    member (the tiers' packed epilogue: the [Tn, W] matrix never
    transfers whole).  Returns a list of EscalatedChunk.

    `need_winner=False` skips the winner transfer + translation (chunk
    .winner is None): the device-merge path (`merge_packed_rows_jit`)
    already scattered the tier winners into the packed word on device,
    so the collect half only owes conflicts + aliveness."""
    from .. import telemetry
    read = telemetry.d2h_read

    chunks = []
    for W, sub_rows, out in pending:
        n = len(sub_rows)
        sub = np.ascontiguousarray(sub_rows, np.int64)
        win = read(out['winner'])[:n] if need_winner else None
        alive = np.ascontiguousarray(read(out['alive_after'])[:n],
                                     np.int32)
        if 'visible_before' in out:
            vb = np.ascontiguousarray(
                read(out['visible_before'])[:n], bool)
        else:
            vb = np.zeros((n,), bool)
        conf_rows = np.nonzero(alive > 1)[0].astype(np.int32)
        conf_g = np.zeros((0, W), np.int32)
        if conf_rows.size:
            padlen = 1
            while padlen < conf_rows.size:
                padlen *= 2
            rows_p = np.zeros((padlen,), np.int32)
            rows_p[:conf_rows.size] = conf_rows
            conf = read(telemetry.h2d_call(
                gather_rows, out['conflicts'], rows_p))[:conf_rows.size]
            conf_g = np.where(conf >= 0, sub[np.clip(conf, 0, n - 1)],
                              -1).astype(np.int32)
        win_g = None
        if win is not None:
            win_g = np.where(win >= 0, sub[np.clip(win, 0, n - 1)],
                             -1).astype(np.int32)
        chunks.append(EscalatedChunk(sub.astype(np.int32), win_g,
                                     conf_rows, conf_g, alive, vb))
    return chunks


def escalate_overflow_collect(pending):
    """Dict-contract collect: the global-row `resolved` map
    (`escalate_overflow`'s documented contract), built from the
    vectorized chunks.  Batch drivers consume the array chunks directly
    (`escalate_overflow_collect_arrays`); this form remains for
    per-row consumers and the kernel unit tests."""
    resolved = {}
    for ch in escalate_overflow_collect_arrays(pending):
        conf_of = {}
        for i, local in enumerate(ch.conf_rows):
            conf_of[int(local)] = [int(c) for c in ch.conflicts[i]
                                   if c >= 0]
        for i, r in enumerate(ch.rows):
            resolved[int(r)] = (int(ch.winner[i]), conf_of.get(i, []),
                                int(ch.alive[i]),
                                bool(ch.visible_before[i]))
    return resolved


def merge_escalated_arrays(winner, conflicts, alive, overflow, chunks,
                           visible_before=None):
    """Vectorized merge of EscalatedChunks into the (host, writable)
    register output arrays: scatters winner/conflicts/alive, widens the
    conflicts matrix when a tier kept more survivors than its column
    count, and clears the overflow flag of every resolved row -- flags
    left standing afterwards are exactly the rows the caller must route
    to the host oracle.  Returns the four (possibly replaced) arrays."""
    if not chunks:
        return winner, conflicts, alive, overflow
    width = conflicts.shape[1] if conflicts.ndim == 2 else 0
    need = width
    for ch in chunks:
        if ch.conf_rows.size:
            need = max(need, int((ch.conflicts >= 0).sum(axis=1)
                                 .max(initial=0)))
    if need > width:
        wide = np.full((conflicts.shape[0], need), -1, conflicts.dtype)
        if width:
            wide[:, :width] = conflicts
        conflicts = wide
    for ch in chunks:
        winner[ch.rows] = ch.winner
        conflicts[ch.rows, :] = -1
        if ch.conf_rows.size:
            m = min(ch.conflicts.shape[1], conflicts.shape[1])
            conflicts[ch.rows[ch.conf_rows], :m] = ch.conflicts[:, :m]
        alive[ch.rows] = ch.alive
        overflow[ch.rows] = 0
        if visible_before is not None:
            visible_before[ch.rows] = ch.visible_before
    return winner, conflicts, alive, overflow


def merge_escalated(winner, conflicts, alive, overflow, resolved):
    """Scatters `escalate_overflow` results into the (host) register
    output arrays, widening the conflicts matrix when a tier kept more
    survivors than its column count, and CLEARING the overflow flag of
    every resolved row -- flags left standing afterwards are exactly the
    rows the caller must route to the host oracle.  Returns the four
    (possibly replaced) arrays."""
    if not resolved:
        return winner, conflicts, alive, overflow
    width = conflicts.shape[1] if conflicts.ndim == 2 else 0
    need = max(len(c) for (_, c, _, _) in resolved.values())
    if need > width:
        wide = np.full((conflicts.shape[0], need), -1, conflicts.dtype)
        wide[:, :width] = conflicts
        conflicts = wide
    for row, (w, confs, al, _vb) in resolved.items():
        winner[row] = w
        conflicts[row, :] = -1
        if confs:
            conflicts[row, :len(confs)] = confs
        alive[row] = al
        overflow[row] = 0
    return winner, conflicts, alive, overflow
