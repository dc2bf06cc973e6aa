"""Multi-chip execution: the batched resolver step over a `jax.sharding.Mesh`.

The reference is single-threaded per document and scales only by document
independence (`/root/reference/src/doc_set.js:7-9` holds many independent
docs).  Here that independence becomes the **dp** mesh axis, and the element
axis of long lists/Texts becomes the **sp** (sequence-parallel) axis
(SURVEY.md section 2 mapping table; section 5 "long-context" mapping):

  dp  - documents/replicas sharded across devices; each device schedules,
        resolves and linearizes its own document shard; the cluster-wide
        knowledge frontier (vector-clock union across every replica,
        reference `src/connection.js:9-14` clockUnion) is one `lax.pmax`
        over this axis.
  sp  - the element axis of long lists/Texts.  Arena columns
        (eo/ep/ec/ea/ev/vis0) live SHARDED on sp -- resident state per
        device is O(L/sp).  Per-op list indexes are dominance counts
        (`ops/list_rank.dominance_indexes`) whose visible-mask products
        reduce over the element axis: each sp device computes partial
        counts over its local arena block and a `lax.psum` over sp
        completes them; this is the skip-list-probe replacement and the
        dominant cost for long Texts.  RGA linearization (pointer
        doubling) needs the whole insertion forest, so the step
        all-gathers the arena columns over sp transiently (peak O(L),
        resident O(L/sp)) before doubling; op metadata is then gathered
        locally from the full rank vector.

Visibility deltas are DERIVED on device from the register kernel's own
alive/visible outputs via each list op's register row (`op_row`), the
same formulation the fused single-chip dispatch uses
(`ops/registers.resolve_rank_dominate`) -- so real workloads run
end-to-end without a host-computed timeline.

Everything is a single `shard_map`-wrapped, jitted step: XLA inserts the
collectives and overlaps them with compute over ICI.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)

from ..ops import clock as clock_ops
from ..ops import list_rank
from ..ops import registers as register_ops
from . import replica


def make_mesh(n_devices=None, sp=None):
    """Builds a (dp, sp) mesh over the available devices.

    sp defaults to 2 when the device count is even (so both axes are
    exercised), else 1."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if n > len(devices):
        raise ValueError('need %d devices, have %d' % (n, len(devices)))
    if sp is None:
        sp = 2 if (n % 2 == 0 and n >= 2) else 1
    if n % sp != 0:
        raise ValueError('sp=%d must divide the device count %d' % (sp, n))
    dp = n // sp
    arr = np.array(devices[:dp * sp]).reshape(dp, sp)
    return Mesh(arr, ('dp', 'sp'))


# ---------------------------------------------------------------------------
# the per-doc pipeline (runs identically sharded and unsharded)
# ---------------------------------------------------------------------------

def _op_metadata(elem_obj, elem_rank, op_elem, op_valid):
    """Per-op (object, rank) of the touched element, gathered over the FULL
    arena; invalid ops get the sentinels dominance_indexes excludes
    (obj=-2 never matches an element, rank=-1)."""
    ge = jnp.clip(op_elem, 0, elem_obj.shape[0] - 1)
    orank = jnp.where(op_valid, elem_rank[ge], -1)
    oobj = jnp.where(op_valid, elem_obj[ge], -2)
    return oobj, orank


def _doc_pipeline(batch, n_linearize_iters, eo=None, ep=None, ec=None,
                  ea=None, ev=None):
    """schedule + register-resolve + linearize for a [D, ...] doc batch.
    Pure per-doc vmap -- no cross-doc communication.  The arena columns
    may be passed explicitly (the sharded step all-gathers them over sp
    first); by default they come from the batch."""
    order, doc_clock = jax.vmap(clock_ops.schedule_queue)(
        batch['clock'], batch['ch_actor'], batch['ch_seq'],
        batch['ch_deps'], batch['ch_valid'])

    reg = jax.vmap(lambda g, t, a, s, c, d: register_ops.resolve_registers(
        g, t, a, s, c, d, jnp.ones_like(d)))(
        batch['rg'], batch['rt'], batch['ra'], batch['rs'],
        batch['rc'], batch['rd'])

    if eo is None:
        eo, ep, ec, ea, ev = (batch['eo'], batch['ep'], batch['ec'],
                              batch['ea'], batch['ev'])
    rank = jax.vmap(lambda o, p, c, a, v: list_rank.linearize(
        o, p, c, a, v, n_iters=n_linearize_iters))(eo, ep, ec, ea, ev)
    return order, doc_clock, reg, rank


def _op_deltas(reg, op_row, op_valid):
    """Visibility delta per list op from the register kernel outputs:
    +1 insert, -1 remove, 0 no visibility change -- the reference toggles
    element visibility the same way per applied assign
    (op_set.js:107-163); derived on device like the fused path."""
    T = reg['alive_after'].shape[1]
    row = jnp.clip(op_row, 0, T - 1)
    alive = jnp.take_along_axis(reg['alive_after'], row, axis=1) > 0
    before = jnp.take_along_axis(reg['visible_before'], row, axis=1)
    return jnp.where((op_row >= 0) & op_valid,
                     alive.astype(jnp.int32) - before.astype(jnp.int32),
                     0)


# ---------------------------------------------------------------------------
# sharded step
# ---------------------------------------------------------------------------

_BATCH_SPECS = {
    'clock': P('dp', None),
    'ch_actor': P('dp', None),
    'ch_seq': P('dp', None),
    'ch_deps': P('dp', None, None),
    'ch_valid': P('dp', None),
    'rg': P('dp', None), 'rt': P('dp', None), 'ra': P('dp', None),
    'rs': P('dp', None), 'rc': P('dp', None, None), 'rd': P('dp', None),
    'eo': P('dp', 'sp'), 'ep': P('dp', 'sp'), 'ec': P('dp', 'sp'),
    'ea': P('dp', 'sp'), 'ev': P('dp', 'sp'),
    'vis0': P('dp', 'sp'),
    'op_elem': P('dp', None),
    'op_row': P('dp', None),
    'op_valid': P('dp', None),
}

_OUT_SPECS = {
    'order': P('dp', None),
    'doc_clock': P('dp', None),
    'frontier': P(),
    'alive_after': P('dp', None),
    'winner': P('dp', None),
    'conflicts': P('dp', None, None),
    'visible_before': P('dp', None),
    'overflow': P('dp', None),
    'rank': P('dp', None),
    'indexes': P('dp', None),
}


def build_sharded_step(mesh, n_linearize_iters, chunk=64):
    """Compiles the full resolver step over `mesh`.

    Input: a dict of arrays with GLOBAL shapes (D docs total):
      clock [D, A]; ch_actor/ch_seq/ch_valid [D, C]; ch_deps [D, C, A]
      rg/rt/ra/rs/rd [D, T] (+ rc [D, T, A])      -- register rows
      eo/ep/ec/ea/ev [D, L]                        -- element arenas
      vis0 [D, L]; op_elem/op_row/op_valid [D, Tops]

    The dp axis size must divide D, and the sp axis size must divide L
    (asserted at trace time -- a non-dividing L would silently drop the
    trailing element block).

    Returns a jitted fn producing: order [D, C], doc_clock [D, A],
    frontier [A] (pmax over every doc of every replica shard),
    register outputs [D, T...], rank [D, L], indexes [D, Tops]."""

    @partial(shard_map, mesh=mesh,
             in_specs=(_BATCH_SPECS,), out_specs=_OUT_SPECS)
    def step(batch):
        # arena columns arrive sp-SHARDED (resident state O(L/sp) per
        # device); linearization needs the whole insertion forest, so
        # gather them transiently over sp before pointer doubling
        def gather_sp(x):
            return jax.lax.all_gather(x, 'sp', axis=1, tiled=True)

        eo_f, ep_f, ec_f, ea_f, ev_f = (
            gather_sp(batch['eo']), gather_sp(batch['ep']),
            gather_sp(batch['ec']), gather_sp(batch['ea']),
            gather_sp(batch['ev']))
        order, doc_clock, reg, rank = _doc_pipeline(
            batch, n_linearize_iters, eo_f, ep_f, ec_f, ea_f, ev_f)

        # replica clock gossip: union = elementwise max over the dp axis
        # (reference clockUnion, src/connection.js:9-14, batched)
        frontier = replica.frontier_pmax(jnp.max(doc_clock, axis=0), 'dp')

        # visibility deltas from the register outputs (fused-path rule)
        od = _op_deltas(reg, batch['op_row'], batch['op_valid'])

        # sp-sharded dominance: the LOCAL arena block is this device's
        # input shard; only the rank block is sliced from the gathered
        # full vector
        Ll = batch['eo'].shape[1]
        off = jax.lax.axis_index('sp') * Ll
        er_b = jax.lax.dynamic_slice_in_dim(rank, off, Ll, axis=1)

        def per_doc(eo, er, vis, rank_full, eo_full, oe, odd, ov):
            oobj, orank = _op_metadata(eo_full, rank_full, oe, ov)
            return list_rank.dominance_indexes(
                eo, er, vis, oe, oobj, orank, odd, ov,
                chunk=chunk, axis_name='sp', l_offset=off)

        indexes = jax.vmap(per_doc)(
            batch['eo'], er_b, batch['vis0'], rank, eo_f,
            batch['op_elem'], od, batch['op_valid'])

        return {
            'order': order,
            'doc_clock': doc_clock,
            'frontier': frontier,
            'alive_after': reg['alive_after'],
            'winner': reg['winner'],
            'conflicts': reg['conflicts'],
            'visible_before': reg['visible_before'],
            'overflow': reg['overflow'],
            'rank': rank,
            'indexes': indexes,
        }

    return jax.jit(step)


def single_step(batch, n_linearize_iters, chunk=128):
    """Unsharded reference of the same step (single chip / oracle for the
    sharded path).  jittable."""
    order, doc_clock, reg, rank = _doc_pipeline(batch, n_linearize_iters)
    frontier = jnp.max(doc_clock, axis=0)
    od = _op_deltas(reg, batch['op_row'], batch['op_valid'])

    def per_doc(eo, er, vis, oe, odd, ov):
        oobj, orank = _op_metadata(eo, er, oe, ov)
        return list_rank.dominance_indexes(
            eo, er, vis, oe, oobj, orank, odd, ov, chunk=chunk)

    indexes = jax.vmap(per_doc)(
        batch['eo'], rank, batch['vis0'],
        batch['op_elem'], od, batch['op_valid'])
    return {
        'order': order, 'doc_clock': doc_clock, 'frontier': frontier,
        'alive_after': reg['alive_after'], 'winner': reg['winner'],
        'conflicts': reg['conflicts'],
        'visible_before': reg['visible_before'],
        'overflow': reg['overflow'], 'rank': rank, 'indexes': indexes,
    }


def shard_batch(mesh, batch):
    """Places a global batch dict onto the mesh per `_BATCH_SPECS`."""
    return {
        k: jax.device_put(v, NamedSharding(mesh, _BATCH_SPECS[k]))
        for k, v in batch.items()
    }


def demo_batch(n_docs=8, n_changes=4, n_actors=4, n_regs=8, n_elems=8,
               n_list_ops=8):
    """A tiny synthetic-but-consistent workload for compile checks and the
    sharded-vs-unsharded differential test.

    Per doc: n_changes causally-chained changes round-robin over actors;
    one register group with n_regs sequential writers; one list object whose
    n_elems elements form an insertion chain, each made visible by one op."""
    D, C, A, T, L, To = (n_docs, n_changes, n_actors, n_regs, n_elems,
                         n_list_ops)
    rng = np.random.RandomState(0)

    clock = np.zeros((D, A), np.int32)
    ch_actor = np.tile(np.arange(C, dtype=np.int32) % A, (D, 1))
    ch_seq = np.tile((np.arange(C, dtype=np.int32) // A) + 1, (D, 1))
    ch_deps = np.zeros((D, C, A), np.int32)
    for i in range(1, C):
        # each change depends on the previous one in round-robin order
        ch_deps[:, i, (i - 1) % A] = ((i - 1) // A) + 1
    ch_valid = np.ones((D, C), bool)

    rg = np.tile((np.arange(T, dtype=np.int32) % 2), (D, 1))
    rt = np.tile(np.arange(T, dtype=np.int32), (D, 1))
    ra = rng.randint(0, A, size=(D, T)).astype(np.int32)
    rs = np.ones((D, T), np.int32)
    rc = np.zeros((D, T, A), np.int32)
    for t in range(1, T):
        rc[:, t] = rc[:, t - 1]
        np.put_along_axis(rc[:, t], ra[:, t - 1][:, None],
                          rs[:, t - 1][:, None], axis=1)
    rd = np.zeros((D, T), bool)

    eo = np.zeros((D, L), np.int32)
    ep = np.tile(np.arange(-1, L - 1, dtype=np.int32), (D, 1))
    ec = np.tile(np.arange(1, L + 1, dtype=np.int32), (D, 1))
    ea = rng.randint(0, A, size=(D, L)).astype(np.int32)
    ev = np.ones((D, L), bool)

    vis0 = np.zeros((D, L), np.float32)
    op_elem = np.tile(np.arange(To, dtype=np.int32) % L, (D, 1))
    # each list op points at a register row; its visibility delta derives
    # from the register kernel outputs on device (the fused-path rule)
    op_row = np.tile(np.arange(To, dtype=np.int32) % T, (D, 1))
    op_valid = np.ones((D, To), bool)

    return {
        'clock': clock, 'ch_actor': ch_actor, 'ch_seq': ch_seq,
        'ch_deps': ch_deps, 'ch_valid': ch_valid,
        'rg': rg, 'rt': rt, 'ra': ra, 'rs': rs, 'rc': rc, 'rd': rd,
        'eo': eo, 'ep': ep, 'ec': ec, 'ea': ea, 'ev': ev,
        'vis0': vis0, 'op_elem': op_elem, 'op_row': op_row,
        'op_valid': op_valid,
    }
