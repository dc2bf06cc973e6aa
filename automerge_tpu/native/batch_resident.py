"""Device-resident POOL state for the batch register path (ISSUE 6
tentpole a).

`resident.py` keeps a single big list object's arena columns on device
between batches; this module does the same for the state every *batch*
re-stages through the register/clock path -- starting with the pool-
resident clock table that `native/core.cpp` persists across batches
(struct ResClock): densified all_deps rows keyed (doc, actor, seq) are
immutable once their change is applied, so the device copy only ever
needs the rows appended since the last batch.

Consistency rides the C++ generation counter:

* (gen, Ap) unchanged and n_rows grew  ->  delta-upload rows
  [cached_n, n_rows) with one scatter (pow2-bucketed index padding, the
  `resident.py` pattern);
* gen bumped (rollback, new actor registered, row-cap restart) or Ap
  grown (actor capacity)  ->  full re-upload at the new pow2 capacity;
* n_rows outgrew the pow2 row capacity with gen/Ap unchanged  ->  the
  table grows ON DEVICE (device-to-device copy into the next bucket)
  and the batch still delta-uploads only its appended rows;
* n_rows unchanged  ->  no upload at all: the steady-state batch whose
  changes all dedup into persisted rows pays ZERO clock traffic
  (`resident.batch_hits`).

The device table is handed to the register kernels in place of the
batch-local `amtpu_col_clocktab` view; batch `clock_idx` columns
reference pool-global rows, so the kernel maths is unchanged and byte
parity with the non-resident path holds by construction (pinned by the
A/B lanes in tests/test_resident.py and the adversarial fuzz suite).
"""

import ctypes
from functools import lru_cache

import numpy as np

from .. import trace
from ..analysis import sanitize
from .resident import _bucket_pow2


def clock_table_scatter(tab, idx, rows):
    """The clock table's delta rows: pad slots carry idx == capacity (out
    of bounds) and drop.  Jitted, its XLA module is
    `jit_clock_table_scatter`."""
    return tab.at[idx].set(rows, mode='drop')


@lru_cache(maxsize=None)
def _jit_row_scatter(donate):
    import jax

    if donate:
        # accelerators: reuse the prior table's device buffer for the
        # output instead of allocating per delta (donate_argnums is
        # proven on the tier staging path, ops/registers.py); on CPU
        # "transfers" are memcpys and donation buys nothing
        jitted = jax.jit(clock_table_scatter, donate_argnums=(0,))
    else:
        jitted = jax.jit(clock_table_scatter)

    def dispatch(tab, idx, rows):
        # jax zero-copies 64B-aligned numpy inputs on CPU and even
        # jnp.array's "copy" can be deferred past dispatch (measured on
        # jax 0.4.37: mutating the source after dispatch corrupts the
        # in-flight scatter -- the PR-4 alias class).  Hand the
        # computation PRIVATE synchronous host copies instead: jax may
        # alias them freely because no caller ever sees them, so the
        # staging arrays are reusable the moment dispatch returns.
        trace.metric('transfer.h2d_bytes', idx.nbytes + rows.nbytes)
        out = jitted(tab, np.array(idx), np.array(rows))
        # AMTPU_SANITIZE=1: poison the caller-visible staging arrays the
        # moment dispatch returns -- if the private-copy contract above
        # ever regresses (jax aliasing idx/rows), the in-flight scatter
        # reads sentinel garbage and the parity lanes fail loudly
        # instead of shipping silent corruption (docs/ANALYSIS.md)
        sanitize.poison(idx, rows)
        return out
    return dispatch


class PoolClockCache:
    """Device-resident copy of one pool's ResClock table."""

    __slots__ = ('tab', 'gen', 'n', 'ap', 'cap')

    def __init__(self):
        self.tab = None
        self.gen = -1
        self.n = 0
        self.ap = 0
        self.cap = 0

    def table(self, L, pool, donate_ok=True):
        """Returns the device clock table [cap, Ap] covering the pool's
        current rows, uploading as little as the generation contract
        allows.  Call once per batch, AFTER begin (the batch's rows are
        appended by then).

        `donate_ok=False` disables buffer donation on the delta scatter:
        the wave-pipelined driver hands the PREVIOUS table version to a
        batch whose kernels are still in flight when the next wave's
        delta runs, so donating would recycle a buffer an enqueued
        computation may still read.  The staging is the `device.upload`
        span."""
        info = (ctypes.c_int64 * 4)()
        L.amtpu_resclk_info(pool, info)
        n, ap, gen = int(info[0]), int(info[1]), int(info[2])
        with trace.span('device.upload'):
            self._sync(L, pool, n, ap, gen, donate_ok)
        return self.tab

    def _sync(self, L, pool, n, ap, gen, donate_ok):
        """Brings `self.tab` to the pool's (n, ap, gen) state."""
        import jax
        import jax.numpy as jnp

        need_full = (self.tab is None or gen != self.gen
                     or ap != self.ap or n < self.n)
        if not need_full and n > self.cap:
            # capacity growth WITHOUT invalidation: the persisted rows
            # are already on device, so grow there (device-to-device
            # copy into the next pow2 bucket) instead of re-staging the
            # whole table from host -- the steady-state cost of crossing
            # a pow2 boundary is one device copy, not O(n) host traffic
            cap = _bucket_pow2(n, floor=64)
            self.tab = jnp.zeros((cap, max(ap, 1)),
                                 self.tab.dtype).at[:self.cap].set(self.tab)
            self.cap = cap
            trace.metric('resident.batch_grow_uploads')
        if need_full:
            if gen != self.gen and self.tab is not None:
                trace.metric('resident.batch_gen_invalidation')
            cap = _bucket_pow2(max(n, 1), floor=64)
            host = np.zeros((cap, max(ap, 1)), np.int32)
            if n:
                src = np.ctypeslib.as_array(L.amtpu_resclk_tab(pool),
                                            shape=(n, ap))
                host[:n] = src
            self.tab = jnp.asarray(host)
            trace.metric('transfer.h2d_bytes', host.nbytes)
            trace.metric('resident.batch_full_uploads')
            trace.metric('resident.batch_full_upload_rows', n)
            self.cap = cap
        elif n > self.n:
            k = n - self.n
            kp = _bucket_pow2(k, floor=16)
            idx = np.full(kp, self.cap, np.int32)    # cap = dropped
            idx[:k] = np.arange(self.n, n, dtype=np.int32)
            rows = np.zeros((kp, ap), np.int32)
            src = np.ctypeslib.as_array(L.amtpu_resclk_tab(pool),
                                        shape=(n, ap))
            rows[:k] = src[self.n:n]
            donate = donate_ok and jax.default_backend() != 'cpu'
            self.tab = _jit_row_scatter(donate)(self.tab, idx, rows)
            trace.metric('resident.batch_hits')
            trace.metric('resident.batch_delta_rows', k)
        else:
            # every clock row of this batch was already resident
            trace.metric('resident.batch_hits')
            trace.metric('resident.batch_noop')
        self.gen, self.n, self.ap = gen, n, ap

    def drop_if_disabled(self, L, pool):
        """Release the device table once C++ permanently disabled the
        pool's resident cache (actor population past
        AMTPU_RESCLK_MAX_ACTORS): the buffer can be pool-lifetime large
        (up to row-cap x Ap x 4 bytes) and will never be read again."""
        if self.tab is None:
            return
        info = (ctypes.c_int64 * 4)()
        L.amtpu_resclk_info(pool, info)
        if int(info[3]):
            self.tab = None
            self.gen = -1
            self.n = self.ap = self.cap = 0
            trace.metric('resident.batch_cache_dropped')
