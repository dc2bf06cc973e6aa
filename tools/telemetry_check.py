"""Disabled-path overhead gate for the telemetry layer.

The observability contract (ISSUE 1 / docs/OBSERVABILITY.md) is that an
idle telemetry layer is FREE: with tracing disabled, the instrumented
pipeline must run within noise of an un-instrumented one.  The
un-instrumented binary no longer exists, so this harness reconstructs
it in-process: every telemetry entry point the hot path touches
(trace.span/count/add/metric, telemetry.observe_batch, the always-on
counters) is monkeypatched to a bare no-op, which is the
closest executable stand-in for deleting the call sites.

Protocol: one warmup, then PAIRS interleaved (raw, disabled) runs of
the quickbench workload on fresh pools -- interleaving is the only
honest A/B on this single-core host (runs drift +-15% between windows;
see tools/quickbench.py).  MINIMA compare (the minimum of N identical
runs is the least-contended sample, the robust statistic for a shared
host); the target is ~2% overhead, the assert threshold defaults to 6%
to absorb residual jitter (AMTPU_TCHECK_TOL overrides).  The gate takes
the MEDIAN of AMTPU_TCHECK_TRIALS (default 5) independent overhead
estimates, so one unlucky scheduling window cannot fail it alone, and
accepts a clean best-trial (<= TOL/2) even when the median is over --
a real regression inflates every window, host contention does not
deflate one (ISSUE 8 deflake).  A final enabled-path pass sanity-checks
that tracing actually records (an accidentally dead telemetry layer
must not pass the overhead gate by being dead).

Run via `make telemetry-check`, or directly:
    JAX_PLATFORMS=cpu AMTPU_BENCH_DOCS=256 python tools/telemetry_check.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# small-but-real default workload (env overrides win)
os.environ.setdefault('AMTPU_BENCH_DOCS', '256')
os.environ.setdefault('AMTPU_BENCH_ORACLE_DOCS', '1')

import msgpack  # noqa: E402

from automerge_tpu import telemetry, trace  # noqa: E402
from automerge_tpu.native import NativeDocPool, ShardedNativePool  # noqa: E402
from automerge_tpu.telemetry import attribution, capacity, recorder  # noqa: E402
from automerge_tpu.telemetry.spans import NULL_SPAN  # noqa: E402

PAIRS = int(os.environ.get('AMTPU_TCHECK_PAIRS', 5))
TOL = float(os.environ.get('AMTPU_TCHECK_TOL', 0.06))
TRIALS = int(os.environ.get('AMTPU_TCHECK_TRIALS', 5))


def _noop(*args, **kwargs):
    return None


def _null_span(*args, **kwargs):
    return NULL_SPAN


_PATCHES = [
    (trace, 'span', _null_span), (trace, 'count', _noop),
    (trace, 'add', _noop), (trace, 'metric', _noop),
    (telemetry, 'span', _null_span),
    (telemetry, 'observe_batch', _noop),
    (telemetry, 'metric', _noop),
    # the always-on recorder/attribution seams (ISSUE 12): the raw arm
    # must approximate deleting them too, so the gate prices their
    # disabled-path cost honestly
    (recorder, 'record', _noop),
    (attribution, 'note_flush_phase', _noop),
    # the always-on capacity seams (ISSUE 15): per-doc fan-out/egress
    # attribution is priced against the same bar as the recorder
    (capacity, 'note_fanout', _noop),
    (capacity, 'note_egress', _noop),
    # the wire-trace stamping seam (ISSUE 16): SidecarClient consults
    # the ambient span context on EVERY outbound request, so the raw
    # arm prices that lookup alongside the other always-on hooks
    (telemetry, 'current_trace_context', _noop),
]


class raw_mode(object):
    """Context manager approximating the un-instrumented pipeline."""

    def __enter__(self):
        self._saved = [(m, n, getattr(m, n)) for m, n, _ in _PATCHES]
        for m, n, f in _PATCHES:
            setattr(m, n, f)

    def __exit__(self, *exc):
        for m, n, f in self._saved:
            setattr(m, n, f)
        return False


def main():
    import random

    import bench
    rng = random.Random(int(os.environ.get('AMTPU_BENCH_SEED', 7)))
    config = int(os.environ.get('AMTPU_TCHECK_CONFIG', 3))
    batch, metric = bench.BUILDERS[config](rng)
    total_ops = sum(len(c['ops']) for chs in batch.values() for c in chs)
    keyed = {NativeDocPool._doc_key(d): chs for d, chs in batch.items()}
    payload = msgpack.packb(keyed, use_bin_type=True)
    print('telemetry-check: config %d, %d docs, %d ops'
          % (config, len(batch), total_ops), file=sys.stderr)

    def make_pool():
        n = int(os.environ.get('AMTPU_BENCH_SHARDS', 0)) or \
            ShardedNativePool.default_shards()
        n = min(n, len(batch))
        return ShardedNativePool(n) if n > 1 else NativeDocPool()

    def run_once():
        pool = make_pool()
        t0 = time.perf_counter()
        pool.apply_batch_bytes(payload)
        return time.perf_counter() - t0

    telemetry.disable()
    run_once()                      # warmup: jit compiles, allocator heat
    # median-of-TRIALS overhead estimates (each from its own interleaved
    # minima): one unlucky scheduling window can no longer fail the gate
    # on its own -- the jitter this deflakes is documented at +-15%
    # between windows on this host
    overheads = []
    for t in range(TRIALS):
        raw_times, dis_times = [], []
        for _ in range(PAIRS):
            with raw_mode():
                raw_times.append(run_once())
            dis_times.append(run_once())
        raw_best = min(raw_times)
        dis_best = min(dis_times)
        overheads.append((dis_best - raw_best) / raw_best)
        print('trial %d: raw %s | disabled %s -> %.2f%%'
              % (t, ['%.3f' % x for x in raw_times],
                 ['%.3f' % x for x in dis_times], 100 * overheads[-1]),
              file=sys.stderr)
    overhead = sorted(overheads)[len(overheads) // 2]
    print('telemetry-check: disabled-path overhead %.2f%% '
          '(median of %d trials %s; tolerance %.0f%%)'
          % (100 * overhead, TRIALS,
             ['%.1f%%' % (100 * o) for o in sorted(overheads)],
             100 * TOL))

    # enabled-path sanity: tracing must actually record when on
    telemetry.reset_all()
    telemetry.enable()
    try:
        run_once()
        snap = telemetry.phase_snapshot()
        assert snap, 'enabled tracing recorded no phases'
        assert telemetry.metrics_snapshot() is not None
        block = telemetry.bench_block()
        assert block['batch_latency'], 'no batch latency recorded'
    finally:
        telemetry.disable()
    print('telemetry-check: enabled-path sanity ok (%d phases)'
          % len(snap), file=sys.stderr)

    # Acceptance (deflaked, ISSUE 8): the gate measures the DISABLED
    # telemetry layer, whose true overhead is ~0-2% -- a failure mode is
    # "every interleaved window this run was contended", not "the layer
    # got slow".  So fail only when the median exceeds tolerance AND no
    # single trial came in clean (<= TOL/2): a real regression inflates
    # every trial including the least-contended one, while host jitter
    # cannot suppress a genuine +6% in all five windows at once.
    clean_min = min(overheads)
    if overhead > TOL and clean_min > TOL / 2:
        print('telemetry-check: FAIL -- disabled path is %.1f%% slower '
              'than the no-op pipeline (tolerance %.0f%%; best trial '
              '%.1f%%)' % (100 * overhead, 100 * TOL, 100 * clean_min))
        return 1
    if overhead > TOL:
        print('telemetry-check: PASS (median %.1f%% is over tolerance '
              'but the best trial measured %.1f%% -- host contention, '
              'not instrument cost)' % (100 * overhead, 100 * clean_min))
        return 0
    print('telemetry-check: PASS')
    return 0


if __name__ == '__main__':
    sys.exit(main())
