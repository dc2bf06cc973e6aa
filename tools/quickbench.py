"""Fast perf-iteration harness for the host pipeline.

Builds ONE BASELINE-config workload (default: config 3, the headline
shape), then loops fresh-pool `apply_batch_bytes` runs and prints wall
times + the phase split.  Intended for tight optimize-measure loops on
the HOST phases; run with JAX_PLATFORMS=cpu when the TPU link is down --
host-phase timings are device-independent.

The single-core host jitters +-15% between windows: for honest A/B
comparisons interleave runs of both binaries (swap the built .so), or
compare the thread-CPU cxx.* spans (tracing on), which are immune
to wall-clock contention.

Tracing is toggled at RUNTIME (telemetry.enable(); no more AMTPU_TRACE
env mutation before import); --no-trace measures the production
disabled path.  The final stdout line is BENCH JSON embedding
`telemetry.bench_block()` (fallback rates, device seconds, batch
histograms).  `make telemetry-check` gates the disabled-path overhead
of the same workload (tools/telemetry_check.py).

Usage:  [JAX_PLATFORMS=cpu] python tools/quickbench.py \
            [--config N] [--runs K] [--no-trace]
Env:    the same AMTPU_BENCH_* knobs bench.py reads.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import msgpack  # noqa: E402

from automerge_tpu import telemetry  # noqa: E402
from automerge_tpu.native import NativeDocPool, ShardedNativePool  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--config', type=int, default=3, choices=[1, 2, 3, 4])
    ap.add_argument('--runs', type=int, default=5)
    ap.add_argument('--no-trace', action='store_true',
                    help='leave span tracing disabled (measures the '
                         'production path; always-on counters still '
                         'accumulate)')
    ap.add_argument('--phases', type=int, nargs='?', const=10, default=0,
                    metavar='N',
                    help='print a top-N phase table (seconds + share of '
                         'native batch time) from the embedded telemetry '
                         'block -- collect regressions readable without '
                         'jq (default N=10; implies tracing)')
    args = ap.parse_args()
    if args.runs < 1:
        ap.error('--runs must be >= 1')
    if args.phases and args.no_trace:
        ap.error('--phases needs tracing; drop --no-trace')
    if not args.no_trace:
        telemetry.enable()

    import random

    import bench
    rng = random.Random(int(os.environ.get('AMTPU_BENCH_SEED', 7)))
    t0 = time.perf_counter()
    batch, metric = bench.BUILDERS[args.config](rng)
    total_ops = sum(len(c['ops']) for chs in batch.values() for c in chs)
    keyed = {NativeDocPool._doc_key(d): chs for d, chs in batch.items()}
    payload = msgpack.packb(keyed, use_bin_type=True)
    print('config %d (%s): %d docs, %d ops, payload %.1f MB (built %.1fs)'
          % (args.config, metric, len(batch), total_ops,
             len(payload) / 1e6, time.perf_counter() - t0),
          file=sys.stderr)

    def make_pool():
        n = int(os.environ.get('AMTPU_BENCH_SHARDS', 0)) or \
            ShardedNativePool.default_shards()
        n = min(n, len(batch))
        return ShardedNativePool(n) if n > 1 else NativeDocPool()

    t0 = time.perf_counter()
    make_pool().apply_batch_bytes(payload)
    print('warmup: %.2fs' % (time.perf_counter() - t0), file=sys.stderr)

    # ONE measurement window for the whole embed: warmup's compiles are
    # excluded, then histograms, counters, AND phases all cover exactly
    # the timed runs (mixed windows would skew any phase-per-batch math)
    telemetry.reset_all()
    times = []
    for _ in range(args.runs):
        pool = make_pool()
        t0 = time.perf_counter()
        pool.apply_batch_bytes(payload)
        times.append(time.perf_counter() - t0)
    med = sorted(times)[len(times) // 2]
    print('runs: %s -> best %.0f ops/s, median %.0f ops/s'
          % (['%.3f' % t for t in times], total_ops / min(times),
             total_ops / med), file=sys.stderr)
    if telemetry.enabled():
        print(telemetry.phase_report(), file=sys.stderr)
    block = telemetry.bench_block()
    if args.phases:
        print(phase_table(block, args.phases), file=sys.stderr)
    print(json.dumps({'metric': 'quickbench_%s' % metric,
                      'value': round(total_ops / med, 1),
                      'unit': 'ops/sec', 'config': args.config,
                      'telemetry': block}))


def phase_table(block, top_n):
    """Top-N phase table from a bench_block: seconds + share of the
    summed per-shard native batch time (shares can exceed 100% only if
    a span double-counts; collect share is THE regression gauge --
    ISSUE 3 tracks it below 50%).  Note: with async dispatch,
    device.collect includes the kernel compute it blocks on."""
    phases = block.get('phases') or {}
    lat = block.get('batch_latency', {})
    # pipeline mode drives _phase_a/b directly, so only the whole-batch
    # 'sharded' series exists -- fall back to it for the share basis
    native_s = (lat.get('native', {}).get('sum', 0.0)
                or lat.get('sharded', {}).get('sum', 0.0))
    rows = sorted(((v['s'], v['n'], k) for k, v in phases.items()
                   if v['s'] > 0), reverse=True)[:top_n]
    if not rows:
        return 'phase table: no phase occupancy recorded'
    width = max(len(k) for _s, _n, k in rows)
    out = ['top %d phases (of %.2fs native batch time):'
           % (len(rows), native_s)]
    for s, n, k in rows:
        share = (' %5.1f%%' % (100.0 * s / native_s)) if native_s else ''
        out.append('  %-*s %8.3fs%s  x%d' % (width, k, s, share, n))
    return '\n'.join(out)


if __name__ == '__main__':
    main()
