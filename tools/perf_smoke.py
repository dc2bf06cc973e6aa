"""CI gate: the packed member epilogue must be ACTIVE on the table
workload's kernel path (ISSUE 3), the collect wall must stay down and
the pool-resident batch state must actually serve (ISSUE 6).

Part A runs the config-4 shape through tools/quickbench.py with the
kernel path forced (AMTPU_HOST_FULL=0) and fails if

  * `fallback.oracle` is nonzero -- a register group fell past every
    escalation tier back to the host oracle, or
  * `collect.packed_member_batches` is zero -- the member-mode batches
    stopped taking the packed epilogue (ONE i32 per register row +
    sparse CSR conflicts), or
  * `collect.full_matrix_readback` is nonzero -- some batch read back
    the full winner/conflicts/alive/overflow matrices, the pre-packed
    transfer wall this gate exists to keep dead, or
  * `device.collect` share of summed native batch time >= 40% -- the
    per-batch upload/collect round-trip ISSUE 6 removed is creeping
    back (shares come from the phases block quickbench embeds).

Part B drives a steady-state pool IN-PROCESS: the config-4 changes
split into two causally-ordered halves applied to ONE pool, so the
second batch runs against mirrors and pool-resident clock rows the
first batch persisted.  It fails unless `resident.batch_hits` (the
device clock table survived across batches: delta-upload or no-op) and
`resident.batch_hit_rows` (C++ rows served from persisted entries) are
both nonzero -- a silently dead resident cache must not pass.

Wired into `make check` as `make perf-smoke` (next to fallback-check,
which gates the escalation ladder itself on the same shape).

Usage: [JAX_PLATFORMS=cpu] python tools/perf_smoke.py
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

COLLECT_SHARE_MAX = float(os.environ.get('AMTPU_SMOKE_COLLECT_SHARE',
                                         0.40))


def quickbench_gates():
    env = dict(os.environ)
    env.setdefault('JAX_PLATFORMS', 'cpu')
    env['AMTPU_HOST_FULL'] = '0'            # the kernel path IS the subject
    env.pop('AMTPU_PACKED_EPILOGUE', None)  # gate the DEFAULT epilogue
    # same deterministic shape as fallback-check: member mode engages and
    # the dup-assign groups escalate, so the packed epilogue (not the
    # fused path) is what actually serves the batches
    env.setdefault('AMTPU_BENCH_C4_DOCS', '256')
    env.setdefault('AMTPU_BENCH_SHARDS', '8')
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, 'quickbench.py'),
         '--config', '4', '--runs', '1'],
        env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        print('perf-smoke: quickbench failed (rc=%d)' % proc.returncode,
              file=sys.stderr)
        return 1
    line = proc.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    tel = result.get('telemetry', {})
    fallbacks = tel.get('fallbacks', {})
    collect = tel.get('collect', {})
    phases = tel.get('phases', {})
    from automerge_tpu import telemetry
    share, collect_s, basis = telemetry.collect_share(tel)

    problems = []
    if fallbacks.get('oracle', -1) != 0:
        problems.append('fallback.oracle = %s (want 0)'
                        % fallbacks.get('oracle'))
    if collect.get('packed_member_batches', 0) <= 0:
        problems.append('collect.packed_member_batches missing/zero -- '
                        'the packed member epilogue did not engage')
    if collect.get('full_matrix_readback', 0) != 0:
        problems.append('collect.full_matrix_readback = %s (want 0) -- '
                        'a batch read back the full register matrices'
                        % collect.get('full_matrix_readback'))
    if not basis or not phases:
        problems.append('no phases/batch-latency block in the BENCH '
                        'line -- collect share is unattributable')
    elif share >= COLLECT_SHARE_MAX:
        problems.append('device.collect share %.1f%% >= %.0f%% of summed '
                        'batch time (%.3fs of %.3fs) -- the per-batch '
                        'collect round-trip is creeping back'
                        % (100 * share, 100 * COLLECT_SHARE_MAX,
                           collect_s, basis))
    if problems:
        print('perf-smoke FAILED:', file=sys.stderr)
        for p in problems:
            print('  * ' + p, file=sys.stderr)
        print('  telemetry.collect = %s' % json.dumps(collect),
              file=sys.stderr)
        print('  telemetry.fallbacks = %s' % json.dumps(fallbacks),
              file=sys.stderr)
        return 1
    print('perf-smoke: packed epilogue on %d member batches, '
          'full-matrix readbacks 0, oracle 0, collect share %.1f%%, '
          '%.0f ops/s'
          % (collect['packed_member_batches'], 100 * share,
             result.get('value', 0.0)))
    return 0


def resident_hit_gate():
    """Steady-state resident gate, in-process (the env must bind BEFORE
    jax/the pool library initialize, which is why this runs after main()
    set it).  Two causally-ordered halves of the config-4 changes hit
    ONE pool: batch 2 must be served by state batch 1 persisted.

    Wave pipelining is pinned OFF: intra-call waves hit each other's
    just-appended rows, which would satisfy the counters even if the
    cache were wiped between apply calls -- the exact regression this
    gate exists to catch."""
    os.environ['AMTPU_PIPELINE_DEPTH'] = '1'
    import random

    import msgpack

    import bench
    from automerge_tpu import telemetry, trace
    from automerge_tpu.native import NativeDocPool

    rng = random.Random(int(os.environ.get('AMTPU_BENCH_SEED', 7)))
    batch, _metric = bench.BUILDERS[4](rng)
    keyed = {NativeDocPool._doc_key(d): chs for d, chs in batch.items()}
    # split "all but each doc's causally-last change" -> "the last
    # change": batch 2 then reuses batch 1's actor population (a NEW
    # actor would bump the resident generation and legitimately force a
    # full re-upload -- steady-state serving is the stable-actor case
    # this gate pins)
    halves = [
        msgpack.packb({d: chs[:-1] for d, chs in keyed.items()
                       if len(chs) > 1}, use_bin_type=True),
        msgpack.packb({d: chs[-1:] for d, chs in keyed.items()},
                      use_bin_type=True),
    ]
    pool = NativeDocPool()
    telemetry.enable()
    try:
        for payload in halves:
            pool.apply_batch_bytes(payload)
        m = trace.metrics_snapshot()
    finally:
        telemetry.disable()
    hits = int(m.get('resident.batch_hits', 0))
    hit_rows = int(m.get('resident.batch_hit_rows', 0))
    if hits <= 0 or hit_rows <= 0:
        print('perf-smoke FAILED:', file=sys.stderr)
        print('  * resident.batch_hits=%d batch_hit_rows=%d (want both '
              '> 0) -- the pool-resident clock table did not survive '
              'across batches' % (hits, hit_rows), file=sys.stderr)
        print('  resident.* = %s' % json.dumps(
            {k: v for k, v in sorted(m.items())
             if k.startswith('resident.')}), file=sys.stderr)
        return 1
    print('perf-smoke: resident batch state served across batches '
          '(batch_hits=%d, hit_rows=%d)' % (hits, hit_rows))
    return 0


def main():
    rc = quickbench_gates()
    if rc:
        return rc
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    os.environ['AMTPU_HOST_FULL'] = '0'
    os.environ.setdefault('AMTPU_BENCH_C4_DOCS', '64')
    return resident_hit_gate()


if __name__ == '__main__':
    sys.exit(main())
