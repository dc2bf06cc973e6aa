#!/usr/bin/env python3
"""The benchmark: one run of one cell of `BENCHMARK.json`.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from the root of a checkout, on a machine that holds the chips
the cell asks for; without them it exits non-zero and prints no result.
The traffic, the window, the reference check and the metrics are all
found by the names in `BENCHMARK.json` (see `harness/spec.py`).  The last
line of stdout is the result: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), `device`, with ``--trace 1`` a `breakdown` of the
device trace, and last `compared`: each number the check compared with
its limit.  The same numbers close stderr.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--fault', default=None,
                    help='plant a fault under the timed path (the control '
                         'and the fault checks; never in a measured run)')
    args = ap.parse_args(argv)
    # JAX's persistent compile cache lives inside the checkout, at a
    # fixed path (the path is part of what a later run must find again);
    # JAX reads this when it is imported, and the program's
    # `enable_compile_cache` keeps to it
    os.environ['JAX_COMPILATION_CACHE_DIR'] = os.path.join(ROOT,
                                                           '.jax_cache')
    sys.path.insert(0, HERE)
    sys.path.insert(1, ROOT)
    from harness import device, runner, spec
    try:
        cell = spec.load(ROOT, args.workload)
    except (spec.SpecError, OSError, KeyError, ValueError) as e:
        print('benchmark: %s' % e, file=sys.stderr)
        return 2
    try:
        result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                            T_START, fault=args.fault)
    except device.NoAccelerator as e:
        print(e, file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
