"""Runs the reference over the sampled docs in spawned worker processes
(pure Python, no JAX), once the program's state is freed, and sums what
each doc's comparison counted."""

import os

from .children import context


def run(worker, jobs):
    """Sums the ``{number: count}`` dicts `worker(job)` returns."""
    procs = max(1, min(8, (os.cpu_count() or 2) // 2, len(jobs)))
    totals = {}
    with context().Pool(procs) as pool:
        for got in pool.imap_unordered(worker, jobs):
            for k, v in got.items():
                totals[k] = totals.get(k, 0) + v
    return totals
