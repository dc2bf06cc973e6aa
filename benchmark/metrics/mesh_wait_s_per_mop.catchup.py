"""Dispatch: counter `mesh.collective_wait_s` (chip-thread seconds
blocked on a chip's device outputs while no chip's were ready) in the
window per million ops it completed (program counter).  A mesh whose
chips never waited counts nothing and reads 0 (its `mesh.shards` counted
in the window); nothing to read where the program has no mesh."""


def read(ctx):
    counters = ctx['program']['counters']
    if 'mesh.shards' not in counters or not ctx['client']['ops_done']:
        return None
    s = counters.get('mesh.collective_wait_s', 0.0)
    return s / (ctx['client']['ops_done'] / 1e6)
