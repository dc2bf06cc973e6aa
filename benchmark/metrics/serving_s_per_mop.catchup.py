"""Serving: `scheduler.flush` span seconds minus the pool's own batch
seconds (`telemetry.observe_batch('native')`), per million ops the
window completed (program spans, traced run)."""


def read(ctx):
    prog = ctx['program']
    flush = prog['spans'].get('scheduler.flush')
    if flush is None or not ctx['client']['ops_done']:
        return None
    return (flush['s'] - prog['pool_batch']['s']) \
        / (ctx['client']['ops_done'] / 1e6)
