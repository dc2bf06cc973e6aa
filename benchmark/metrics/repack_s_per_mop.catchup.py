"""Serving: `pool.repack` seconds in the window per million ops it
completed (program spans, traced run): the flush's dict <-> msgpack
re-pack on either side of the pool call, and the op count.  Nothing to
read where the program has no such span."""


def read(ctx):
    s = ctx['program']['spans'].get('pool.repack')
    if s is None or not ctx['client']['ops_done']:
        return None
    return s['s'] / (ctx['client']['ops_done'] / 1e6)
