"""Serving: `gateway.decode` seconds in the window per million ops it
completed (program spans, traced run): each reader thread decoding a
frame into a request and admitting it, concurrently with the flush.
Nothing to read where the program has no such span."""


def read(ctx):
    s = ctx['program']['spans'].get('gateway.decode')
    if s is None or not ctx['client']['ops_done']:
        return None
    return s['s'] / (ctx['client']['ops_done'] / 1e6)
