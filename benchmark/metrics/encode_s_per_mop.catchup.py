"""Serving: `gateway.encode` seconds in the window per million ops it
completed (program spans, traced run): each response's msgpack encode
and framing.  Nothing to read where the program has no such span."""


def read(ctx):
    s = ctx['program']['spans'].get('gateway.encode')
    if s is None or not ctx['client']['ops_done']:
        return None
    return s['s'] / (ctx['client']['ops_done'] / 1e6)
