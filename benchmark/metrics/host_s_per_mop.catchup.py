"""Pool / C++ host: `host.begin` + `host.mid` + `host.finish` seconds
in the window per million ops it completed (program spans, traced
run)."""


def read(ctx):
    spans = ctx['program']['spans']
    names = ('host.begin', 'host.mid', 'host.finish')
    if not any(n in spans for n in names) or not ctx['client']['ops_done']:
        return None
    s = sum(spans[n]['s'] for n in names if n in spans)
    return s / (ctx['client']['ops_done'] / 1e6)
