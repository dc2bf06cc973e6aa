"""Pool / C++ host: `shard.run` seconds (the dispatcher's wall wait for
every chip of a doc-sharded pool: phase a on each chip's thread, the
shared collect, each chip's emit) in the window per million ops it
completed (program span, traced run).  Nothing to read where the pool
is not sharded."""


def read(ctx):
    s = ctx['program']['spans'].get('shard.run')
    if s is None or not ctx['client']['ops_done']:
        return None
    return s['s'] / (ctx['client']['ops_done'] / 1e6)
