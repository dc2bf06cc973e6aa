"""Pool / C++ host: counter `mesh.encode_shard_skew_s` (per batch, the
slowest chip's phase a, C++ decode/begin and dispatch, less the
fastest's) in the window per million ops it completed (program
counter).  Nothing to read where the program has no mesh."""


def read(ctx):
    s = ctx['program']['counters'].get('mesh.encode_shard_skew_s')
    if s is None or not ctx['client']['ops_done']:
        return None
    return s / (ctx['client']['ops_done'] / 1e6)
