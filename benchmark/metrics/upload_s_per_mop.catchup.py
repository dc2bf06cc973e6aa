"""Dispatch: `device.upload` seconds in the window per million ops it
completed (program spans, traced run): explicit host-to-device staging,
the pool-resident clock table's sync and the resident arena's uploads.
Nothing to read where the program has no such span."""


def read(ctx):
    s = ctx['program']['spans'].get('device.upload')
    if s is None or not ctx['client']['ops_done']:
        return None
    return s['s'] / (ctx['client']['ops_done'] / 1e6)
