"""Device: 1 - (union of device op intervals) / traced window, from the
profiler trace of the window, in percent."""


def read(ctx):
    tr = ctx['trace']
    if tr is None or tr['span_s'] <= 0:
        return None
    return 100.0 * (1.0 - tr['busy_s'] / tr['span_s'])
