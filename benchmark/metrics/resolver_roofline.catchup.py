"""Kernels: the least time the window's acknowledged ops need at the
chip's peak HBM bandwidth (`harness/roofline.py`), over the device time
of the resolver's XLA modules in the trace, in percent.  Nothing to read
where the trace holds no resolver module."""

from harness import device, roofline

#: substrings of the resolver's jitted programs' module names
RESOLVER = ('resolve', 'register', 'linearize', 'list_rank', 'rank',
            'dominance', 'dominate', 'merge_packed', 'escalat', 'resident')


def read(ctx):
    tr = ctx['trace']
    if tr is None:
        return None
    dev_s = sum(s for name, s in tr['modules'].items()
                if any(p in name for p in RESOLVER))
    if dev_s <= 0:
        return None
    peak = device.peaks(ctx['device']['kind'])['hbm_bytes_per_s']
    least = roofline.resolver_bytes(ctx['client']['op_counts']) / peak
    return 100.0 * least / dev_s
