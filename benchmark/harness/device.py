"""The device a run is on, its published peaks, and its memory peak."""

import json
import os

from .spec import HERE


class NoAccelerator(Exception):
    pass


def require(chips, allow_cpu=False):
    """{platform, kind, count} of the chips this process holds.  Anything
    but a TPU, or fewer chips than the cell asks for, ends the run with a
    non-zero exit and no result; `allow_cpu` is for the benchmark's own
    tests, which drive the rest of a run on the CPU."""
    import jax
    devs = jax.devices()
    info = {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs)}
    if info['platform'] != 'tpu' and not allow_cpu:
        raise NoAccelerator('benchmark: platform is %r, not tpu; no result'
                            % info['platform'])
    if info['count'] < chips:
        raise NoAccelerator('benchmark: %d chips, the cell needs %d; no '
                            'result' % (info['count'], chips))
    return info


def peaks(kind):
    """Published peaks of one chip of `kind`; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, 'peaks.json')) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError('no peaks for device kind %r in peaks.json' % kind)
    return table[kind]


def memory_peak_bytes(count):
    """peak_bytes_in_use on the fullest of the first `count` chips, or
    None where the backend keeps no such statistic."""
    import jax
    peaks_ = []
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        if 'peak_bytes_in_use' in stats:
            peaks_.append(int(stats['peak_bytes_in_use']))
    return max(peaks_) if peaks_ else None
