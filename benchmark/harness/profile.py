"""The device trace of a window: JAX's profiler around it, and the
reduction from the trace to numbers.

`load` turns an `.xplane.pb` into plain events; `reduce` does the rest
on those events alone, so a test can feed it a small recorded trace:

  busy        the union of the intervals in which an operation ran on a
              device ("XLA Ops" line; the "XLA Modules" line where a
              plane has no op line), averaged over the chips used
  modules     device seconds per XLA module (jitted program), by name
              with its run-time id stripped
  gaps        the longest stretches with no device operation, each named
              by the host event that overlaps it most
"""

import glob
import os
import re
import shutil

_ID = re.compile(r'\(\d+\)$')


def start(trace_dir):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop(trace_dir):
    """Stops the trace; returns the path of its xplane file."""
    import jax
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    if not found:
        raise RuntimeError('the profiler wrote no xplane file')
    return found[0]


def load(path):
    """[(plane, line, name, start_ns, dur_ns)] of every timed event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def is_device(plane):
    return plane.startswith('/device:') and 'CPU' not in plane


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _busy_intervals(events, plane):
    mine = [ev for ev in events if ev[0] == plane and ev[4] > 0]
    for line in ('XLA Ops', 'XLA Modules'):
        sel = [ev for ev in mine if ev[1] == line]
        if sel:
            return _union((ev[3], ev[3] + ev[4]) for ev in sel)
    return _union((ev[3], ev[3] + ev[4]) for ev in mine)


def module_name(name):
    return _ID.sub('', name).strip()


def reduce(events, top=10):
    """Busy seconds per chip (mean), device seconds per module, and the
    `top` longest idle gaps of the first chip, each named by the host
    event overlapping it most.  None where no device event was traced."""
    planes = sorted({ev[0] for ev in events if is_device(ev[0])})
    planes = [p for p in planes
              if any(ev[0] == p and ev[4] > 0 for ev in events)]
    if not planes:
        return None
    busy = [_busy_intervals(events, p) for p in planes]
    busy_s = sum(sum(e - s for s, e in b) for b in busy) / len(busy) / 1e9
    modules = {}
    for ev in events:
        if is_device(ev[0]) and ev[1] == 'XLA Modules':
            k = module_name(ev[2])
            modules[k] = modules.get(k, 0.0) + ev[4] / 1e9
    ops = {}
    for ev in events:
        if ev[0] == planes[0] and ev[1] == 'XLA Ops':
            ops[ev[2]] = ops.get(ev[2], 0.0) + ev[4] / 1e9
    lo = min(ev[3] for ev in events)
    hi = max(ev[3] + ev[4] for ev in events)
    gaps, cur = [], lo
    for s, e in busy[0]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [ev for ev in events if ev[0].startswith('/host:') and ev[4] > 0]
    named = []
    for g0, g1 in gaps[:top]:
        best, best_ov = 'no host event', 0.0
        for ev in host:
            ov = min(g1, ev[3] + ev[4]) - max(g0, ev[3])
            if ov > best_ov:
                best, best_ov = ev[2], ov
        named.append([best, (g1 - g0) / 1e9])
    device_ops = sorted(modules.items() or ops.items(),
                        key=lambda kv: -kv[1])[:top]
    return {'chips': len(planes), 'busy_s': busy_s,
            'span_s': (hi - lo) / 1e9, 'modules': modules,
            'device_ops': [[k, v] for k, v in device_ops],
            'idle_gaps': named}
