"""One run of one cell: device, gateway, traffic, window, check, result.

The order matters.  The chip is claimed first and anything but the
chips the cell asks for ends the run with no result.  Set-up ends when
the window opens.  The window ends once its time is up and every
request it sent is answered; then the sampled docs are read back and
the device's memory peak is read; then the gateway and its pool are
freed, and only then does the reference run, so that it never sets the
peak nor shares the chip's process with live state.
"""

import gc
import json
import os
import shutil
import sys
import tempfile
import time

from . import device, meter, profile, server, spec


def _log(*args):
    print(*args, file=sys.stderr, flush=True)


def _number(v):
    return v if isinstance(v, (int, float)) and v == v else None


def run(cell, seed, seconds, trace, t_start, allow_cpu=False, fault=None):
    """The result line of one run, as a dict."""
    dev = device.require(cell['chips'], allow_cpu)
    _log('[device] %s' % json.dumps(dev))
    from automerge_tpu.utils.jaxenv import enable_compile_cache
    cache = enable_compile_cache()
    _log('[device] compile cache: %s' % cache)
    server.build_native()
    compiles = meter.Compiles()
    kind = spec.kind(cell)
    mix = cell['traffic']
    work = tempfile.mkdtemp(prefix='amtpu-bench-')
    ctx = {'cell': cell, 'seed': seed, 'device': dev, 'trace': None,
           'seconds': seconds}
    traffic = None
    cwd = os.getcwd()
    sock = os.path.join(work, 'gw.sock')
    if len(sock) > 100:
        # a unix socket's path has room for about 107 bytes: bind it
        # relative to the run's directory, where the traffic processes
        # start too
        os.chdir(work)
        sock = 'gw.sock'
    try:
        gw = server.Gateway(sock,
                            queue_max_ops=mix.get('queue_max_ops'),
                            fault=fault)
        traffic = kind.Run(cell, seed, gw.server.sock_path)
        try:
            info = traffic.setup(seconds)
            _log('[setup] %s' % json.dumps(info))
            if trace:
                meter.telemetry_on()
            c0, m0 = compiles.read(), meter.snapshot()
            if trace:
                profile.start(os.path.join(work, 'trace'))
            t_open, _t_close = traffic.open()
            ctx['setup_s'] = t_open - t_start
            traffic.wait()
            if trace:
                xplane = profile.stop(os.path.join(work, 'trace'))
            c1, m1 = compiles.read(), meter.snapshot()
            client = traffic.finish()
            ctx['memory_peak_bytes'] = device.memory_peak_bytes(
                cell['chips'])
        finally:
            gw.stop()
        del gw
        gc.collect()
        ctx['client'] = client
        ctx['compiles'] = {k: c1[k] - c0[k] for k in c0}
        ctx['program'] = meter.delta(m0, m1)
        _log('[window] %s' % json.dumps(
            {k: v for k, v in client.items() if k != 'errors'}))
        _log('[window] compiles: %s' % json.dumps(ctx['compiles']))
        if client.get('errors'):
            _log('[window] first errors: %r' % (client['errors'][:3],))
        _log('[memory] peak_bytes_in_use after the window: %s'
             % ctx['memory_peak_bytes'])
        if trace:
            t0 = time.monotonic()
            ctx['trace'] = profile.reduce(profile.load(xplane))
            _log('[trace] reduced in %.1f s' % (time.monotonic() - t0))
        t0 = time.monotonic()
        numbers = traffic.check()
        _log('[check] reference compared in %.1f s'
             % (time.monotonic() - t0))
    finally:
        if traffic is not None:
            traffic.close()
        os.chdir(cwd)
        shutil.rmtree(work, ignore_errors=True)
    return _result(cell, ctx, dev, numbers, trace)


def _result(cell, ctx, dev, numbers, trace):
    metrics = {}
    for m in (cell['per_layer'] if trace else cell['end_to_end']):
        v = _number(spec.reader(m['name'])(ctx))
        if v is not None:
            metrics[m['name']] = {'value': v, 'unit': m['unit']}
    correct = all(value <= limit for _n, value, limit in numbers)
    device_ = dict(dev, memory_peak_bytes=ctx['memory_peak_bytes'])
    out = {'correct': correct,
           'attempted': ctx['client']['attempted'],
           'failed': ctx['client']['failed'],
           'metrics': metrics, 'device': device_}
    if trace and ctx['trace'] is not None:
        device_['busy_s'] = ctx['trace']['busy_s']
        device_['window_s'] = ctx['trace']['span_s']
        out['breakdown'] = {'device_ops': ctx['trace']['device_ops'],
                            'idle_gaps': ctx['trace']['idle_gaps']}
    compared = {name: {'value': value, 'limit': limit}
                for name, value, limit in numbers}
    for name, value, limit in numbers:
        _log('[check] %s = %s (limit %s)' % (name, value, limit))
    out['compared'] = compared
    return out
