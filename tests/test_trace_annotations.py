"""Program spans on the device trace's clock, and the transfer and compile
counters (docs/OBSERVABILITY.md, Spans and Transfer sections).

A small gateway flush runs under JAX's profiler with tracing enabled;
the xplane it writes must hold the served path's spans on the threads
that ran them, as self time: one thread's line never has two program
annotations open at once.
"""

import ctypes
import gc
import glob
import os
import socket
import struct
import tempfile
import threading
import time

import msgpack
import numpy as np
import pytest

from automerge_tpu import telemetry
from automerge_tpu.telemetry import spans

ROOT_ID = '00000000-0000-0000-0000-000000000000'

#: every span name the served path may write into the trace
PROGRAM = ('gateway.', 'scheduler.', 'pool.', 'host.', 'device.',
           'sidecar.', 'sync.', 'runtime.', 'test.')


@pytest.fixture
def tracing():
    was = telemetry.enabled()
    telemetry.enable()
    try:
        yield
    finally:
        if not was:
            telemetry.disable()


def _change(actor, seq, n_ops=3):
    return {'actor': actor, 'seq': seq, 'deps': {},
            'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k%d' % i,
                     'value': '%s-%d-%d' % (actor, seq, i)}
                    for i in range(n_ops)]}


def _call(sock, obj):
    body = msgpack.packb(obj, use_bin_type=True)
    sock.sendall(struct.pack('>I', len(body)) + body)
    buf = b''
    while len(buf) < 4 or len(buf) < 4 + struct.unpack('>I', buf[:4])[0]:
        chunk = sock.recv(1 << 16)
        assert chunk, 'gateway closed the connection'
        buf += chunk
    return msgpack.unpackb(buf[4:], raw=False, strict_map_key=False)


def _host_lines(xplane):
    """[(line index, [(name, start_ns, end_ns)])] of the host threads."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith('/host:'):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events]
            out.append((len(out), evs))
    return out


def _program(evs):
    return [e for e in evs if e[0].startswith(PROGRAM)]


@pytest.fixture(scope='module')
def traced_flush():
    """One gateway flush of two requests on the kernel path, traced."""
    import jax

    from automerge_tpu.native import make_pool
    from automerge_tpu.scheduler import GatewayServer
    from automerge_tpu.sidecar.server import SidecarBackend

    saved = {k: os.environ.get(k) for k in ('AMTPU_HOST_FULL',
                                            'AMTPU_HOST_DOM')}
    os.environ['AMTPU_HOST_FULL'] = '0'      # drive the device path
    os.environ['AMTPU_HOST_DOM'] = '0'
    work = tempfile.mkdtemp(prefix='amtpu-annot-')
    was = telemetry.enabled()
    telemetry.enable()
    gw = GatewayServer(os.path.join(work, 'gw.sock'), use_msgpack=True,
                       backend=SidecarBackend(pool=make_pool())).start()
    try:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.connect(gw.sock_path)
            # set-up: compile outside the trace
            _call(s, {'id': 0, 'cmd': 'apply_batch',
                      'docs': {'warm': [_change('aa', 1)]}})
            jax.profiler.start_trace(os.path.join(work, 'trace'))
            try:
                for rid in (1, 2):
                    resp = _call(s, {'id': rid, 'cmd': 'apply_batch',
                                     'docs': {'d%d' % rid: [
                                         _change('aa', 1),
                                         _change('bb', 1)]}})
                    assert 'error' not in resp, resp
                with telemetry.span('test.outer'):
                    gc.collect()
            finally:
                jax.profiler.stop_trace()
    finally:
        gw.stop()
        if not was:
            telemetry.disable()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    found = glob.glob(os.path.join(work, 'trace', '**', '*.xplane.pb'),
                      recursive=True)
    assert found, 'the profiler wrote no xplane file'
    return _host_lines(found[0])


def test_served_spans_reach_the_device_trace(traced_flush):
    lines = {i: {e[0] for e in _program(evs)} for i, evs in traced_flush}
    every = set().union(*lines.values())
    for name in ('gateway.decode', 'scheduler.flush', 'pool.repack',
                 'host.begin', 'device.collect', 'gateway.encode',
                 'scheduler.wait', 'runtime.gc'):
        assert name in every, (name, sorted(every))
    # the reader thread decodes; the dispatcher flushes
    decode = {i for i, names in lines.items() if 'gateway.decode' in names}
    flush = {i for i, names in lines.items() if 'scheduler.flush' in names}
    assert decode and flush and not decode & flush


def test_one_annotation_at_a_time_per_thread(traced_flush):
    n = 0
    for _i, evs in traced_flush:
        prog = sorted(_program(evs), key=lambda e: e[1])
        n += len(prog)
        for (a, _s0, e0), (b, s1, _e1) in zip(prog, prog[1:]):
            assert s1 >= e0, ('%s overlaps %s on one thread' % (a, b))
    assert n > 10


def test_child_closes_and_reopens_its_parent(traced_flush):
    # test.outer was split around the collection nested inside it
    line = [sorted(_program(evs), key=lambda e: e[1])
            for _i, evs in traced_flush
            if any(e[0] == 'test.outer' for e in evs)]
    assert len(line) == 1
    names = [e[0] for e in line[0]]
    first = names.index('test.outer')
    assert names[first:first + 3] == ['test.outer', 'runtime.gc',
                                      'test.outer'], names


def test_bypass_read_waits_outside_decode(tracing):
    """A read answered inline while a flush holds the pool lock waits
    outside `gateway.decode`: the span covers decode, routing and
    admission, not the lock wait, the handle or the send."""
    from automerge_tpu.scheduler import GatewayServer
    work = tempfile.mkdtemp(prefix='amtpu-bypass-')
    gw = GatewayServer(os.path.join(work, 'gw.sock'),
                       use_msgpack=True).start()
    got = {}
    try:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.connect(gw.sock_path)
            assert 'error' not in _call(s, {
                'id': 1, 'cmd': 'apply_changes', 'doc': 'd',
                'changes': [_change('aa', 1)]})
            none = {'s': 0.0, 'n': 0}
            before = telemetry.phase_snapshot().get('gateway.decode', none)
            bypass = telemetry.metrics_snapshot().get(
                'scheduler.bypass_reads', 0)
            reader = threading.Thread(target=lambda: got.update(resp=_call(
                s, {'id': 2, 'cmd': 'get_patch', 'doc': 'd'})))
            with gw.pool_lock:            # what a flush holds
                reader.start()
                time.sleep(0.5)
                assert 'resp' not in got  # the read waits for the lock
            reader.join(30)
            after = telemetry.phase_snapshot()['gateway.decode']
    finally:
        gw.stop()
    assert got['resp']['result']['diffs']
    assert telemetry.metrics_snapshot()['scheduler.bypass_reads'] \
        == bypass + 1
    assert after['n'] == before['n'] + 1
    assert after['s'] - before['s'] < 0.25


def test_disabled_span_creates_no_annotation(monkeypatch):
    made = []

    class Spy(object):
        def __init__(self, name):
            made.append(name)

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans, '_annotation', [Spy])
    was = telemetry.enabled()
    telemetry.disable()
    try:
        sp = telemetry.span('test.off')
        assert sp is telemetry.NULL_SPAN
        with sp:
            pass
        assert made == []
        assert spans._on_gc not in gc.callbacks
        telemetry.enable()
        with telemetry.span('test.parent'):
            with telemetry.span('test.child'):
                pass
        # self time: the parent reopens once its child closes (a
        # collection may nest in between as runtime.gc)
        assert [n for n in made if n.startswith('test.')] == [
            'test.parent', 'test.child', 'test.parent']
    finally:
        if not was:
            telemetry.disable()


class _FakeLib(object):
    """The two ResClock entry points `PoolClockCache.table` reads, over a
    host array of (n, ap) clock rows."""

    def __init__(self, rows):
        self.set(rows)

    def set(self, rows, gen=1):
        self.rows = np.ascontiguousarray(rows, np.int32)
        self.gen = gen

    def amtpu_resclk_info(self, _pool, info):
        n, ap = self.rows.shape
        info[0], info[1], info[2], info[3] = n, ap, self.gen, 0

    def amtpu_resclk_tab(self, _pool):
        return self.rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def test_clock_table_upload_counts_its_bytes(tracing):
    from automerge_tpu.native.batch_resident import PoolClockCache
    rows = np.arange(40 * 3, dtype=np.int32).reshape(40, 3)
    L = _FakeLib(rows)
    cache = PoolClockCache()
    before = telemetry.metrics_snapshot().get('transfer.h2d_bytes', 0)
    up0 = telemetry.phase_snapshot().get('device.upload', {'n': 0})['n']
    tab = cache.table(L, None)
    full = telemetry.metrics_snapshot()['transfer.h2d_bytes'] - before
    # a full upload: the 64-row pow2 floor x 3 actors of int32
    assert full == 64 * 3 * 4
    np.testing.assert_array_equal(np.asarray(tab)[:40], rows)
    # a delta of 5 rows: the 16-row pad floor of indexes and rows
    L.set(np.arange(45 * 3, dtype=np.int32).reshape(45, 3))
    tab = cache.table(L, None, donate_ok=False)
    delta = telemetry.metrics_snapshot()['transfer.h2d_bytes'] \
        - before - full
    assert delta == 16 * 4 + 16 * 3 * 4
    np.testing.assert_array_equal(np.asarray(tab)[:45], L.rows)
    assert telemetry.phase_snapshot()['device.upload']['n'] == up0 + 2


def test_compiles_are_counted_per_function():
    import jax

    from automerge_tpu.native import make_pool
    make_pool()                      # registers the listener, once
    make_pool()

    def fresh_counted_fn(x):
        return x * 3 + 1

    key = 'jit.compiles.jit(fresh_counted_fn)'
    before = telemetry.metrics_snapshot()
    jax.jit(fresh_counted_fn)(np.arange(4, dtype=np.int32))
    after = telemetry.metrics_snapshot()
    assert after.get(key, 0) - before.get(key, 0) == 1
    assert after['jit.compiles'] - before.get('jit.compiles', 0) >= 1
    assert after['jit.compile_s'] > before.get('jit.compile_s', 0.0)


@pytest.mark.parametrize('module, fn', [
    ('automerge_tpu.native.batch_resident', 'clock_table_scatter'),
    ('automerge_tpu.native.resident', 'arena_scatter')])
def test_scatter_modules_have_distinct_names(module, fn):
    import importlib

    import jax
    import jax.numpy as jnp
    f = getattr(importlib.import_module(module), fn)
    text = jax.jit(f).lower(jnp.zeros((8, 2), jnp.int32),
                            np.arange(2, dtype=np.int32),
                            np.ones((2, 2), np.int32)).as_text()
    assert 'module @jit_%s ' % fn in text
    assert 'module @jit_scatter ' not in text

