"""Telemetry layer tests (PR 1): histogram bucketing/percentile math,
concurrent-writer safety, span-id propagation through a sidecar
serve_stream round trip, and a golden check that the Prometheus text
exposition parses (format 0.0.4)."""

import io
import json
import os
import re
import subprocess
import sys
import threading
import urllib.request

import pytest

from automerge_tpu import telemetry, trace
from automerge_tpu.telemetry.metrics import MetricRegistry

ROOT_ID = '00000000-0000-0000-0000-000000000000'
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CH = {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
    {'action': 'set', 'obj': ROOT_ID, 'key': 'bird', 'value': 'magpie'}]}


@pytest.fixture(autouse=True)
def _isolate():
    """Telemetry state is process-global: zero it around every test and
    restore the enable flag + exporter."""
    telemetry.reset_all()
    was = telemetry.enabled()
    was_file = telemetry.trace_file()
    yield
    telemetry.set_trace_file(was_file)
    if was:
        telemetry.enable()
    else:
        telemetry.disable()
    telemetry.reset_all()


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------

def test_histogram_bucketing_and_counts():
    reg = MetricRegistry()
    h = reg.histogram('h_test_seconds', 'test')
    bounds = h.labels().bounds
    # boundary value lands in its own bucket (le = inclusive upper edge)
    h.observe(bounds[3])
    assert h.labels().counts[3] == 1
    # just above a bound spills into the next bucket
    h.observe(bounds[3] * 1.0001)
    assert h.labels().counts[4] == 1
    # below the first bound -> bucket 0; beyond the last -> +Inf bucket
    h.observe(0.0)
    assert h.labels().counts[0] == 1
    h.observe(bounds[-1] * 10)
    assert h.labels().counts[-1] == 1
    assert h.labels().count == 4
    assert abs(h.labels().sum -
               (bounds[3] + bounds[3] * 1.0001 + bounds[-1] * 10)) < 1e-9


def test_histogram_percentiles():
    reg = MetricRegistry()
    h = reg.histogram('h_pct_seconds', 'test')
    for _ in range(50):
        h.observe(0.0005)       # bucket (..., 0.000512]
    for _ in range(50):
        h.observe(0.002)        # bucket (0.001024, 0.002048]
    assert h.quantile(0.5) <= 0.000512 + 1e-12
    p95 = h.quantile(0.95)
    assert 0.001024 < p95 <= 0.002048
    assert h.quantile(0.99) <= 0.002048
    s = h.summary()
    assert s['count'] == 100 and abs(s['sum'] - 0.125) < 1e-6
    assert s['p50'] <= s['p95'] <= s['p99']


def test_histogram_edge_cases():
    reg = MetricRegistry()
    h = reg.histogram('h_edge_seconds', 'test')
    assert h.quantile(0.5) == 0.0           # empty
    h.observe(1e9)                          # +Inf bucket clamps to last bound
    assert h.quantile(0.99) == h.labels().bounds[-1]


def test_counter_rejects_negative_and_gauge_sets():
    reg = MetricRegistry()
    c = reg.counter('c_total', 'test')
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge('g_now', 'test')
    g.set(4.5)
    g.dec(0.5)
    assert g.value == 4.0
    # re-registration with a different schema is an error
    with pytest.raises(ValueError):
        reg.gauge('c_total', 'test')


# ---------------------------------------------------------------------------
# concurrency: hammer the registry like ShardedNativePool hammers trace
# ---------------------------------------------------------------------------

def test_concurrent_writers_exact_totals():
    reg = MetricRegistry()
    c = reg.counter('cc_total', 'test')
    lc = reg.counter('cl_total', 'test', ('shard',))
    h = reg.histogram('ch_seconds', 'test')
    n_threads, n_iter = 8, 2000

    def hammer(tid):
        child = lc.labels(str(tid % 2))
        for _ in range(n_iter):
            c.inc()
            child.inc(2)
            h.observe(0.001)
            telemetry.metric('fallback.hammer')
            telemetry.phase_count('hammer.phase')

    telemetry.enable()
    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    assert c.value == total
    assert lc.labels('0').value + lc.labels('1').value == 2 * total
    assert h.labels().count == total
    assert telemetry.metrics_snapshot()['fallback.hammer'] == total
    assert telemetry.phase_snapshot()['hammer.phase']['n'] == total


# ---------------------------------------------------------------------------
# runtime toggle + trace shim compatibility
# ---------------------------------------------------------------------------

def test_runtime_toggle_and_trace_shim():
    telemetry.disable()
    with trace.span('t.off'):
        pass
    assert 't.off' not in trace.snapshot()
    trace.ENABLED = True                    # legacy toggle spelling
    assert telemetry.enabled()
    with trace.span('t.on'):
        pass
    trace.add('t.add', 0.25, 2)
    trace.count('t.count', 3)
    snap = trace.snapshot()
    assert snap['t.on']['n'] == 1
    assert abs(snap['t.add']['s'] - 0.25) < 1e-9 and snap['t.add']['n'] == 2
    assert snap['t.count']['n'] == 3
    assert 'occupancy seconds' in trace.report()
    trace.ENABLED = False
    assert not telemetry.enabled()
    # the always-on flat metrics ignore the toggle
    trace.metric('fallback.test', 2)
    assert trace.metrics_snapshot()['fallback.test'] == 2.0


def test_span_nesting_and_context():
    telemetry.enable()
    assert telemetry.current_trace_context() is None
    with telemetry.span('outer') as outer:
        ctx = telemetry.current_trace_context()
        assert ctx == {'traceId': outer.trace_id, 'spanId': outer.span_id}
        with telemetry.span('inner') as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    assert telemetry.current_trace_context() is None


# ---------------------------------------------------------------------------
# span-id propagation through a sidecar serve_stream round trip
# ---------------------------------------------------------------------------

def test_span_propagation_serve_stream_round_trip(tmp_path):
    from automerge_tpu.sidecar.server import serve_stream
    telemetry.enable()
    path = str(tmp_path / 'spans.jsonl')
    telemetry.set_trace_file(path)
    trace_id, parent_id = 'feedfacecafed00d', '0123456789abcdef'
    reqs = [
        {'id': 1, 'cmd': 'apply_changes', 'doc': 'd', 'changes': [CH],
         'trace': {'traceId': trace_id, 'spanId': parent_id}},
        {'id': 2, 'cmd': 'get_patch', 'doc': 'd'},
    ]
    rfile = io.BytesIO(''.join(json.dumps(r) + '\n' for r in reqs).encode())
    wfile = io.BytesIO()
    serve_stream(rfile, wfile)
    telemetry.set_trace_file(None)     # flush/close before reading

    resps = [json.loads(l) for l in wfile.getvalue().splitlines()]
    assert [r['id'] for r in resps] == [1, 2]
    assert resps[0]['result']['clock'] == {'a': 1}
    # the trace envelope is consumed server-side: responses carry no
    # telemetry fields (byte-parity with the pre-PR-1 protocol)
    assert set(resps[0]) == {'id', 'result'}

    recs = [json.loads(l) for l in open(path)]
    req_spans = [r for r in recs if r['name'] == 'sidecar.request']
    assert len(req_spans) == 2
    # request 1 RESUMES the client's trace; request 2 mints its own
    assert req_spans[0]['trace'] == trace_id
    assert req_spans[0]['parent'] == parent_id
    assert req_spans[0]['attrs']['cmd'] == 'apply_changes'
    assert req_spans[1]['trace'] != trace_id
    # nested pool spans joined the SAME trace as their request
    nested = [r for r in recs if r['parent'] == req_spans[0]['span']]
    assert nested and all(r['trace'] == trace_id for r in nested)


def test_client_injects_trace_context():
    from automerge_tpu.sidecar.client import SidecarClient
    from automerge_tpu.sidecar.server import serve_stream
    telemetry.enable()
    c = SidecarClient.__new__(SidecarClient)
    c._msgpack = False
    c._next_id = 0
    c._proc = c._sock = None
    c._r = io.BytesIO(
        (json.dumps({'id': 1, 'result': {'ok': True}}) + '\n').encode())
    c._w = io.BytesIO()
    with telemetry.span('frontend.change') as root:
        assert c.call('ping') == {'ok': True}
    sent = json.loads(c._w.getvalue())
    # same trace as the caller's span; the parent span id is the
    # client-hop span (sidecar.client.request) nested under it, so the
    # server's spans become children of the hop, not of the frontend
    assert sent['trace']['traceId'] == root.trace_id
    assert sent['trace']['spanId'] != root.span_id
    assert len(sent['trace']['spanId']) == 16
    # ...and the server resumes exactly that trace
    out = io.BytesIO()
    serve_stream(io.BytesIO(c._w.getvalue()), out)
    assert json.loads(out.getvalue())['result'] == {'ok': True}

    # without an ambient span the client still stamps: a freshly minted
    # ROOT context (ISSUE 16 always-stamp; 128-bit trace id), distinct
    # from the earlier trace
    telemetry.disable()
    c._w = io.BytesIO()
    c.__dict__['_r'] = io.BytesIO(
        (json.dumps({'id': 2, 'result': {'ok': True}}) + '\n').encode())
    c.call('ping')
    sent2 = json.loads(c._w.getvalue())
    assert len(sent2['trace']['traceId']) == 32
    assert sent2['trace']['traceId'] != sent['trace']['traceId']

    # AMTPU_TRACE_WIRE=0 (latched per client) turns stamping off
    c._wire_trace = False
    c._w = io.BytesIO()
    c.__dict__['_r'] = io.BytesIO(
        (json.dumps({'id': 3, 'result': {'ok': True}}) + '\n').encode())
    c.call('ping')
    assert 'trace' not in json.loads(c._w.getvalue())


# ---------------------------------------------------------------------------
# Prometheus exposition: golden parse + required families
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'           # metric name
    r'(\{[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*"'    # first label
    r'(?:,[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*")*\})?'  # more labels
    r' (-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|\+Inf|NaN)$')


def parse_exposition(body):
    """Strict mini-parser: returns ({family: type}, [(name, labels, value)]);
    asserts every line is HELP, TYPE, or a well-formed sample."""
    types, samples = {}, []
    for line in body.splitlines():
        if not line:
            continue
        if line.startswith('# HELP '):
            assert len(line.split(' ', 3)) == 4, line
            continue
        if line.startswith('# TYPE '):
            _, _, name, type_ = line.split(' ', 3)
            assert type_ in ('counter', 'gauge', 'histogram'), line
            types[name] = type_
            continue
        m = _SAMPLE_RE.match(line)
        assert m, 'unparseable exposition line: %r' % line
        samples.append((m.group(1), m.group(2) or '', m.group(3)))
    for name, _labels, _v in samples:
        base = re.sub(r'_(bucket|sum|count)$', '', name)
        assert name in types or base in types, \
            'sample %s has no TYPE declaration' % name
    return types, samples


def _kernel_backend():
    """A sidecar over a native pool on the chip's kernel path, so the
    device phases appear in the exposition."""
    from automerge_tpu.sidecar.server import SidecarBackend
    from tests.conftest import LanePool
    return SidecarBackend(pool=LanePool('kernel'))


def test_metrics_request_answers_valid_exposition():
    telemetry.enable()
    backend = _kernel_backend()
    resp = backend.handle({'id': 1, 'cmd': 'apply_changes', 'doc': 'd',
                           'changes': [CH]})
    assert 'result' in resp
    out = backend.handle({'id': 2, 'cmd': 'metrics'})
    assert out['id'] == 2
    body = out['result']['body']
    assert 'text/plain' in out['result']['contentType']
    types, samples = parse_exposition(body)

    # acceptance criteria: batch-latency histogram, per-phase occupancy,
    # op/doc counters, oracle-fallback counters
    assert types['amtpu_batch_latency_seconds'] == 'histogram'
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert any('pool="native"' in l for l, _ in
               by_name['amtpu_batch_latency_seconds_bucket'])
    assert float(dict(by_name['amtpu_ops_total'])['']) >= 1
    assert float(dict(by_name['amtpu_docs_total'])['']) >= 1
    assert any('phase="device.dispatch"' in l for l, _ in
               by_name['amtpu_phase_seconds_total'])
    assert any('reason="overflow_batches"' in l for l, _ in
               by_name['amtpu_fallback_total'])
    assert any('cmd="apply_changes"' in l for l, _ in
               by_name['amtpu_sidecar_requests_total'])

    # histogram invariants: buckets cumulative-monotonic, +Inf == _count
    nat = [(l, float(v)) for l, v in
           by_name['amtpu_batch_latency_seconds_bucket']
           if 'pool="native"' in l]
    counts = [v for _, v in nat]
    assert counts == sorted(counts)
    inf = [v for l, v in nat if 'le="+Inf"' in l]
    count = [float(v) for l, v in
             by_name['amtpu_batch_latency_seconds_count']
             if 'pool="native"' in l]
    assert inf == count and count[0] >= 1


def test_healthz_command():
    backend = _kernel_backend()
    out = backend.handle({'id': 1, 'cmd': 'healthz'})
    assert out['result']['ok'] is True
    assert 'uptime_s' in out['result']
    # unknown commands still error (the new cmds didn't loosen dispatch)
    assert 'error' in backend.handle({'id': 2, 'cmd': 'frobnicate'})
    reqs = telemetry.SIDECAR_REQS.snapshot()
    assert reqs.get('healthz,ok') == 1
    assert reqs.get('unknown,error') == 1


def test_http_listener_serves_metrics_and_healthz():
    from automerge_tpu.telemetry.httpd import start_metrics_server
    telemetry.enable()
    with telemetry.span('engine.batch'):
        pass
    server = start_metrics_server(0)
    try:
        base = 'http://127.0.0.1:%d' % server.server_port
        with urllib.request.urlopen(base + '/metrics', timeout=10) as r:
            assert r.status == 200
            assert 'text/plain' in r.headers['Content-Type']
            types, _ = parse_exposition(r.read().decode())
            assert 'amtpu_up' in types
        with urllib.request.urlopen(base + '/healthz', timeout=10) as r:
            assert r.status == 200
            assert json.load(r)['ok'] is True
        try:
            urllib.request.urlopen(base + '/nope', timeout=10)
            assert False, 'expected 404'
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# full subprocess round trip: the deployment shape a scraper sees
# ---------------------------------------------------------------------------

def test_sidecar_subprocess_metrics_round_trip():
    from automerge_tpu.sidecar.client import SidecarClient
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, '-m', 'automerge_tpu.sidecar.server', '--trace'],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=REPO)
    with SidecarClient(proc=proc) as c:
        c.apply_changes('doc1', [CH])
        assert c.healthz()['ok'] is True
        out = c.metrics()
        types, samples = parse_exposition(out['body'])
        assert types['amtpu_batch_latency_seconds'] == 'histogram'
        names = {n for n, _, _ in samples}
        assert 'amtpu_sidecar_requests_total' in names
        assert 'amtpu_fallback_total' in names
        assert 'amtpu_phase_seconds_total' in names   # --trace enabled it


# ---------------------------------------------------------------------------
# bench embedding
# ---------------------------------------------------------------------------

def test_bench_block_shape():
    telemetry.enable()
    backend = _kernel_backend()
    backend.handle({'id': 1, 'cmd': 'apply_changes', 'doc': 'd',
                    'changes': [CH]})
    telemetry.metric('fallback.overflow_batches', 2)
    block = telemetry.bench_block()
    # every KNOWN reason is pre-seeded at 0 (the fallback-check gate
    # reads presence, not just values); observed counters overlay
    assert block['fallbacks']['overflow_batches'] == 2
    for reason in telemetry.KNOWN_FALLBACK_REASONS:
        assert reason in block['fallbacks'], reason
    assert block['fallbacks']['oracle'] == 0
    # the scheduler block is pre-seeded the same way (serve-check and
    # dashboards read explicit zeros before the first gateway request)
    for key in telemetry.KNOWN_SCHEDULER_KEYS:
        assert block['scheduler'][key] == 0, key
    assert block['batch_latency']['native']['count'] == 1
    assert block['ops_total'] >= 1 and block['docs_total'] >= 1
    assert 'device.dispatch' in block['phases']
    json.dumps(block)    # must be JSON-serializable as-is
