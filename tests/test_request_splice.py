"""An apply_batch request kept as its frame's bytes (docs/SERVING.md,
request path): the reader finds each doc's changes span, the flush
splices the spans into the pool's payload, and a doc's changes decode
only where something reads them.  Every answer must be what the serial
backend, or the decode-to-dicts path, gives for the same frame."""

import os
import random
import socket
import struct
import tempfile

import msgpack
import pytest

from automerge_tpu import native, telemetry
from automerge_tpu.native import NativeDocPool
from automerge_tpu.scheduler import GatewayServer
from automerge_tpu.scheduler.gateway import _Conn, _op_docs, _op_weight
from automerge_tpu.sidecar.client import SidecarClient
from automerge_tpu.sidecar.server import SidecarBackend
from automerge_tpu.utils.common import doc_key
from automerge_tpu.utils.request_map import FrameDocs, read_request
from automerge_tpu.utils.wire import array_header, map_header
from test_result_splice import Wire, map_change, round_requests

ROOT_ID = '00000000-0000-0000-0000-000000000000'


@pytest.fixture(autouse=True)
def _hygiene():
    telemetry.reset_all()
    yield
    telemetry.reset_all()


def pack(obj, **kw):
    return msgpack.packb(obj, use_bin_type=True, **kw)


def unpack(buf):
    return msgpack.unpackb(buf, raw=False, strict_map_key=False)


def frame_request(body):
    """What the gateway's msgpack reader makes of a frame."""
    return read_request(body, native.scan_changes)


def _gateway(pool=None):
    path = os.path.join(tempfile.mkdtemp(), 'gw.sock')
    backend = SidecarBackend(pool=pool) if pool is not None else None
    return GatewayServer(path, use_msgpack=True, backend=backend)


def _flush(gw, wires, reqs):
    """Submits `(who, request)` pairs and runs them as one flush;
    returns {rid: decoded response}."""
    for who, req in reqs:
        gw.submit(wires[who].conn, req)
    batch, execs = gw.queue.claim()
    assert len(batch) == len(reqs) and not execs
    gw._flush(batch, execs)
    return {r['id']: r for r in (wires[who].read() for who, _ in reqs)}


def _spy_payloads(pool):
    seen = []
    apply = pool.apply_batch_bytes_resilient

    def spy(payload):
        seen.append(bytes(payload))
        return apply(payload)
    pool.apply_batch_bytes_resilient = spy
    return seen


def _n_frame_docs(reqs):
    return sum(len(r['docs']) for _, r in reqs if r['cmd'] == 'apply_batch')


def test_coalesced_frames_answer_as_serial():
    """Text, map and table docs, int doc ids, and apply_changes requests
    coalesced with apply_batch frames: the pool gets the payload the
    dict path packs, and every answer is the serial backend's."""
    gw = _gateway()
    wires = {'A': Wire(gw, 1), 'B': Wire(gw, 2)}
    serial = SidecarBackend(pool=NativeDocPool())
    payloads = _spy_payloads(gw.backend.pool)
    try:
        for seq in (1, 2):
            reqs = round_requests(seq)
            framed = [(who, frame_request(pack(req))) for who, req in reqs]
            kinds = {type(r.get('docs')) for _, r in framed}
            assert FrameDocs in kinds
            got = _flush(gw, wires, framed)
            merged = {}
            for _, req in reqs:
                if req['cmd'] == 'apply_batch':
                    merged.update(req['docs'])
                else:
                    merged[req['doc']] = req['changes']
            assert payloads[-1] == pack(
                {doc_key(d): chs for d, chs in merged.items()})
            for _, req in reqs:
                want = serial.handle(dict(req))
                assert 'result' in want, want
                assert got[req['id']] == want, req
    finally:
        for w in wires.values():
            w.close()
    snap = telemetry.metrics_snapshot()
    assert snap['gateway.request_spliced_docs'] == \
        2 * _n_frame_docs(round_requests(1))
    assert snap.get('gateway.request_decoded_docs', 0) == 0


def _dup_frame(rid, entries):
    """An apply_batch frame whose docs map lists `entries` (doc, changes)
    in order, repeats included -- what no dict can pack."""
    body = [map_header(len(entries))]
    for d, chs in entries:
        body += [pack(d), pack(chs)]
    return b''.join([map_header(3), pack('cmd'), pack('apply_batch'),
                     pack('id'), pack(rid), pack('docs')] + body)


def test_repeated_doc_key_resolves_as_unpackb():
    entries = [('a', [map_change('x', 1)]), (3, [map_change('y', 1)]),
               ('a', [map_change('z', 1), map_change('z', 2)])]
    frame = _dup_frame(5, entries)
    want_req = unpack(frame)
    req = frame_request(frame)
    assert list(req['docs']) == list(want_req['docs']) == ['a', 3]
    assert dict(req['docs']) == want_req['docs']
    assert _op_weight('apply_batch', req) == \
        _op_weight('apply_batch', want_req) == 3
    telemetry.reset_all()
    gw = _gateway()
    wires = {'A': Wire(gw, 1)}
    try:
        got = _flush(gw, wires, [('A', frame_request(frame))])
    finally:
        wires['A'].close()
    want = SidecarBackend(pool=NativeDocPool()).handle(want_req)
    assert got[5] == want
    assert telemetry.metrics_snapshot()['gateway.request_spliced_docs'] == 2


@pytest.mark.parametrize('seq', [1, 2])
def test_routing_weight_and_ops_total_match_the_dict_path(seq):
    reqs = [r for _, r in round_requests(seq) if r['cmd'] == 'apply_batch']
    for req in reqs:
        framed = frame_request(pack(req))
        assert _op_docs('apply_batch', framed) == \
            _op_docs('apply_batch', req)
        assert _op_weight('apply_batch', framed) == \
            _op_weight('apply_batch', req)
    counts = []
    for as_frame in (False, True):
        telemetry.reset_all()
        gw = _gateway()
        wires = {'A': Wire(gw, 1)}
        try:
            for s in range(1, seq + 1):
                batch = [('A', frame_request(pack(r)) if as_frame else r)
                         for _, r in round_requests(s)
                         if r['cmd'] == 'apply_batch']
                _flush(gw, wires, batch)
        finally:
            wires['A'].close()
        counts.append(telemetry.OPS.value)
    assert counts[0] == counts[1] > 0


def _change_with(value):
    return {'actor': 'v', 'seq': 1, 'deps': {},
            'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'x',
                     'value': value}]}


def _bad_utf8_frame():
    good = pack({'cmd': 'apply_batch', 'id': 9,
                 'docs': {'d': [_change_with('Zq')]}})
    assert good.count(b'\xa2Zq') == 1
    return good.replace(b'\xa2Zq', b'\xa2\xff\xfe')


def _reader_answers(frames):
    """Runs a gateway connection's reader over `frames`, flushes what it
    queued, and returns the answers in order."""
    gw = _gateway()
    mine, theirs = socket.socketpair()
    conn = _Conn(mine, gw, 1)
    rfile = theirs.makefile('rb')
    try:
        for f in frames:
            theirs.sendall(struct.pack('>I', len(f)) + f)
        theirs.shutdown(socket.SHUT_WR)
        conn._run_msgpack()
        batch, execs = gw.queue.claim()
        if batch or execs:
            gw._flush(batch, execs)
        out = []
        for _ in frames:
            (n,) = struct.unpack('>I', rfile.read(4))
            out.append(unpack(rfile.read(n)))
        return out
    finally:
        conn.close()
        rfile.close()
        theirs.close()


def _dict_path_answer(frame):
    """What a reader that decodes the whole frame answers."""
    try:
        req = unpack(frame)
        if not isinstance(req, dict):
            raise ValueError('request is not a map')
    except Exception as e:
        return {'id': None, 'error': 'bad msgpack: %s' % e,
                'errorType': 'RangeError'}
    gw = _gateway()
    wires = {'A': Wire(gw, 1)}
    try:
        inline = gw.route(wires['A'].conn, req)
        if inline is not None:
            inline()
        else:
            batch, execs = gw.queue.claim()
            gw._flush(batch, execs)
        return wires['A'].read()
    finally:
        wires['A'].close()


GOOD = pack({'cmd': 'apply_batch', 'id': 4,
             'docs': {'d': [map_change('w', 1)]}})


@pytest.mark.parametrize('frame', [
    pack({'cmd': 'apply_batch', 'id': 1,
          'docs': {'d': [map_change('w', 1)], 'e': 'not changes'}}),
    pack({'cmd': 'apply_batch', 'id': 2, 'docs': {'d': {'x': 1}}}),
    pack({'cmd': 'apply_batch', 'id': 3, 'docs': {}}),
    GOOD[:-7],
    GOOD + b'\xc0',
    _bad_utf8_frame(),
    pack([1, 2]),
    b'\xc1',
], ids=['non-array-docs-value', 'map-docs-value', 'empty-docs',
        'truncated', 'trailing-byte', 'invalid-utf8-in-change',
        'not-a-map', 'reserved-byte'])
def test_malformed_frames_answer_as_before(frame):
    got = _reader_answers([frame])[0]
    want = _dict_path_answer(frame)
    assert got == want
    assert telemetry.metrics_snapshot().get(
        'gateway.request_spliced_docs', 0) == 0


def test_float32_and_loose_encodings_reach_the_pool_as_packb_gives():
    """Spans not in canonical form (a float32, an int in a wider tag
    than it needs) are decoded on the reader and packed, so the pool
    gets the bytes, and the client the patch, of the dict path."""
    f32 = pack({'cmd': 'apply_batch', 'id': 1,
                'docs': {'f': [_change_with(1.25)],
                         'g': [_change_with('ok')]}},
               use_single_float=True)
    assert b'\xca' in f32
    loose = GOOD.replace(b'\xa3seq\x01', b'\xa3seq\xcd\x00\x01')
    assert loose != GOOD and unpack(loose) == unpack(GOOD)
    answers = []
    for frame, n_loose in ((f32, 1), (loose, 1)):
        telemetry.reset_all()
        gw = _gateway()
        payloads = _spy_payloads(gw.backend.pool)
        wires = {'A': Wire(gw, 1)}
        req = unpack(frame)
        try:
            got = _flush(gw, wires, [('A', frame_request(frame))])
        finally:
            wires['A'].close()
        assert payloads == [pack({doc_key(d): chs
                                  for d, chs in req['docs'].items()})]
        want = SidecarBackend(pool=NativeDocPool()).handle(req)
        assert got[req['id']] == want and 'result' in want
        answers.append(got[req['id']])
        snap = telemetry.metrics_snapshot()
        assert snap['gateway.request_decoded_docs'] == n_loose
        assert snap.get('gateway.request_spliced_docs', 0) == \
            len(req['docs']) - n_loose
    value = answers[0]['result']['f']['diffs'][0]['value']
    assert value == 1.25 and isinstance(value, float)


def test_serial_fallback_decodes_the_frames():
    """A doc the pool refuses whole (an invalid change) replays the
    flush serially: each frame decodes and answers as the serial
    backend does."""
    bad = {'actor': 'b', 'seq': 'one', 'deps': {}, 'ops': []}
    reqs = [('A', {'id': 1, 'cmd': 'apply_batch',
                   'docs': {'x': [map_change('m', 1)], 'y': [bad]}}),
            ('A', {'id': 2, 'cmd': 'apply_batch',
                   'docs': {'z': [map_change('n', 1)]}})]
    gw = _gateway()
    wires = {'A': Wire(gw, 1)}
    try:
        got = _flush(gw, wires, [(w, frame_request(pack(r)))
                                 for w, r in reqs])
    finally:
        wires['A'].close()
    serial = SidecarBackend(pool=NativeDocPool())
    for _, req in reqs:
        assert got[req['id']] == serial.handle(dict(req))
    assert 'error' in got[1] and 'result' in got[2]
    snap = telemetry.metrics_snapshot()
    assert snap['scheduler.serial_fallback'] == 1
    # the refused payload carried the frames' bytes; the replay decoded
    assert snap['gateway.request_spliced_docs'] == 3
    assert snap['gateway.request_decoded_docs'] == 3


def test_changes_subscriber_decodes_only_its_doc():
    """A changes-mode subscriber on one doc of an eight-doc frame gets
    its change; only that doc's changes decode (its submitted clock),
    the other seven reach the pool as the frame's bytes."""
    path = os.path.join(tempfile.mkdtemp(), 'gw.sock')
    gw = GatewayServer(path, use_msgpack=True).start()
    docs = ['d%02d' % i for i in range(8)]
    try:
        with SidecarClient(sock_path=path, use_msgpack=True) as writer, \
                SidecarClient(sock_path=path, use_msgpack=True) as sub:
            writer.apply_batch({d: [map_change('w', 1)] for d in docs})
            sub.subscribe('d03', clock={'w': 1})
            writer.apply_batch({d: [map_change('w', 2)] for d in docs})
            ev = sub.next_event(timeout=10)
            assert ev is not None and ev['event'] == 'change'
            assert ev['doc'] == 'd03'
    finally:
        gw.stop()
    snap = telemetry.metrics_snapshot()
    assert snap['gateway.request_decoded_docs'] == 1
    assert snap['gateway.request_spliced_docs'] == 2 * len(docs)


def _random_value(rng, depth=0):
    pick = rng.randrange(8 if depth < 3 else 5)
    if pick == 0:
        return rng.choice([0, 1, 127, 128, 255, 256, 65535, 65536,
                           2 ** 32, 2 ** 63, 2 ** 64 - 1, -1, -32, -33,
                           -128, -129, -32768, -32769, -2 ** 31,
                           -2 ** 31 - 1, -2 ** 63])
    if pick == 1:
        return ''.join(rng.choice('abé中\U0001f600')
                       for _ in range(rng.choice([0, 3, 31, 32, 300])))
    if pick == 2:
        return rng.choice([None, True, False, 0.5, -2.0])
    if pick == 3:
        return bytes(rng.randrange(256) for _ in range(rng.choice([0, 5])))
    if pick == 4:
        return 'k%d' % rng.randrange(100)
    if pick == 5:
        return [_random_value(rng, depth + 1)
                for _ in range(rng.choice([0, 2, 16]))]
    return {'f%d' % i: _random_value(rng, depth + 1)
            for i in range(rng.choice([0, 2, 16]))}


def _loose_pack(obj, rng, p):
    """msgpack of `obj` where, with chance `p` at each value, a header
    is wider than it needs or an exact float is a float32: valid, and
    decoding to `obj`, but not what packb gives."""
    wide = rng.random() < p
    if isinstance(obj, dict):
        head = (b'\xde' + struct.pack('>H', len(obj)) if wide
                else map_header(len(obj)))
        return head + b''.join(_loose_pack(k, rng, 0) +
                               _loose_pack(v, rng, p)
                               for k, v in obj.items())
    if isinstance(obj, list):
        head = (b'\xdc' + struct.pack('>H', len(obj)) if wide
                else array_header(len(obj)))
        return head + b''.join(_loose_pack(v, rng, p) for v in obj)
    if wide and isinstance(obj, str) and len(obj.encode()) < 32:
        raw = obj.encode()
        return b'\xd9' + bytes([len(raw)]) + raw
    if wide and isinstance(obj, int) and not isinstance(obj, bool) \
            and 0 <= obj < 2 ** 31:
        return b'\xd2' + struct.pack('>i', obj)
    if wide and isinstance(obj, float):
        return b'\xca' + struct.pack('>f', obj)
    return pack(obj)


def test_scan_changes_takes_exactly_the_canonical_spans():
    """`native.scan_changes` counts ops where a span is what
    packb(unpackb(span)) gives and refuses every other span."""
    rng = random.Random(2 ** 33 + 26)
    bufs, want = [], []
    for _ in range(300):
        changes = [{'actor': 'a', 'seq': s, 'deps': {},
                    'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                             'value': _random_value(rng)}
                            for _ in range(rng.choice([0, 1, 3, 17]))]}
                   for s in range(1, rng.choice([1, 2, 4]) + 1)]
        buf = _loose_pack(changes, rng, rng.choice([0, 0, 0.02, 0.2]))
        assert unpack(buf) == changes
        bufs.append(buf)
        want.append(sum(len(c['ops']) for c in changes)
                    if pack(changes) == buf else -1)
    odd = [pack([1, 2]), pack([{'ops': 'x'}]), pack({'a': []}),
           pack([{'ops': [1], 'x': {1: 2}}]),
           b'\x91\x82\xa1a\x01\xa1a\x02',          # a repeated key
           b'\x91\xc7\x01\x05\x00',                # an ext value
           b'\x91\xa2\xc3\x28',                    # invalid utf-8
           pack([{'ops': []}])[:-1]]                # truncated
    bufs += odd
    want += [-1] * len(odd)
    spans, frame = [], b''
    for buf in bufs:
        spans.append((len(frame), len(frame) + len(buf)))
        frame += buf
    assert native.scan_changes(frame, spans) == want
    assert sum(1 for n in want if n > 0) > 100
    assert sum(1 for n in want[:300] if n < 0) > 30


def test_served_catchup_over_a_dp4_mesh_splices_every_doc():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip('needs 4 devices')
    from test_pool_layout import SLOTS, _requests, reference
    from automerge_tpu.native.mesh_pool import MeshDocPool
    pool = MeshDocPool(dp=4)
    gw = _gateway(pool)
    wires = {'A': Wire(gw, 1)}
    states = {}
    try:
        for k, _parts, docs in _requests():
            frame = pack({'cmd': 'apply_batch', 'id': k + 1,
                          'docs': docs})
            resp = _flush(gw, wires, [('A', frame_request(frame))])[k + 1]
            for d, changes in docs.items():
                states[d], want = reference.apply_changes(
                    states.get(d, reference.init()), changes)
                assert resp['result'][d] == want, (k, d)
    finally:
        wires['A'].close()
    for d, state in states.items():
        assert pool.get_patch(d) == reference.get_patch(state), d
    snap = telemetry.metrics_snapshot()
    assert snap['mesh.batches'] == SLOTS + 1
    assert snap['gateway.request_spliced_docs'] == \
        snap['scheduler.batched_docs'] > 0
    assert snap.get('gateway.request_decoded_docs', 0) == 0
    assert snap['scheduler.result_spliced_docs'] == \
        snap['scheduler.batched_docs']
