"""Checkpoint/resume: save() dumps the application-order change history,
load() restores it as ONE batched replay (the reference's save/load is the
same log-replay model, `/root/reference/src/automerge.js:10-17,45-52`, but
scalar; round-tripping must reproduce the document byte-identically).
"""

import random

import pytest

from automerge_tpu.errors import RangeError
from automerge_tpu.native import NativeDocPool, ShardedNativePool
from tests.conftest import LanePool

ROOT = '00000000-0000-0000-0000-000000000000'


def build_history(pool, doc='d', seed=3):
    rng = random.Random(seed)
    pool.apply_changes(doc, [
        {'actor': 'A', 'seq': 1, 'deps': {},
         'ops': [{'action': 'makeText', 'obj': 'T'},
                 {'action': 'ins', 'obj': 'T', 'key': '_head', 'elem': 1},
                 {'action': 'set', 'obj': 'T', 'key': 'A:1', 'value': 'x'},
                 {'action': 'link', 'obj': ROOT, 'key': 'text',
                  'value': 'T'}]}])
    # interleaved concurrent edits from two actors, applied in a specific
    # order (replay must preserve it for byte-identical materialization)
    for seq in range(1, 6):
        for actor in ('B', 'C'):
            elem = 10 * seq + (1 if actor == 'B' else 2)
            pool.apply_changes(doc, [
                {'actor': actor, 'seq': seq, 'deps': {'A': 1},
                 'ops': [{'action': 'ins', 'obj': 'T', 'key': 'A:1',
                          'elem': elem},
                         {'action': 'set', 'obj': 'T',
                          'key': '%s:%d' % (actor, elem),
                          'value': chr(97 + seq)},
                         {'action': 'set', 'obj': ROOT,
                          'key': 'k%d' % rng.randrange(3),
                          'value': seq}]}])


@pytest.mark.parametrize('pool_lane', ['host', 'kernel', 'sharded'],
                         indirect=True)
def test_save_load_round_trip(pool_lane):
    pool = pool_lane()
    build_history(pool)
    want = pool.get_patch('d')
    blob = pool.save('d')
    assert isinstance(blob, bytes)

    fresh = pool_lane()
    patch = fresh.load('d2', blob)
    assert patch == want
    assert fresh.get_patch('d2') == want
    # the restored doc keeps full semantics: history ships, edits apply
    assert fresh.get_missing_changes('d2', {}) \
        == pool.get_missing_changes('d', {})
    fresh.apply_changes('d2', [
        {'actor': 'B', 'seq': 6, 'deps': {'B': 5},
         'ops': [{'action': 'set', 'obj': ROOT, 'key': 'post',
                  'value': 1}]}])
    assert fresh.get_clock('d2')['clock']['B'] == 6


def test_checkpoints_are_cross_pool_compatible():
    """A checkpoint saved on the chip's kernel path restores on the CPU
    default and vice versa (one wire format)."""
    kpool = LanePool('kernel')
    build_history(kpool)
    blob = kpool.save('d')
    hpool = LanePool('host')
    assert hpool.load('x', blob) == kpool.get_patch('d')

    blob2 = hpool.save('x')
    kpool2 = LanePool('kernel')
    assert kpool2.load('y', blob2) == hpool.get_patch('x')


def test_load_rejects_garbage(pool_lane):
    pool = pool_lane()
    with pytest.raises(RangeError, match='checkpoint'):
        pool.load('d', b'\x81\xa1x\x01')


def test_empty_doc_round_trips():
    pool = NativeDocPool()
    blob = pool.save('never-touched')
    fresh = NativeDocPool()
    patch = fresh.load('d', blob)
    assert patch['diffs'] == [] and patch['clock'] == {}


def test_load_batch_restores_many_docs_in_one_pass():
    pool = NativeDocPool()
    for d in ('a', 'b', 'c'):
        build_history(pool, doc=d, seed=ord(d))
    blobs = {d: pool.save(d) for d in ('a', 'b', 'c')}
    fresh = ShardedNativePool(n_shards=2)
    fresh.load_batch(blobs)
    for d in ('a', 'b', 'c'):
        assert fresh.get_patch(d) == pool.get_patch(d)
    with pytest.raises(RangeError, match='checkpoint'):
        fresh.load_batch({'x': b'garbage'})


@pytest.mark.parametrize('garbage', [b'\x90', b'garbage', b'\xc0'])
def test_load_rejects_all_malformed_shapes(pool_lane, garbage):
    with pytest.raises(RangeError, match='checkpoint'):
        pool_lane().load('d', garbage)


def test_sidecar_save_load_survives_json_framing():
    """Checkpoints are binary; the sidecar base64-wraps them so the
    default JSON-lines framing can carry them round trip."""
    import io
    import json as _json
    from automerge_tpu.sidecar.server import SidecarBackend, serve_stream
    reqs = [
        {'id': 1, 'cmd': 'apply_changes', 'doc': 'd', 'changes': [
            {'actor': 'A', 'seq': 1, 'deps': {},
             'ops': [{'action': 'set', 'obj': ROOT, 'key': 'k',
                      'value': 7}]}]},
        {'id': 2, 'cmd': 'save', 'doc': 'd'},
    ]
    rfile = io.BytesIO(('\n'.join(_json.dumps(r) for r in reqs))
                       .encode() + b'\n')
    wfile = io.BytesIO()
    backend = SidecarBackend()
    serve_stream(rfile, wfile, use_msgpack=False, backend=backend)
    lines = [_json.loads(x) for x in wfile.getvalue().splitlines()]
    assert all('error' not in r for r in lines), lines
    blob = lines[1]['result']['checkpoint_b64']
    # restore through the same JSON framing into a fresh doc
    req3 = {'id': 3, 'cmd': 'load', 'doc': 'd2', 'data': blob}
    rfile = io.BytesIO((_json.dumps(req3) + '\n').encode())
    wfile = io.BytesIO()
    serve_stream(rfile, wfile, use_msgpack=False, backend=backend)
    out = _json.loads(wfile.getvalue().splitlines()[0])
    assert 'error' not in out, out
    assert out['result'] == backend.pool.get_patch('d')
    # malformed base64 maps to a protocol error, not a crashed loop
    req4 = {'id': 4, 'cmd': 'load', 'doc': 'd3', 'data': '!!not-base64!!'}
    rfile = io.BytesIO((_json.dumps(req4) + '\n').encode())
    wfile = io.BytesIO()
    serve_stream(rfile, wfile, use_msgpack=False, backend=backend)
    out = _json.loads(wfile.getvalue().splitlines()[0])
    assert out.get('errorType') == 'RangeError'
