"""A flush's results as the pool's own bytes (docs/SERVING.md, result
path): the gateway splices each doc's patch into the response frame and
decodes a doc only where something reads it.  Every response must decode
to what the serial backend answers for the same changes."""

import json
import os
import socket
import struct
import tempfile

import msgpack
import pytest

from automerge_tpu import faults, telemetry
from automerge_tpu.native import NativeDocPool, ShardedNativePool
from automerge_tpu.resilience import error_envelope
from automerge_tpu.scheduler import GatewayServer
from automerge_tpu.scheduler.gateway import _Conn
from automerge_tpu.sidecar.client import SidecarClient
from automerge_tpu.sidecar.server import SidecarBackend
from automerge_tpu.sync.fanout import FanoutEngine
from automerge_tpu.utils import patch_map
from automerge_tpu.utils.patch_map import PatchMap, byte_results

ROOT_ID = '00000000-0000-0000-0000-000000000000'


@pytest.fixture(autouse=True)
def _hygiene():
    # reset_all, not metrics_reset: a flush also observes the batch
    # occupancy histogram, which a later file's test counts from zero
    faults.disarm()
    telemetry.reset_all()
    yield
    faults.disarm()
    telemetry.reset_all()


def text_change(actor, seq, chars):
    """Round 1 makes a Text object and types `chars`; later rounds
    insert after the previous round's last character and delete the
    first one of it."""
    ops = []
    if seq == 1:
        ops += [{'action': 'makeText', 'obj': 'txt'},
                {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
                 'value': 'txt'}]
        prev = '_head'
    else:
        prev = '%s:%d' % (actor, (seq - 1) * 10 + len(chars))
        ops.append({'action': 'del', 'obj': 'txt',
                    'key': '%s:%d' % (actor, (seq - 1) * 10 + 1)})
    for i, c in enumerate(chars):
        elem = seq * 10 + i + 1
        ops += [{'action': 'ins', 'obj': 'txt', 'key': prev, 'elem': elem},
                {'action': 'set', 'obj': 'txt',
                 'key': '%s:%d' % (actor, elem), 'value': c}]
        prev = '%s:%d' % (actor, elem)
    return {'actor': actor, 'seq': seq, 'deps': {}, 'ops': ops}


def map_change(actor, seq):
    return {'actor': actor, 'seq': seq, 'deps': {},
            'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k%d' % i,
                     'value': '%s-%d-%d' % (actor, seq, i)}
                    for i in range(3)] +
                   ([{'action': 'del', 'obj': ROOT_ID, 'key': 'k0'}]
                    if seq > 1 else [])}


def table_change(actor, seq):
    if seq == 1:
        ops = [{'action': 'makeTable', 'obj': 'tb'},
               {'action': 'link', 'obj': ROOT_ID, 'key': 'rows',
                'value': 'tb'}]
        for r in ('r1', 'r2'):
            ops += [{'action': 'makeMap', 'obj': r},
                    {'action': 'set', 'obj': r, 'key': 'n', 'value': 0},
                    {'action': 'link', 'obj': 'tb', 'key': r, 'value': r}]
    else:
        ops = [{'action': 'set', 'obj': 'r%d' % r, 'key': 'n',
                'value': seq * 10 + r} for r in (1, 2)]
    return {'actor': actor, 'seq': seq, 'deps': {}, 'ops': ops}


def round_requests(seq, poison=None):
    """One flush's requests: two apply_batch ops and two apply_changes
    ops over text, map and table docs, int doc ids among them.  `poison`
    renames one doc so a fault pinned to it quarantines that doc."""
    def name(d):
        return poison if d == 'map-b' and poison else d
    return [
        ('A', {'id': seq * 10 + 1, 'cmd': 'apply_batch',
               'docs': {'text-a': [text_change('ta', seq, 'abc')],
                        name('map-b'): [map_change('mb', seq)],
                        7: [table_change('t7', seq)]}}),
        ('B', {'id': seq * 10 + 2, 'cmd': 'apply_changes', 'doc': 8,
               'changes': [map_change('m8', seq)]}),
        ('B', {'id': seq * 10 + 3, 'cmd': 'apply_changes',
               'doc': 'text-d', 'changes': [text_change('td', seq, 'xy')]}),
        ('A', {'id': seq * 10 + 4, 'cmd': 'apply_batch',
               'docs': {'table-e': [table_change('te', seq)],
                        9: [text_change('t9', seq, 'q')]}}),
    ]


class Wire(object):
    """A real gateway connection over a socketpair: the flush's answers
    go through `_Conn.send` and are read back off the other end."""

    def __init__(self, gw, cid):
        mine, theirs = socket.socketpair()
        self.conn = _Conn(mine, gw, cid)
        self.msgpack = gw.use_msgpack
        self.rfile = theirs.makefile('rb')
        self._theirs = theirs

    def read(self):
        if self.msgpack:
            (n,) = struct.unpack('>I', self.rfile.read(4))
            return msgpack.unpackb(self.rfile.read(n), raw=False,
                                   strict_map_key=False)
        return json.loads(self.rfile.readline())

    def close(self):
        self.conn.close()
        self.rfile.close()
        self._theirs.close()


def _gateway(use_msgpack=True):
    path = os.path.join(tempfile.mkdtemp(), 'gw.sock')
    return GatewayServer(path, use_msgpack=use_msgpack)


def _flush_round(gw, wires, reqs):
    """Submits one round's requests and runs them as ONE coalesced
    flush; returns {rid: decoded response off the wire}."""
    for who, req in reqs:
        gw.submit(wires[who].conn, req)
    batch, execs = gw.queue.claim()
    assert len(batch) == len(reqs) and not execs
    gw._flush(batch, execs)
    got = {}
    for who, req in reqs:
        resp = wires[who].read()
        got[resp['id']] = resp
    return got


def _as_wire(obj, use_msgpack):
    """`obj` as the framing carries it (JSON turns int keys to str)."""
    if use_msgpack:
        return obj
    return json.loads(json.dumps(obj))


def _n_docs(reqs):
    return sum(len(r['docs']) if r['cmd'] == 'apply_batch' else 1
               for _, r in reqs)


@pytest.mark.parametrize('framing', ['msgpack', 'jsonl'])
def test_coalesced_flush_answers_as_serial(framing):
    use_msgpack = framing == 'msgpack'
    gw = _gateway(use_msgpack)
    wires = {'A': Wire(gw, 1), 'B': Wire(gw, 2)}
    serial = SidecarBackend(pool=NativeDocPool())
    try:
        for seq in (1, 2):
            reqs = round_requests(seq)
            got = _flush_round(gw, wires, reqs)
            for _, req in reqs:
                want = serial.handle(dict(req))
                assert 'result' in want, want
                assert got[req['id']] == _as_wire(want, use_msgpack), req
    finally:
        for w in wires.values():
            w.close()
    snap = telemetry.metrics_snapshot()
    n = 2 * _n_docs(round_requests(1))
    if use_msgpack:
        assert snap['scheduler.result_spliced_docs'] == n
        assert snap.get('scheduler.result_decoded_docs', 0) == 0
    else:
        # JSONL has no byte form: every doc decodes, none splices
        assert snap['scheduler.result_decoded_docs'] == n
        assert snap.get('scheduler.result_spliced_docs', 0) == 0


@pytest.mark.parametrize('where', ['apply_batch', 'apply_changes'])
def test_quarantined_doc_splices_its_envelope(where):
    gw = _gateway()
    wires = {'A': Wire(gw, 1), 'B': Wire(gw, 2)}
    seen = []
    apply = gw.backend.pool.apply_batch

    def spy(changes_by_doc):
        out = apply(changes_by_doc)
        seen.append(out)
        return out
    gw.backend.pool.apply_batch = spy
    poison = 'poison' if where == 'apply_batch' else 'text-d'
    reqs = round_requests(1, poison=poison if where == 'apply_batch'
                          else None)
    faults.arm('native.begin', 'permanent', 1.0, match=poison)
    try:
        got = _flush_round(gw, wires, reqs)
    finally:
        faults.disarm()
        for w in wires.values():
            w.close()
    (out,) = seen
    assert isinstance(out, PatchMap)
    assert out.quarantined == {poison}
    snap = telemetry.metrics_snapshot()
    assert snap['scheduler.quarantined'] == 1
    n = _n_docs(reqs)
    if where == 'apply_batch':
        env = got[11]['result']['poison']
        assert env['errorType'] == 'PermanentFault' and 'clock' not in env
        # the envelope and every sibling went out as the pool's bytes
        assert out.n_decoded == 0
        assert snap['scheduler.result_spliced_docs'] == n
    else:
        assert got[13]['errorType'] == 'PermanentFault'
        assert 'result' not in got[13]
        # only the error answer read its envelope
        assert out.n_decoded == 1
        assert snap['scheduler.result_decoded_docs'] == 1
        assert snap['scheduler.result_spliced_docs'] == n - 1
    healthy = [got[12]['result']] + [
        p for rid in (11, 14) for d, p in got[rid]['result'].items()
        if d != poison]
    if where == 'apply_batch':
        healthy.append(got[13]['result'])
    assert len(healthy) == n - 1
    assert all('clock' in p for p in healthy)


def test_edits_through_values_reach_the_response():
    """A wrapper that edits `diff['value']` through `.values()` (as the
    benchmark's planted `altered` fault does) edits the cached decode,
    which the frame then encodes in place of the pool's bytes."""
    gw = _gateway()
    wires = {'A': Wire(gw, 1), 'B': Wire(gw, 2)}
    apply = gw.backend.pool.apply_batch

    def altered(changes_by_doc):
        out = apply(changes_by_doc)
        for res in out.values():
            for diff in res.get('diffs') or ():
                if 'value' in diff:
                    diff['value'] = ['altered', diff['value']]
                    break
        return out
    gw.backend.pool.apply_batch = altered
    reqs = round_requests(1)
    try:
        got = _flush_round(gw, wires, reqs)
    finally:
        for w in wires.values():
            w.close()
    patches = [got[12]['result'], got[13]['result']] + \
        list(got[11]['result'].values()) + list(got[14]['result'].values())
    assert len(patches) == _n_docs(reqs)
    for p in patches:
        first = next(d for d in p['diffs'] if 'value' in d)
        assert first['value'][0] == 'altered', p
    snap = telemetry.metrics_snapshot()
    assert snap['scheduler.result_decoded_docs'] == len(patches)
    assert snap.get('scheduler.result_spliced_docs', 0) == 0


def test_patch_subscriber_decodes_only_its_doc():
    """A patch-mode subscriber on one doc of an eight-doc flush gets its
    patch; only that doc is decoded, the other seven go out as bytes."""
    path = os.path.join(tempfile.mkdtemp(), 'gw.sock')
    gw = GatewayServer(path, use_msgpack=True).start()
    docs = ['d%02d' % i for i in range(8)]
    try:
        with SidecarClient(sock_path=path, use_msgpack=True) as writer, \
                SidecarClient(sock_path=path, use_msgpack=True) as sub:
            writer.apply_batch({d: [map_change('w', 1)] for d in docs})
            sub.subscribe('d03', clock={'w': 1}, mode='patch')
            res = writer.apply_batch({d: [map_change('w', 2)]
                                      for d in docs})
            ev = sub.next_event(timeout=10)
            assert ev is not None and ev['event'] == 'patch'
            assert ev['doc'] == 'd03'
            assert ev['patch']['clock'] == {'w': 2}
            assert ev['patch']['diffs'] == res['d03']['diffs']
    finally:
        gw.stop()       # joins the dispatcher: every flush has counted
    snap = telemetry.metrics_snapshot()
    assert snap['scheduler.result_decoded_docs'] == 1
    assert snap['scheduler.result_spliced_docs'] == 2 * len(docs)


def test_fanout_tracks_rows_presence_and_prefixes():
    sent = []
    engine = FanoutEngine(NativeDocPool(), lambda obj: sent.append(obj))
    assert engine.tracked(['a', 'ws/1', 7]) == set()
    engine.subscribe(('c', 'p'), 'a', {}, lambda buf: None,
                     backfill=False)
    engine.presence(('c', 'p'), 'b', {'cursor': 1})
    engine.subscribe_prefix(('c', 'q'), 'ws/', lambda buf: None)
    assert engine.tracked(['a', 'b', 'ws/1', 'x', 7]) == {'a', 'b', 'ws/1'}


@pytest.mark.parametrize('make_pool', [
    NativeDocPool, lambda: ShardedNativePool(n_shards=2)],
    ids=['native', 'sharded'])
def test_patch_map_alone(make_pool):
    batch = {'text-a': [text_change('ta', 1, 'hey')],
             7: [table_change('t7', 1)],
             'map-b': [map_change('mb', 1)]}
    plain = make_pool().apply_batch(batch)
    pool = make_pool()
    with byte_results():
        pm = pool.apply_batch(batch)
    # outside the block every caller still gets decoded dicts
    assert type(plain) is dict
    assert type(make_pool().apply_batch(batch)) is dict
    assert isinstance(pm, PatchMap)
    assert len(pm) == 3 and list(pm) == ['text-a', 7, 'map-b']
    assert 7 in pm and 'i:7' not in pm and pm.n_decoded == 0
    assert not pm.quarantined
    body, raw = pm.packed('map-b')
    assert raw and msgpack.unpackb(bytes(body), raw=False) == plain['map-b']
    assert pm == plain and pm.n_decoded == 3
    first = pm['text-a']
    assert pm['text-a'] is first
    first['clock']['edited'] = 1
    body, raw = pm.packed('text-a')
    assert not raw
    assert msgpack.unpackb(body, raw=False)['clock']['edited'] == 1
    with pytest.raises(KeyError):
        pm['missing']


def test_envelope_head_matches_the_resilience_envelope():
    for exc in (RuntimeError('boom'), faults.InjectedFault('x'),
                ValueError('')):
        packed = msgpack.packb(error_envelope(exc), use_bin_type=True)
        assert packed.startswith(patch_map._ENVELOPE_HEAD)


def test_view_responses_pack_like_dicts():
    """`pack_body` of a response holding views decodes to the same
    object `packb` gives for the decoded dicts."""
    batch = {'a': [map_change('x', 1)], 5: [map_change('y', 1)]}
    with byte_results():
        pm = NativeDocPool().apply_batch(batch)
    plain = NativeDocPool().apply_batch(batch)
    for resp, want in (
            ({'id': 1, 'result': patch_map.SubMap(pm, ('a', 5))},
             {'id': 1, 'result': plain}),
            ({'id': 2, 'result': patch_map.DocResult(pm, 5)},
             {'id': 2, 'result': plain[5]}),
            ({'id': 3, 'error': 'e'}, {'id': 3, 'error': 'e'})):
        parts, _ = patch_map.pack_body(resp)
        got = msgpack.unpackb(b''.join(parts), raw=False,
                              strict_map_key=False)
        assert got == want
        assert json.loads(json.dumps(resp, default=patch_map.plain)) == \
            json.loads(json.dumps(want))
