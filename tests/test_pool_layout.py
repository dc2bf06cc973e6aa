"""The pool's layout comes from the chips the process holds
(docs/SERVING.md, pool layout): `make_pool` builds a doc-sharded
`MeshDocPool` with one chip pool per chip on a multi-chip TPU process,
the single `NativeDocPool` on one chip or on the CPU, and AMTPU_MESH
overrides either way.  The device probe is stubbed; the served catch-up
runs a real dp=4 mesh on the suite's virtual CPU devices, through the
gateway, against the benchmark's frozen reference backend."""

import os
import socket
import struct
import sys
import tempfile

import msgpack
import pytest

from automerge_tpu import native, telemetry
from automerge_tpu.native import NativeDocPool, make_pool
from automerge_tpu.native.mesh_pool import MeshDocPool
from automerge_tpu.scheduler import GatewayServer
from automerge_tpu.scheduler.gateway import _Conn
from automerge_tpu.sidecar.server import SidecarBackend

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from doctypes import text as text_doctype  # noqa: E402
from reference import backend as reference  # noqa: E402

TPU4 = ('tpu', 'TPU v5 lite', 4)
TPU1 = ('tpu', 'TPU v5 lite', 1)
CPU8 = ('cpu', 'cpu', 8)


@pytest.fixture
def held(monkeypatch):
    """Stubs the device probe: `held(devices)` makes the process look as
    if it held them; AMTPU_MESH starts unset."""
    monkeypatch.delenv('AMTPU_MESH', raising=False)
    monkeypatch.setattr(native, '_layouts_stated', set())

    def hold(devices):
        monkeypatch.setattr(native, '_devices_held', lambda: devices)
    return hold


@pytest.mark.parametrize('devices, want', [
    (TPU4, (MeshDocPool, 4)),
    (TPU1, (NativeDocPool, 1)),
    (CPU8, (NativeDocPool, 1)),
    (('tpu', 'TPU v5 lite', 2), (MeshDocPool, 2)),
])
def test_make_pool_takes_the_chips_held(held, devices, want):
    held(devices)
    pool = make_pool()
    assert type(pool) is want[0]
    assert getattr(pool, 'dp', 1) == want[1]


@pytest.mark.parametrize('devices, mesh, want', [
    (TPU1, '4', (MeshDocPool, 4, 1)),
    (CPU8, '2,4', (MeshDocPool, 2, 4)),
    (TPU4, '0', (NativeDocPool, 1, 1)),
    (TPU4, '', (NativeDocPool, 1, 1)),
    (TPU4, '2', (MeshDocPool, 2, 1)),
])
def test_amtpu_mesh_overrides_the_chips(held, monkeypatch, devices, mesh,
                                        want):
    held(devices)
    monkeypatch.setenv('AMTPU_MESH', mesh)
    pool = make_pool()
    assert type(pool) is want[0]
    assert (getattr(pool, 'dp', 1), getattr(pool, 'sp', 1)) == want[1:]


def test_no_backend_yet_counts_the_chips_at_first_use(held, monkeypatch):
    """A process that has not started JAX gets a pool that counts the
    chips only when something first uses it."""
    held(None)
    counted = []

    def count():
        counted.append(1)
        return TPU4
    monkeypatch.setattr(native, '_count_devices', count)
    pool = make_pool()
    assert counted == []
    assert pool.dp == 4
    assert counted == [1]
    assert type(object.__getattribute__(pool, '_built')) is MeshDocPool
    pool.apply_batch = 'replaced'
    assert object.__getattribute__(pool, '_built').apply_batch == 'replaced'
    assert counted == [1]


def test_probe_counts_a_started_backend():
    import jax
    jax.devices()
    assert native._devices_held() == ('cpu', 'cpu', len(jax.devices()))


def test_single_pool_states_its_layout_once(held, capsys):
    telemetry.metrics_reset()
    held(TPU1)
    make_pool()
    make_pool()
    err = capsys.readouterr().err
    assert err.count('[pool] NativeDocPool dp=1 on 1 x TPU v5 lite (tpu)') \
        == 1, err
    assert telemetry.metrics_snapshot().get('pool.chips') == 1


def test_mesh_states_its_layout_at_first_use(held, capsys):
    import jax
    telemetry.metrics_reset()
    held(TPU4)
    pool = make_pool()
    assert '[pool]' not in capsys.readouterr().err
    pool.pools
    err = capsys.readouterr().err
    assert err.count('[pool] MeshDocPool dp=4 on %d x cpu (cpu)'
                     % len(jax.devices())) == 1, err
    assert telemetry.metrics_snapshot().get('pool.chips') == 4


# -- a served catch-up over a dp=4 mesh ------------------------------------

CFG = {'docs': 64, 'actors_per_doc': 16, 'ops_per_change': 6,
       'delete_share': 0.15}
SLOTS = 4
SEED = 2 ** 33 + 25


def _slot(s):
    n = CFG['docs'] // SLOTS
    return range(s * n, (s + 1) * n)


def _requests():
    """The benchmark's catch-up stream at a small size: request k brings
    slot k its first round and slot k - 1 its second."""
    for k in range(SLOTS + 1):
        parts = [(s, r) for s, r in ((k, 1), (k - 1, 2)) if 0 <= s < SLOTS]
        yield k, parts, {
            text_doctype.doc_id(i): text_doctype.round_changes(
                CFG, SEED, i, r)
            for s, r in parts for i in _slot(s)}


def _read(rfile):
    (n,) = struct.unpack('>I', rfile.read(4))
    return msgpack.unpackb(rfile.read(n), raw=False, strict_map_key=False)


def test_served_catchup_over_a_dp4_mesh_matches_the_reference():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip('needs 4 devices')
    telemetry.metrics_reset()
    pool = MeshDocPool(dp=4)
    gw = GatewayServer(os.path.join(tempfile.mkdtemp(), 'gw.sock'),
                       use_msgpack=True, backend=SidecarBackend(pool=pool))
    mine, theirs = socket.socketpair()
    conn = _Conn(mine, gw, 1)
    rfile = theirs.makefile('rb')
    states = {}
    answered = 0
    try:
        for k, parts, docs in _requests():
            gw.submit(conn, {'cmd': 'apply_batch', 'id': k + 1,
                             'docs': docs})
            batch, execs = gw.queue.claim()
            gw._flush(batch, execs)
            resp = _read(rfile)
            assert resp['id'] == k + 1 and 'result' in resp, resp
            for d, changes in docs.items():
                states[d], want = reference.apply_changes(
                    states.get(d, reference.init()), changes)
                assert resp['result'][d] == want, (k, d)
            answered += len(docs)
    finally:
        conn.close()
        rfile.close()
        theirs.close()
    for d, state in states.items():
        assert pool.get_patch(d) == reference.get_patch(state), d
    snap = telemetry.metrics_snapshot()
    assert snap['scheduler.result_spliced_docs'] == answered
    assert snap.get('scheduler.result_decoded_docs', 0) == 0
    assert snap['mesh.batches'] == SLOTS + 1
    assert snap['mesh.shards'] == 4 * snap['mesh.batches']
    assert snap.get('fallback.oracle', 0) == 0
