"""Chaos suite (ISSUE 4): fault injection x poison isolation x healing.

Three layers under test, matching docs/RESILIENCE.md:

  * the fault MATRIX: every injection site x transient/permanent x both
    execution modes, asserting byte-parity of surviving docs against the
    no-fault run, quarantine accounting, and the retry counters;
  * poison-batch isolation on the sharded pool (a failure stays inside
    its shard, then inside its doc);
  * the self-healing sidecar: crash (SIGKILL and the in-band
    `sidecar.frame` fault) -> respawn -> checkpoint-WAL replay ->
    byte-identical state, plus the serve-loop InternalError catch-all
    and the unix-socket SIGTERM cleanup satellites.
"""

import msgpack
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import pytest

from automerge_tpu import faults, resilience, telemetry
from automerge_tpu.native import NativeDocPool, ShardedNativePool

ROOT_ID = '00000000-0000-0000-0000-000000000000'
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the poison doc the matrix pins permanent faults to
POISON = 'd3'


@pytest.fixture(autouse=True)
def _fault_hygiene():
    """No lane may leak armed specs or counters into the next."""
    faults.disarm()
    telemetry.metrics_reset()
    yield
    faults.disarm()
    telemetry.metrics_reset()


@pytest.fixture(params=['default', 'kernel'])
def exec_mode(request):
    """Both execution modes face every fault lane: the CPU default
    (full host path; device sites are unreachable by construction) and
    the forced kernel path (AMTPU_HOST_REG=0 keeps the hot-key batch on
    the escalation ladder instead of the CPU hostreg shortcut)."""
    if request.param == 'kernel':
        prior = {k: os.environ.get(k)
                 for k in ('AMTPU_HOST_FULL', 'AMTPU_HOST_REG')}
        os.environ['AMTPU_HOST_FULL'] = '0'
        os.environ['AMTPU_HOST_REG'] = '0'
        yield 'kernel'
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    else:
        yield 'default'


def build_docs():
    """Six plain map docs plus one 20-concurrent-writer hot doc, so the
    kernel path exercises dispatch, collect, AND the escalation ladder
    in one batch."""
    docs = {('d%d' % i): [
        {'actor': 'a%d' % i, 'seq': s + 1, 'deps': {},
         'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k%d' % s,
                  'value': s}]}
        for s in range(3)] for i in range(6)}
    docs['hot'] = [
        {'actor': 'w%03d' % a, 'seq': 1, 'deps': {},
         'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                  'value': 'w%03d' % a}]}
        for a in range(20)]
    return docs


def reference_patches():
    """The no-fault run the matrix compares against (per-call fresh
    pool; faults are guaranteed disarmed by the hygiene fixture)."""
    return NativeDocPool().apply_batch(build_docs())


def assert_byte_parity(got, want, skip=()):
    """Per-doc byte parity: every surviving doc's patch must be
    msgpack-byte-identical to the fault-free run."""
    assert set(got) == set(want)
    for doc in want:
        if doc in skip:
            continue
        assert msgpack.packb(got[doc], use_bin_type=True) == \
            msgpack.packb(want[doc], use_bin_type=True), doc


class TestFaultMatrix:
    """Each site x {transient, permanent} x both exec modes."""

    # (site, fires_in): device-path sites cannot fire on the full host
    # path -- those lanes assert the armed-but-unreachable contract
    SITES = [('native.begin', ('default', 'kernel')),
             ('native.mid', ('default', 'kernel')),
             ('device.dispatch', ('kernel',)),
             ('device.collect', ('kernel',)),
             ('escalation.tier', ('kernel',))]

    @pytest.mark.parametrize('site,fires_in',
                             SITES, ids=[s for s, _ in SITES])
    def test_transient_retries_to_parity(self, site, fires_in, exec_mode):
        """Two forced transient faults: the batch must complete with
        results byte-identical to the fault-free run and
        resilience.retry.success >= 1 (the ISSUE-4 acceptance lane)."""
        want = reference_patches()
        telemetry.metrics_reset()
        faults.arm(site, 'transient', 1.0, count=2)
        got = NativeDocPool().apply_batch(build_docs())
        snap = telemetry.metrics_snapshot()
        assert_byte_parity(got, want)
        if exec_mode in fires_in:
            assert snap.get('resilience.fault_injected', 0) == 2, snap
            assert snap.get('resilience.retry.success', 0) >= 1, snap
            assert snap.get('resilience.rollback', 0) >= 2, snap
        else:
            # armed but unreachable in this mode: zero fires, zero cost
            assert snap.get('resilience.fault_injected', 0) == 0, snap
        assert not snap.get('resilience.quarantined'), snap

    @pytest.mark.parametrize('site,fires_in',
                             SITES, ids=[s for s, _ in SITES])
    def test_permanent_quarantines_poison_doc(self, site, fires_in,
                                              exec_mode):
        """A permanent fault pinned to one doc: that doc alone is
        quarantined (per-doc error envelope) and every other doc's
        patch is byte-identical to the fault-free run."""
        if site == 'escalation.tier':
            # no doc scope at the tier dispatch: the hot doc is the only
            # one whose resolution escalates, so an unpinned permanent
            # fault converges on exactly it
            poison, arm_kwargs = 'hot', {}
        else:
            poison, arm_kwargs = POISON, {'match': POISON}
        want = reference_patches()
        telemetry.metrics_reset()
        faults.arm(site, 'permanent', 1.0, **arm_kwargs)
        pool = NativeDocPool()
        got = pool.apply_batch(build_docs())
        snap = telemetry.metrics_snapshot()
        if exec_mode not in fires_in:
            assert_byte_parity(got, want)
            assert snap.get('resilience.fault_injected', 0) == 0, snap
            return
        assert_byte_parity(got, want, skip=(poison,))
        assert resilience.is_quarantined(got[poison]), got[poison]
        assert got[poison]['errorType'] == 'PermanentFault'
        assert snap.get('resilience.quarantined') == 1, snap
        assert snap.get('resilience.bisect.rounds', 0) >= 1, snap
        # nothing of the poison doc committed (rollback accounting)
        faults.disarm()
        assert pool.get_patch(poison)['clock'] == {}
        # ...and the doc heals on a later, fault-free delivery
        healed = pool.apply_changes(poison, build_docs()[poison])
        assert msgpack.packb(healed, use_bin_type=True) == \
            msgpack.packb(want[poison], use_bin_type=True)

    def test_transient_budget_exhaustion_quarantines(self, exec_mode):
        """An unbounded transient fault pinned to one doc exhausts the
        retry budget and degrades into quarantine -- bounded retries,
        not an infinite stall."""
        want = reference_patches()
        telemetry.metrics_reset()
        faults.arm('native.mid', 'transient', 1.0, match=POISON)
        got = NativeDocPool().apply_batch(build_docs())
        snap = telemetry.metrics_snapshot()
        assert_byte_parity(got, want, skip=(POISON,))
        assert resilience.is_quarantined(got[POISON])
        assert snap.get('resilience.retry.exhausted', 0) >= 1, snap
        assert snap.get('resilience.quarantined') == 1, snap

    def test_degraded_path_heals_device_poison(self, exec_mode):
        """AMTPU_DEGRADE=1: a doc whose device path is permanently
        poisoned commits via the full-host path instead of quarantine;
        counted as resilience.degraded, NOT fallback.oracle."""
        want = reference_patches()
        telemetry.metrics_reset()
        faults.arm('device.dispatch', 'permanent', 1.0, match=POISON)
        os.environ['AMTPU_DEGRADE'] = '1'
        try:
            got = NativeDocPool().apply_batch(build_docs())
        finally:
            os.environ.pop('AMTPU_DEGRADE', None)
        snap = telemetry.metrics_snapshot()
        assert_byte_parity(got, want)
        if exec_mode == 'kernel':
            assert snap.get('resilience.degraded') == 1, snap
            assert not snap.get('resilience.quarantined'), snap
        assert not snap.get('fallback.oracle'), snap

    def test_device_error_raises_untouched(self, monkeypatch):
        """A failing device is no doc's poison: a JaxRuntimeError from
        the kernel fails the batch, and nothing is retried, quarantined
        or degraded around it (AMTPU_DEGRADE=1 on, to show it is not
        taken)."""
        from jax.errors import JaxRuntimeError
        from automerge_tpu.ops import pallas_registers
        calls = []

        def device_fault(*args, **kw):
            calls.append(1)
            raise JaxRuntimeError('INTERNAL: the device halted')
        monkeypatch.setattr(pallas_registers, 'resolve_registers_auto',
                            device_fault)
        monkeypatch.setenv('AMTPU_HOST_FULL', '0')
        monkeypatch.setenv('AMTPU_DEGRADE', '1')
        # two writers per key: the batch needs the register kernel
        docs = {('d%d' % i): [
            {'actor': 'a%d' % a, 'seq': 1, 'deps': {},
             'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'k',
                      'value': a}]} for a in range(2)] for i in range(4)}
        with pytest.raises(JaxRuntimeError):
            NativeDocPool().apply_batch(docs)
        snap = telemetry.metrics_snapshot()
        assert calls == [1], calls
        for k in ('resilience.retry.attempts', 'resilience.quarantined',
                  'resilience.degraded', 'fallback.oracle'):
            assert not snap.get(k), (k, snap)

    def test_checkpoint_load_fault_surfaces_and_clears(self, exec_mode):
        """checkpoint.load faults surface to the caller (the WAL replay
        driver owns the retry policy there); a retry after the fault
        clears restores byte-identical state."""
        src = NativeDocPool()
        want = src.apply_batch(build_docs())
        blobs = {d: src.save(d) for d in build_docs()}
        faults.arm('checkpoint.load', 'transient', 1.0, count=1)
        dst = NativeDocPool()
        with pytest.raises(faults.TransientFault):
            dst.load_batch(blobs)
        assert dst.doc_count() == 0      # nothing half-restored
        dst.load_batch(blobs)            # fault budget spent: clean run
        for d in want:
            assert dst.get_patch(d) == src.get_patch(d)

    def test_env_armed_spec(self, exec_mode):
        """AMTPU_FAULT env syntax arms exactly like the programmatic
        API (the sidecar server subprocess path)."""
        want = reference_patches()
        telemetry.metrics_reset()
        faults.reset('native.begin:transient:1.0:2')
        got = NativeDocPool().apply_batch(build_docs())
        snap = telemetry.metrics_snapshot()
        assert_byte_parity(got, want)
        assert snap.get('resilience.fault_injected', 0) == 2, snap
        assert snap.get('resilience.retry.success', 0) >= 1, snap

    def test_bad_env_spec_raises(self):
        with pytest.raises(ValueError):
            faults.load_env('nonsense')
        with pytest.raises(ValueError):
            faults.load_env('no.such.site:transient:1.0')
        with pytest.raises(ValueError):
            faults.load_env('native.mid:sometimes:1.0')


class TestShardedIsolation:
    @pytest.mark.parametrize('mode', ['pipeline', 'threads'])
    def test_poison_doc_stays_inside_its_shard(self, mode, exec_mode):
        want = reference_patches()
        telemetry.metrics_reset()
        faults.arm('native.mid', 'permanent', 1.0, match=POISON)
        sp = ShardedNativePool(n_shards=4, mode=mode)
        got = sp.apply_batch(build_docs())
        snap = telemetry.metrics_snapshot()
        assert_byte_parity(got, want, skip=(POISON,))
        assert resilience.is_quarantined(got[POISON])
        assert snap.get('resilience.quarantined') == 1, snap

    @pytest.mark.parametrize('mode', ['pipeline', 'threads'])
    def test_transient_shard_failure_retries_to_parity(self, mode,
                                                       exec_mode):
        want = reference_patches()
        telemetry.metrics_reset()
        faults.arm('native.begin', 'transient', 1.0, count=1)
        sp = ShardedNativePool(n_shards=4, mode=mode)
        got = sp.apply_batch(build_docs())
        snap = telemetry.metrics_snapshot()
        assert_byte_parity(got, want)
        assert snap.get('resilience.retry.success', 0) >= 1, snap

    def test_validation_error_preempts_isolation_atomically(self,
                                                            exec_mode):
        """A begin-level validation error fires before any injected
        fault, so isolation never starts: the whole batch raises AND
        (via rollback) commits nothing -- after dropping the bad doc,
        the still-armed infra fault isolates normally."""
        docs = build_docs()
        docs['bad'] = [{'actor': 'X', 'seq': 1, 'deps': {},
                        'ops': [{'action': 'set', 'obj': 'nonexistent',
                                 'key': 'k', 'value': 1}]}]
        want = reference_patches()
        faults.arm('native.mid', 'permanent', 1.0, match=POISON)
        pool = NativeDocPool()
        from automerge_tpu.errors import AutomergeError
        with pytest.raises(AutomergeError, match='unknown object'):
            pool.apply_batch(docs)
        assert pool.get_patch('d0')['clock'] == {}   # nothing committed
        del docs['bad']
        telemetry.metrics_reset()
        got = pool.apply_batch(docs)
        assert_byte_parity(got, want, skip=(POISON,))
        assert resilience.is_quarantined(got[POISON])
        assert telemetry.metrics_snapshot().get(
            'resilience.quarantined') == 1

    def test_protocol_errors_still_raise(self, exec_mode):
        """Validation errors are NOT infrastructure faults: the
        whole-batch raise contract survives the resilience layer."""
        from automerge_tpu.errors import AutomergeError
        pool = NativeDocPool()
        pool.apply_changes('d', [{'actor': 'A', 'seq': 1, 'deps': {},
                                  'ops': [{'action': 'set', 'obj': ROOT_ID,
                                           'key': 'k', 'value': 1}]}])
        with pytest.raises(AutomergeError):
            pool.apply_changes('d', [{'actor': 'A', 'seq': 1, 'deps': {},
                                      'ops': [{'action': 'set',
                                               'obj': ROOT_ID,
                                               'key': 'k',
                                               'value': 'other'}]}])


# ---------------------------------------------------------------------------
# sidecar chaos
# ---------------------------------------------------------------------------

CHS = [
    {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
        {'action': 'set', 'obj': ROOT_ID, 'key': 'bird',
         'value': 'magpie'}]},
    {'actor': 'b', 'seq': 1, 'deps': {'a': 1}, 'ops': [
        {'action': 'makeText', 'obj': 't1'},
        {'action': 'ins', 'obj': 't1', 'key': '_head', 'elem': 1},
        {'action': 'set', 'obj': 't1', 'key': 'b:1', 'value': 'x'},
        {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
         'value': 't1'}]},
]


def _client(**kw):
    from automerge_tpu.sidecar.client import SidecarClient
    return SidecarClient(**kw)


def _uninterrupted_patch():
    with _client() as ref:
        for ch in CHS:
            ref.apply_changes('doc1', [ch])
        return ref.get_patch('doc1')


class TestSidecarSelfHealing:
    def test_sigkill_respawn_replays_wal(self):
        """The ISSUE-4 acceptance lane: SIGKILL mid-session, then a
        subsequent get_patch returns the same patch as an uninterrupted
        session, and healthz exposes the restart count."""
        want = _uninterrupted_patch()
        c = _client()
        try:
            for ch in CHS:
                c.apply_changes('doc1', [ch])
            os.kill(c._proc.pid, signal.SIGKILL)
            time.sleep(0.2)
            assert c.get_patch('doc1') == want
            hz = c.healthz()
            assert hz['restarts'] == 1
            assert c.restarts == 1
            # the healed session keeps working (and keeps its WAL)
            assert c.get_missing_deps('doc1') == {}
        finally:
            c.close()
        # process tree clean: the respawned server is reaped
        assert c._proc is None or c._proc.returncode is not None

    def test_frame_fault_crashes_server_and_client_heals(self):
        """`sidecar.frame` armed in the SERVER via the environment: the
        first request kills the serve loop (simulated crash); the
        client respawns (clean env) and the retried request succeeds."""
        want = _uninterrupted_patch()
        os.environ['AMTPU_FAULT'] = 'sidecar.frame:transient:1.0:1'
        try:
            c = _client()
        finally:
            # respawned servers must NOT re-arm, or the heal loop spins
            os.environ.pop('AMTPU_FAULT', None)
        try:
            for ch in CHS:
                c.apply_changes('doc1', [ch])
            assert c.restarts == 1
            assert c.get_patch('doc1') == want
        finally:
            c.close()

    def test_wal_compaction_round_trip(self):
        """State replays correctly through a compacted WAL (snapshots +
        residual log), not just a raw log."""
        from automerge_tpu.sidecar.client import CheckpointWAL
        want = _uninterrupted_patch()
        c = _client(wal=CheckpointWAL(compact_every=1))
        try:
            for ch in CHS:
                c.apply_changes('doc1', [ch])
            assert c._wal.snapshots         # compaction actually ran
            os.kill(c._proc.pid, signal.SIGKILL)
            time.sleep(0.2)
            assert c.get_patch('doc1') == want
        finally:
            c.close()

    def test_heal_requires_owned_server(self):
        """Satellite: heal means respawning from OUR spawn recipe --
        adopted-process and socket clients must refuse it loudly
        instead of recording a WAL that can never replay."""
        proc = subprocess.Popen(
            [sys.executable, '-m', 'automerge_tpu.sidecar.server'],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO)
        try:
            with pytest.raises(ValueError, match='self-spawned'):
                _client(proc=proc, heal=True)
        finally:
            proc.kill()
            proc.wait(timeout=10)

    def test_dead_client_refuses_reuse(self):
        """Satellite: after an unhealed ConnectionError the client is
        dead -- reuse raises a clear error instead of desyncing ids."""
        c = _client(heal=False)
        try:
            c.apply_changes('d', [CHS[0]])
            os.kill(c._proc.pid, signal.SIGKILL)
            time.sleep(0.2)
            with pytest.raises(ConnectionError):
                c.get_patch('d')
            with pytest.raises(ConnectionError, match='dead'):
                c.get_patch('d')
        finally:
            c.close()

    def test_internal_error_envelope_keeps_loop_alive(self):
        """Satellite: an unexpected exception out of the pool answers
        the InternalError envelope and bumps sidecar.internal_errors;
        the serve loop (and the pool) survives."""
        from automerge_tpu.sidecar.server import SidecarBackend

        class WoundedPool:
            def __init__(self):
                self.real = NativeDocPool()

            def apply_batch(self, docs):
                raise RuntimeError('XLA ate the batch')

            def __getattr__(self, name):
                return getattr(self.real, name)

        telemetry.metrics_reset()
        backend = SidecarBackend(pool=WoundedPool())
        resp = backend.handle({'id': 7, 'cmd': 'apply_batch',
                               'docs': {'d': [CHS[0]]}})
        assert resp['errorType'] == 'InternalError'
        assert 'XLA ate the batch' in resp['error']
        assert telemetry.metrics_snapshot().get(
            'sidecar.internal_errors') == 1
        # the loop survives: the next request answers normally
        assert backend.handle({'id': 8, 'cmd': 'ping'})['result'] == \
            {'ok': True}

    def test_quarantine_envelope_crosses_the_protocol(self):
        """A permanently poisoned doc surfaces as the per-doc error
        envelope in the apply_batch RESPONSE, and healthz reports the
        degraded/quarantine state."""
        os.environ['AMTPU_FAULT'] = 'native.mid:permanent:1.0'
        try:
            c = _client()
        finally:
            os.environ.pop('AMTPU_FAULT', None)
        try:
            got = c.apply_batch({'d1': [CHS[0]]})
            assert resilience.is_quarantined(got['d1']), got
            hz = c.healthz()
            assert hz['degraded'] is True
            assert hz['resilience']['quarantined'] >= 1
        finally:
            c.close()

    def test_unix_socket_sigterm_unlinks_socket(self):
        """Satellite: SIGTERM closes the listener and unlinks the
        socket path, so a supervised restart never hits 'address
        already in use'."""
        path = os.path.join(tempfile.mkdtemp(), 'amtpu-chaos.sock')
        env = dict(os.environ, PYTHONPATH=REPO)
        proc = subprocess.Popen(
            [sys.executable, '-m', 'automerge_tpu.sidecar.server',
             '--socket', path], env=env, cwd=REPO)
        try:
            for _ in range(200):
                if os.path.exists(path):
                    break
                time.sleep(0.1)
            assert os.path.exists(path)
            proc.terminate()                  # SIGTERM, not SIGKILL
            assert proc.wait(timeout=20) == 128 + signal.SIGTERM
            assert not os.path.exists(path)
            # the next incarnation binds immediately (no stale socket)
            srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                srv.bind(path)
            finally:
                srv.close()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
