"""Fleet-failover tests (ISSUE 19, docs/RESILIENCE.md fleet
degradation tiers): the per-member health state machine, deterministic
chaos lanes driven through the `router.heartbeat` fault site
(permanent -> up/suspect/dead/failover with history intact; transient
-> suspect/recover, no failover), write-through restore onto ring
survivors, parked-frame park/replay/fail semantics with the typed
ReplicaUnavailable / ReplicaFailed envelopes, park expiry, and the
placement-journal router restart (post-failover placement survives
byte-identically).
"""

import json
import os
import socket
import threading
import time

import pytest

from automerge_tpu import faults, telemetry
from automerge_tpu.errors import (ReplicaFailedError,
                                  ReplicaUnavailableError)
from automerge_tpu.router import (FailoverExecutor, HealthMonitor,
                                  RouterGateway)
from automerge_tpu.scheduler import GatewayServer
from automerge_tpu.sidecar.client import SidecarClient
from automerge_tpu.sidecar.server import SidecarBackend
from automerge_tpu.storage.coldstore import ColdStore

ROOT_ID = '00000000-0000-0000-0000-000000000000'


@pytest.fixture(autouse=True)
def _hygiene():
    telemetry.reset_all()
    faults.disarm()
    os.environ['AMTPU_FLUSH_DEADLINE_MS'] = '5'
    yield
    del os.environ['AMTPU_FLUSH_DEADLINE_MS']
    faults.disarm()
    telemetry.reset_all()


def change(actor, seq, key='k', value=None):
    return {'actor': actor, 'seq': seq, 'deps': {},
            'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': key,
                     'value': value if value is not None
                     else '%s-%d' % (actor, seq)}]}


def _flat():
    return telemetry.metrics_snapshot()


def _poll(cond, deadline_s=10.0, what='condition'):
    deadline = time.time() + deadline_s
    while not cond():
        assert time.time() < deadline, 'timed out on %s' % what
        time.sleep(0.02)


class Fleet(object):
    """N in-process replica gateways (each with its own write-through
    sync store, as a supervised subprocess fleet would get from
    AMTPU_STORAGE_SYNC) + one router."""

    def __init__(self, tmp, n=2, journal=False):
        self.replicas = {}
        self.gateways = {}
        self.stores = {}
        for i in range(n):
            rid = 'r%d' % i
            path = str(tmp / (rid + '.sock'))
            store = str(tmp / ('store-' + rid))
            self.stores[rid] = store
            self.gateways[rid] = GatewayServer(
                path, backend=SidecarBackend(),
                sync_dir=store).start()
            self.replicas[rid] = path
        self.router_path = str(tmp / 'router.sock')
        self.journal_path = str(tmp / 'placement.json') \
            if journal else None
        self.router = RouterGateway(
            self.router_path, self.replicas,
            journal_path=self.journal_path).start()

    def stop(self):
        self.router.stop()
        for gw in self.gateways.values():
            gw.stop()


@pytest.fixture()
def fleet(tmp_path):
    f = Fleet(tmp_path, n=2)
    yield f
    f.stop()


# ---------------------------------------------------------------------------
# health state machine (no threads)
# ---------------------------------------------------------------------------

class _StubRouter(object):
    replicas = {}
    use_msgpack = False

    def __init__(self):
        self.released = []

    def attach_health(self, m):
        pass

    def release_member_parks(self, member):
        self.released.append(member)


def test_health_state_machine_hysteresis():
    r = _StubRouter()
    hm = HealthMonitor(r, heartbeat_s=9, deadline_s=9, miss_max=3)
    assert hm.state('r0') == 'up'
    hm.note_miss('r0')
    assert hm.state('r0') == 'suspect' and hm.is_parking('r0')
    hm.note_miss('r0')
    assert hm.state('r0') == 'suspect', 'two misses < miss_max'
    # a probe answering again fully recovers (and replays the parks)
    hm.note_ok('r0')
    assert hm.state('r0') == 'up' and not hm.is_parking('r0')
    assert r.released == ['r0']
    # the miss counter reset: three FRESH consecutive misses kill
    for _ in range(3):
        hm.note_miss('r0')
    assert hm.state('r0') == 'dead'
    hm.note_ok('r0')
    assert hm.state('r0') == 'dead', 'dead is terminal for the id'
    flat = _flat()
    assert flat.get('router.health.suspects') == 2
    assert flat.get('router.health.deaths') == 1
    assert flat.get('router.health.recoveries') == 1
    assert flat.get('router.health.misses') == 5


def test_health_mark_dead_and_transport_signals():
    hm = HealthMonitor(_StubRouter(), heartbeat_s=9, deadline_s=9,
                       miss_max=3)
    hm.note_transport_death('r1')
    assert hm.state('r1') == 'suspect'
    hm.mark_dead('r0', cause='exit rc=-9')
    assert hm.state('r0') == 'dead'
    snap = hm.members()
    assert snap['r0']['state'] == 'dead'
    assert snap['r1']['misses'] == 1


# ---------------------------------------------------------------------------
# chaos lanes: the router.heartbeat fault site drives the ladder
# ---------------------------------------------------------------------------

def test_permanent_heartbeat_fault_drives_failover(fleet):
    """A permanently unreachable member walks up -> suspect -> dead
    deterministically, the failover executor restores its docs onto
    the survivor from the write-through store, and every doc keeps
    serving with history intact."""
    router = fleet.router
    docs = ['doc-%d' % i for i in range(16)]
    with SidecarClient(sock_path=fleet.router_path) as c:
        for d in docs:
            for seq in (1, 2):
                assert c.apply_changes(
                    d, [change('a', seq)])['clock'] == {'a': seq}
        victim = 'r0'
        victim_docs = [d for d in docs
                       if router.ring.owner(d) == victim]
        assert victim_docs, 'need docs on the victim'
        ex = FailoverExecutor(router, store_dirs=fleet.stores)
        hm = HealthMonitor(router, heartbeat_s=0.05, deadline_s=0.2,
                           miss_max=2, on_dead=ex.fail_over).start()
        try:
            faults.arm('router.heartbeat', kind='permanent',
                       match=victim)
            _poll(lambda: victim not in router.replicas,
                  what='failover to remove the victim')
            assert hm.state(victim) == 'dead'
            assert router.ring.members() == ['r1']
            # every doc is answerable with its full history, and new
            # writes keep applying in sequence (nothing duplicated:
            # seq 3 on top of a restored seq<=2 history)
            for d in docs:
                assert c.get_patch(d)['clock'] == {'a': 2}, d
                assert c.apply_changes(
                    d, [change('a', 3)])['clock'] == {'a': 3}
        finally:
            faults.disarm()
            hm.stop()
    flat = _flat()
    assert flat.get('router.health.deaths') == 1
    assert flat.get('failover.failovers') == 1
    assert flat.get('failover.docs_recovered') >= len(victim_docs)
    assert not flat.get('failover.docs_lost')
    assert not flat.get('fallback.oracle'), \
        'chaos must never push the pool onto the oracle path'


def test_transient_heartbeat_fault_clears_without_failover(fleet):
    """One injected probe miss only SUSPECTS the member; the next
    probe answers and the member recovers -- no failover, no
    membership change."""
    router = fleet.router
    ex = FailoverExecutor(router, store_dirs=fleet.stores)
    hm = HealthMonitor(router, heartbeat_s=0.05, deadline_s=0.2,
                       miss_max=5, on_dead=ex.fail_over).start()
    try:
        faults.arm('router.heartbeat', kind='transient', count=1)
        _poll(lambda: _flat().get('router.health.suspects', 0) >= 1,
              what='the injected miss to suspect a member')
        _poll(lambda: _flat().get('router.health.recoveries', 0) >= 1,
              what='the next probe to recover it')
        assert sorted(router.replicas) == ['r0', 'r1']
        assert all(st['state'] == 'up'
                   for st in hm.members().values())
    finally:
        faults.disarm()
        hm.stop()
    flat = _flat()
    assert not flat.get('failover.failovers')
    assert not flat.get('router.health.deaths')
    assert not flat.get('fallback.oracle')


# ---------------------------------------------------------------------------
# park / replay / fail semantics
# ---------------------------------------------------------------------------

def _raw_conn(path):
    s = socket.socket(socket.AF_UNIX)
    s.connect(path)
    return s, s.makefile('rb')


def test_suspect_member_parks_mutations_and_replays_on_failover(
        fleet, tmp_path):
    """Mutating frames for a suspect member's docs park in the per-doc
    FIFOs; when the member is declared dead and failed over they
    replay IN ARRIVAL ORDER against the restored doc on the new owner
    -- pipelined seqs must land gapless."""
    router = fleet.router
    doc = 'park-doc'
    with SidecarClient(sock_path=fleet.router_path) as c:
        c.apply_changes(doc, [change('a', 1)])
    victim = router.ring.owner(doc)
    ex = FailoverExecutor(router, store_dirs=fleet.stores)
    # attached but UNSTARTED monitor: the lane drives the machine by
    # hand so the park window is deterministic, not a thread race
    hm = HealthMonitor(router, miss_max=2)
    router.attach_health(hm)
    hm.note_miss(victim)
    s, f = _raw_conn(fleet.router_path)
    try:
        for seq in range(2, 7):
            s.sendall((json.dumps(
                {'id': seq, 'cmd': 'apply_changes', 'doc': doc,
                 'changes': [change('a', seq)]}) + '\n').encode())
        # frame 1 opens the fleet park; frames 2..5 land in the same
        # per-doc FIFO through the ordinary park check
        _poll(lambda: _flat().get('router.health.parked', 0) >= 1
              and _flat().get('router.parked', 0) >= 4,
              what='frames to park for the suspect member')
        assert router.parked_docs_for(victim) == [doc]
        s.settimeout(0.3)
        with pytest.raises(socket.timeout):
            s.recv(1)
        s.settimeout(None)
        hm.note_miss(victim)            # 2nd miss: dead
        assert hm.state(victim) == 'dead'
        res = ex.fail_over(victim)
        assert doc in res['recovered'] and not res['lost'], res
        rids = [json.loads(f.readline())['id'] for _ in range(5)]
        assert rids == [2, 3, 4, 5, 6], rids
    finally:
        s.close()
        router.attach_health(None)
    with SidecarClient(sock_path=fleet.router_path) as c:
        assert c.get_patch(doc)['clock'] == {'a': 6}
    assert _flat().get('failover.replayed') == 5
    assert router.park_stats() == {'parked_docs': 0,
                                   'parked_bytes': 0}


def test_unrecoverable_docs_answer_replica_failed(fleet):
    """With nothing durable registered for the dead member, parked
    mutating frames answer the terminal typed ReplicaFailed envelope
    (and the client maps it)."""
    router = fleet.router
    doc = 'lost-doc'
    with SidecarClient(sock_path=fleet.router_path) as c:
        c.apply_changes(doc, [change('a', 1)])
    victim = router.ring.owner(doc)
    ex = FailoverExecutor(router)       # no store_dirs registered
    hm = HealthMonitor(router, miss_max=1)
    router.attach_health(hm)
    hm.note_miss(victim)                # miss_max=1: straight to dead
    errs = []
    with SidecarClient(sock_path=fleet.router_path) as c:
        t = threading.Thread(target=lambda: errs.append(
            pytest.raises(ReplicaFailedError, c.apply_changes, doc,
                          [change('a', 2)])))
        t.start()
        _poll(lambda: _flat().get('router.health.parked', 0) >= 1,
              what='the mutation to park')
        res = ex.fail_over(victim)
        t.join(timeout=10)
        assert not t.is_alive()
    assert doc in res['lost']
    assert errs and errs[0].value.doc == doc
    assert _flat().get('failover.docs_lost') >= 1
    router.attach_health(None)


def test_park_budget_and_expiry_answer_replica_unavailable(
        tmp_path, monkeypatch):
    """The park window is bounded: past AMTPU_FLEET_PARK_S the sweep
    flushes parked frames with the retryable ReplicaUnavailable
    envelope (mapped by the client), and a zero byte budget refuses
    the park outright."""
    monkeypatch.setenv('AMTPU_FLEET_PARK_S', '0.2')
    f = Fleet(tmp_path, n=2)
    try:
        router = f.router
        doc = 'expire-doc'
        with SidecarClient(sock_path=f.router_path) as c:
            c.apply_changes(doc, [change('a', 1)])
        victim = router.ring.owner(doc)
        hm = HealthMonitor(router, miss_max=2)
        router.attach_health(hm)
        hm.note_miss(victim)
        with SidecarClient(sock_path=f.router_path) as c:
            errs = []
            t = threading.Thread(target=lambda: errs.append(
                pytest.raises(ReplicaUnavailableError,
                              c.apply_changes, doc,
                              [change('a', 2)])))
            t.start()
            _poll(lambda: _flat().get('router.health.parked', 0) >= 1,
                  what='the mutation to park')
            time.sleep(0.25)            # > AMTPU_FLEET_PARK_S
            router.sweep_parked()
            t.join(timeout=10)
            assert not t.is_alive()
            assert errs and errs[0].value.retry_after_ms >= 100
        assert _flat().get('router.health.park_expired') == 1
        # zero budget: the park is refused, the envelope is immediate
        router.park_bytes_max = 0
        with SidecarClient(sock_path=f.router_path) as c:
            with pytest.raises(ReplicaUnavailableError):
                c.apply_changes(doc, [change('a', 2)])
        assert _flat().get('router.health.park_overflow') == 1
        router.attach_health(None)
    finally:
        f.stop()


# ---------------------------------------------------------------------------
# placement journal: a restarted router serves post-failover placement
# ---------------------------------------------------------------------------

def test_journal_restores_post_failover_placement(tmp_path):
    f = Fleet(tmp_path, n=3, journal=True)
    docs = ['doc-%d' % i for i in range(24)]
    try:
        router = f.router
        with SidecarClient(sock_path=f.router_path) as c:
            for d in docs:
                c.apply_changes(d, [change('a', 1)])
        ex = FailoverExecutor(router, store_dirs=f.stores)
        res = ex.fail_over('r0')
        assert not res['lost']
        placement = {d: router.ring.owner(d) for d in docs}
        overrides = router.ring.overrides()
        epoch = router.ring.version
        members = dict(router.replicas)
        assert 'r0' not in members
    finally:
        f.stop()
    # restart a router from the ORIGINAL seed (r0 included): the
    # journal must win -- the dead placement stays dead, byte for byte
    r2 = RouterGateway(str(tmp_path / 'router2.sock'), f.replicas,
                       journal_path=f.journal_path).start()
    try:
        assert r2.replicas == members
        assert {d: r2.ring.owner(d) for d in docs} == placement
        assert r2.ring.overrides() == overrides
        assert r2.ring.version >= epoch
    finally:
        r2.stop()


def test_journal_ignores_corruption(tmp_path):
    journal = tmp_path / 'placement.json'
    journal.write_text('{not json')
    sock = str(tmp_path / 'r.sock')
    gw = GatewayServer(sock, backend=SidecarBackend()).start()
    router = RouterGateway(str(tmp_path / 'router.sock'),
                           {'r0': sock},
                           journal_path=str(journal)).start()
    try:
        assert sorted(router.replicas) == ['r0'], \
            'corrupt journal falls back to the seed membership'
        router.add_member('r0b', sock)
        data = json.loads(journal.read_text())
        assert sorted(data['members']) == ['r0', 'r0b']
    finally:
        router.stop()
        gw.stop()


# ---------------------------------------------------------------------------
# rejoin pinning: a new member must not implicitly claim existing docs
# ---------------------------------------------------------------------------

def test_rejoin_pins_existing_docs_to_survivors(tmp_path):
    """After a failover, a respawned generation joins as a NEW ring
    member.  Without pins the hash remap would route ~1/N of existing
    docs to the empty joiner (forking them on first write); with
    `join_pins` every known doc stays with the member that holds its
    state, and only genuinely new docs may hash to the joiner."""
    f = Fleet(tmp_path, n=3)
    docs = ['doc-%d' % i for i in range(30)]
    try:
        router = f.router
        with SidecarClient(sock_path=f.router_path) as c:
            for d in docs:
                c.apply_changes(d, [change('a', 1)])
        ex = FailoverExecutor(router, store_dirs=dict(f.stores))
        assert not ex.fail_over('r0')['lost']
        before = {d: router.ring.owner(d) for d in docs}
        assert set(before.values()) <= {'r1', 'r2'}
        # the rejoiner gets a fresh empty store, registered AFTER the
        # pins are computed (supervisor ordering)
        pins = ex.join_pins()
        ex.register_store('r0-g1', str(tmp_path / 'store-r0-g1'))
        router.add_member('r0-g1', f.replicas['r1'], pins=pins)
        after = {d: router.ring.owner(d) for d in docs}
        assert after == before, \
            'join remapped docs away from their state: %r' % {
                d: (before[d], after[d]) for d in docs
                if before[d] != after[d]}
        # writes keep landing with history intact through the pins
        with SidecarClient(sock_path=f.router_path) as c:
            for d in docs:
                assert c.apply_changes(
                    d, [change('a', 2)])['clock'] == {'a': 2}
    finally:
        f.stop()


# ---------------------------------------------------------------------------
# supervisor policy (process-free: spawn is stubbed)
# ---------------------------------------------------------------------------

def test_supervisor_generation_naming():
    from automerge_tpu.router.supervisor import ReplicaSupervisor as S
    assert S._member_name('r0', 0) == 'r0'
    assert S._member_name('r0', 2) == 'r0-g2'
    assert S._parse('r0') == ('r0', 0)
    assert S._parse('r0-g2') == ('r0', 2)
    assert S._parse('odd-gName') == ('odd-gName', 0)


def test_supervisor_respawns_then_quarantines(tmp_path, monkeypatch):
    from automerge_tpu.router.supervisor import ReplicaSupervisor

    class _R(object):
        replicas = {}
    sup = ReplicaSupervisor(_R(), str(tmp_path), flap_max=2)
    spawned = []
    monkeypatch.setattr(
        sup, 'spawn', lambda base, gen=0: spawned.append((base, gen)))
    for _ in range(2):                  # deaths 1..2: respawn
        sup._on_exit('r0' if not spawned
                     else 'r0-g%d' % spawned[-1][1], -9)
    assert spawned == [('r0', 1), ('r0', 2)]
    sup._on_exit('r0-g2', -9)           # death 3 > flap_max: barred
    assert spawned == [('r0', 1), ('r0', 2)]
    flat = _flat()
    assert flat.get('failover.respawns') == 2
    assert flat.get('failover.quarantined') == 1


def test_supervisor_refuses_second_chip_replica(tmp_path, monkeypatch):
    """Off the CPU a replica holds the host's chips: a second live one
    is refused before anything is spawned; CPU fleets are not limited."""
    import subprocess
    from automerge_tpu.router.supervisor import ReplicaSupervisor

    class _Live(object):
        def poll(self):
            return None

    def no_popen(*a, **k):
        raise AssertionError('spawned a replica that needs a held chip')
    monkeypatch.setattr(subprocess, 'Popen', no_popen)
    sup = ReplicaSupervisor(object(), str(tmp_path),
                            spawn_env={'JAX_PLATFORMS': ''})
    sup._procs['r0'] = _Live()
    with pytest.raises(RuntimeError, match='compete for the chips'):
        sup.spawn('r1')
    sup._refuse_chip_contention('r1', {'JAX_PLATFORMS': 'cpu'})


# ---------------------------------------------------------------------------
# write-through checkpointing (the durability the restore rests on)
# ---------------------------------------------------------------------------

def test_write_through_store_holds_every_acked_change(tmp_path):
    sync = str(tmp_path / 'sync')
    gw = GatewayServer(str(tmp_path / 'r.sock'),
                       backend=SidecarBackend(),
                       sync_dir=sync).start()
    try:
        with SidecarClient(sock_path=str(tmp_path / 'r.sock')) as c:
            for seq in (1, 2, 3):
                c.apply_changes('wt-doc', [change('a', seq)])
        store = ColdStore(sync, durable=True)
        assert 'wt-doc' in store.doc_ids()
        # the checkpoint is the FULL doc as of the last ack
        from automerge_tpu.sidecar.server import SidecarBackend as SB
        probe = SB()
        probe.pool.load('wt-doc', store.get('wt-doc'))
        patch = probe.handle({'id': 1, 'cmd': 'get_patch',
                              'doc': 'wt-doc'})['result']
        assert patch['clock'] == {'a': 3}
    finally:
        gw.stop()
    assert _flat().get('storage.sync_saves') == 3
    assert not _flat().get('storage.sync_failed')
