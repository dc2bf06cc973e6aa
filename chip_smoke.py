#!/usr/bin/env python3
"""Chip smoke: the main path once on a TPU, through the entry points a
user calls, checked against the scalar oracle (`automerge_tpu.backend`).

    python chip_smoke.py             one chip (what the driver runs)
    python chip_smoke.py --chips 4   the mesh only: dp=4 and sp=4 vs dp=1

One chip, in one process and in this order:
  build      make -B in native/: the library is built here, never reused
  device     platform, kind and count; anything but a TPU fails
  text       BASELINE config 3: 10,000 Text docs x 16 actors, ~1M ops
             in one causal catch-up batch, then a batch extending them
  maps       BASELINE config 2: 1,024 Map docs x 8 actors, ~1M ops
  window     config 2's first round (one change per actor, so no key
             holds more than 8 rows): the registers-only batch that
             make_pool() sends to the Pallas register kernel
  pallas     both Pallas kernels bit-equal to their XLA twins, the
             register kernel also at the widest actor set its gate admits
  served     an in-process gateway: 4 client connections over a unix
             socket write 64 docs, one patch-mode subscriber listens
After each phase it prints the kernels dispatched, compile seconds,
wall time per batch and the telemetry counters, and fails on any
oracle fallback, retry, quarantine, degrade or mesh device shortfall.
The last line of stdout is the JSON result, printed only when every
phase passed.  A smoke run, not a benchmark.
"""

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 21

#: counters that must stay zero: each means the device path was left,
#: or a topology or mode knob flipped and was ignored
FATAL_COUNTERS = ('fallback.oracle', 'resilience.retry.attempts',
                  'resilience.quarantined', 'resilience.degraded',
                  'mesh.device_shortfall', 'mesh.latch_flip_ignored',
                  'resident.latch_flip_ignored')


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(*args):
    print(*args, flush=True)


# ---------------------------------------------------------------------------
# compile and counter accounting
# ---------------------------------------------------------------------------

class Meter(object):
    """Compile seconds and programs (JAX's own monitoring events; a
    persistent-cache hit counts as a program, its seconds are the
    read), kernel dispatch counts (telemetry phase counters) and the
    flat counters, per phase."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == '/jax/core/compile/backend_compile_duration':
                self.compile_s += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == '/jax/compilation_cache/cache_hits':
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def start(self):
        from automerge_tpu import telemetry
        telemetry.enable()
        telemetry.phase_reset()
        telemetry.metrics_reset()
        self._c0 = (self.compile_s, self.compiles, self.cache_hits)

    def report(self, phase, walls):
        """Prints the phase's accounting; fails on a fatal counter.
        Returns the kernels dispatched."""
        from automerge_tpu import telemetry
        phases = telemetry.phase_snapshot()
        flat = telemetry.metrics_snapshot()
        kernels = {k: v['n'] for k, v in phases.items()
                   if k.startswith(('ops.', 'resident.', 'hostdom.',
                                    'hostfull.', 'hostreg.', 'fused.'))}
        spans = {k: round(v['s'], 4) for k, v in phases.items()
                 if k.startswith(('device.', 'host.', 'mesh.'))}
        log('[%s] wall per batch (s): %s' % (
            phase, ', '.join('%s=%.3f' % kv for kv in walls)))
        log('[%s] compile: %.2f s over %d programs, %d of them from the '
            'persistent cache' % (
            phase, self.compile_s - self._c0[0],
            self.compiles - self._c0[1], self.cache_hits - self._c0[2]))
        log('[%s] kernels dispatched: %s' % (phase, json.dumps(kernels)))
        log('[%s] span seconds: %s' % (phase, json.dumps(spans)))
        log('[%s] counters: %s' % (phase, json.dumps(
            {k: flat[k] for k in sorted(flat)})))
        bad = {k: flat[k] for k in FATAL_COUNTERS if flat.get(k)}
        check(not bad, '%s: device path left: %r' % (phase, bad))
        return kernels


# ---------------------------------------------------------------------------
# workloads (seeded) and oracle parity
# ---------------------------------------------------------------------------

def text_batches(n_docs, n_actors=16, ops_per_change=6, seed=SEED):
    """Config 3 as two causal batches per doc: the first holds the doc's
    creation and one change per actor (4 + 16 * 6 = 100 ops), the
    second one more change per actor extending it."""
    from automerge_tpu.parallel.mesh_encode import text_doc_changes
    rng = random.Random(seed)
    first, second = {}, {}
    for d in range(n_docs):
        chs = text_doc_changes(
            'text-%d' % d, n_actors, 2, ops_per_change,
            lambda i, a, has: rng.random() < 0.15 and has)
        first[d] = chs[:1 + n_actors]
        second[d] = chs[1 + n_actors:]
    return first, second


def long_text_changes(n_elems):
    """One Text doc of `n_elems` elements, typed in one change."""
    from automerge_tpu.utils.common import ROOT_ID
    ops, prev = [{'action': 'makeText', 'obj': 't'},
                 {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
                  'value': 't'}], '_head'
    for e in range(1, n_elems + 1):
        ops.append({'action': 'ins', 'obj': 't', 'key': prev, 'elem': e})
        ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%d' % e,
                    'value': chr(97 + e % 26)})
        prev = 'a0:%d' % e
    return [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': ops}]


def n_ops(batch):
    return sum(len(c['ops']) for chs in batch.values() for c in chs)


def payload(batch):
    import msgpack
    from automerge_tpu.utils.common import doc_key
    return msgpack.packb({doc_key(d): chs for d, chs in batch.items()},
                         use_bin_type=True)


def oracle_states(batches, docs):
    from automerge_tpu import backend as Backend
    states = {}
    for d in docs:
        st = Backend.init()
        for b in batches:
            st, _ = Backend.apply_changes(st, b[d])
        states[d] = st
    return states


def sample(docs, seed=SEED):
    """A seeded 10% of the docs (at least one)."""
    docs = list(docs)
    return random.Random(seed).sample(docs, max(1, len(docs) // 10))


def check_parity(label, pool, states):
    from automerge_tpu import backend as Backend
    for d, st in states.items():
        check(pool.get_patch(d) == Backend.get_patch(st),
              '%s: patch of doc %r differs from the oracle' % (label, d))
    log('[%s] parity: %d sampled docs identical to the oracle'
        % (label, len(states)))


def apply_timed(pool, batches):
    """Applies each batch through apply_batch_bytes; returns walls."""
    walls = []
    for i, b in enumerate(batches):
        data = payload(b)
        t0 = time.perf_counter()
        pool.apply_batch_bytes(data)
        walls.append(('batch%d(%d ops)' % (i + 1, n_ops(b)),
                      time.perf_counter() - t0))
    return walls


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_build():
    """Builds the native library from the committed sources; make's
    output goes to the terminal."""
    t0 = time.perf_counter()
    rc = subprocess.call(['make', '-B', '-C', os.path.join(REPO, 'native')],
                         stdout=sys.stderr)
    check(rc == 0, 'build: make exited %d' % rc)
    log('[build] native library built in %.1f s'
        % (time.perf_counter() - t0))


def phase_device(want_count):
    from automerge_tpu.utils.jaxenv import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    device = {'platform': devs[0].platform, 'kind': devs[0].device_kind,
              'count': len(devs)}
    log('[device] %s; compile cache: %s' % (json.dumps(device), cache))
    check(device['platform'] == 'tpu',
          'device: platform is %r, not tpu' % device['platform'])
    check(device['count'] >= want_count, 'device: %d chips, need %d'
          % (device['count'], want_count))
    return device


def phase_text(meter, n_docs=10000):
    from automerge_tpu.native import make_pool
    first, second = text_batches(n_docs)
    log('[text] %d docs, %d + %d ops' % (n_docs, n_ops(first),
                                         n_ops(second)))
    meter.start()
    pool = make_pool()
    walls = apply_timed(pool, [first, second])
    meter.report('text', walls)
    check_parity('text', pool, oracle_states([first, second],
                                             sample(first)))


def phase_maps(meter):
    import bench
    from automerge_tpu.native import make_pool
    batch, _metric = bench.build_config_2(random.Random(SEED))
    log('[maps] %d docs, %d ops' % (len(batch), n_ops(batch)))
    meter.start()
    pool = make_pool()
    walls = apply_timed(pool, [batch])
    meter.report('maps', walls)
    check_parity('maps', pool, oracle_states([batch], sample(batch)))


def phase_window(meter):
    """The first round of config 2 -- every doc, every actor, one change
    each -- on a fresh pool: no key holds more rows than the sliding
    window, so the registers-only dispatch takes the Pallas kernel."""
    import bench
    from automerge_tpu.native import make_pool
    full, _metric = bench.build_config_2(random.Random(SEED))
    batch = {d: [c for c in chs if c['seq'] == 1] for d, chs in full.items()}
    log('[window] %d docs, %d ops' % (len(batch), n_ops(batch)))
    meter.start()
    pool = make_pool()
    walls = apply_timed(pool, [batch])
    kernels = meter.report('window', walls)
    check(kernels.get('ops.registers.pallas', 0) > 0,
          'window: the batch did not reach the Pallas register kernel')
    check_parity('window', pool, oracle_states([batch], sample(batch)))


def pallas_registers_case(rng, T, A, window):
    """The Pallas register kernel vs its XLA twin on seeded input."""
    import numpy as np
    from automerge_tpu.ops import registers
    from automerge_tpu.ops.pallas_registers import resolve_registers_pallas
    group = np.sort(rng.integers(0, T // 4, T)).astype(np.int32)
    time_ = np.arange(T, dtype=np.int32)
    actor = rng.integers(0, A, T).astype(np.int32)
    seq = rng.integers(1, 64, T).astype(np.int32)
    is_del = rng.random(T) < 0.1
    clock_table = rng.integers(0, 64, (1024, A)).astype(np.int32)
    clock_idx = rng.integers(0, 1024, T).astype(np.int32)
    sort_idx = np.lexsort((time_, group)).astype(np.int32)
    t0 = time.perf_counter()
    got = resolve_registers_pallas(group, time_, actor, seq, is_del,
                                   sort_idx, clock_table, clock_idx,
                                   window=window)
    got = {k: np.asarray(v) for k, v in got.items()}
    wall = time.perf_counter() - t0
    want = registers.resolve_registers(
        group, time_, actor, seq, is_del=is_del, alive_in=np.ones(T, bool),
        window=window, sort_idx=sort_idx, clock_table=clock_table,
        clock_idx=clock_idx)
    for k in ('winner', 'alive_after', 'conflicts', 'visible_before',
              'overflow', 'packed'):
        check((got[k] == np.asarray(want[k])).all(),
              'pallas: registers (A=%d) %s differs from the XLA twin'
              % (A, k))
    return wall


def phase_pallas(meter, T=65536, window=8):
    """Both Pallas kernels on the chip, bit-equal to their XLA twins on
    seeded inputs at a config-sized shape; the register kernel at 16
    actors and at the most its VMEM gate admits."""
    import numpy as np
    from automerge_tpu.ops import list_rank
    from automerge_tpu.ops import pallas_registers as pr
    from automerge_tpu.ops.pallas_dominance import dominance_grouped_pallas
    rng = np.random.default_rng(SEED)
    widest = pr.widest_actors(window)
    meter.start()
    walls = [('registers_pallas(A=%d)' % A,
              pallas_registers_case(rng, T, A, window))
             for A in (16, widest)]
    W, L, Td = 64, 4096, 2048
    vis0 = (rng.random((W, L)) < 0.5).astype(np.float32)
    elem_rank = rng.permuted(np.tile(np.arange(L, dtype=np.int32), (W, 1)),
                             axis=1)
    op_elem = rng.integers(0, L, (W, Td)).astype(np.int32)
    op_rank = np.take_along_axis(elem_rank, op_elem, axis=1)
    op_delta = rng.integers(-1, 2, (W, Td)).astype(np.int32)
    op_valid = np.ones((W, Td), bool)
    t0 = time.perf_counter()
    got = np.asarray(dominance_grouped_pallas(
        vis0, elem_rank, op_elem, op_rank, op_delta, op_valid, chunk=128))
    walls.append(('dominance_pallas', time.perf_counter() - t0))
    want = np.asarray(list_rank.dominance_grouped(
        vis0, elem_rank, op_elem, op_rank, op_delta, op_valid, chunk=128))
    check((got == want).all(),
          'pallas: dominance indexes differ from the XLA twin')
    meter.report('pallas', walls)
    log('[pallas] registers (T=%d, A=16 and %d, W=%d) and dominance '
        '(%d, %d, %d) bit-equal to the XLA twins'
        % (T, widest, window, W, L, Td))


def phase_served(meter, n_docs=64, n_conns=4):
    from automerge_tpu import backend as Backend
    from automerge_tpu.parallel.mesh_encode import demo_text_workload
    from automerge_tpu.readview.events import PatchEvent
    from automerge_tpu.scheduler import GatewayServer
    from automerge_tpu.sidecar.client import SidecarClient
    work = {'served-%d' % d: chs
            for d, chs in demo_text_workload(n_docs).items()}
    docs = sorted(work)
    meter.start()
    with tempfile.TemporaryDirectory() as tmp:
        sock = os.path.join(tmp, 'gw.sock')
        gw = GatewayServer(sock).start()
        clients = []
        try:
            clients = [SidecarClient(sock_path=sock)
                       for _ in range(n_conns + 1)]
            sub_client = clients[n_conns]
            watched = docs[0]
            clients[0].apply_changes(watched, work[watched][:1])
            sub_client.subscribe(doc=watched, peer='thin', mode='patch')
            errors = []

            def writer(c, mine):
                try:
                    for d in mine:
                        start = 1 if d == watched else 0
                        for ch in work[d][start:]:
                            c.apply_changes(d, [ch])
                except Exception as e:       # reported below
                    errors.append(e)
            t0 = time.perf_counter()
            threads = [threading.Thread(target=writer,
                                        args=(clients[i], docs[i::n_conns]))
                       for i in range(n_conns)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            check(not errors, 'served: a writer failed: %r' % errors[:1])
            ev = sub_client.next_event(timeout=120)
            check(isinstance(ev, PatchEvent),
                  'served: the patch subscriber got %r' % (ev,))
            for d in docs:
                st, _ = Backend.apply_changes(Backend.init(), work[d])
                check(clients[0].get_patch(d) == Backend.get_patch(st),
                      'served: patch of %r differs from the oracle' % d)
        finally:
            for c in clients:
                c.close()
            gw.stop()
    n_changes = sum(len(v) for v in work.values())
    meter.report('served', [('%d changes over %d conns' % (n_changes,
                                                          n_conns), wall)])
    log('[served] %d docs identical to the oracle; patch subscriber got '
        'a frame' % len(docs))


def phase_mesh(meter, n_docs=10000, n_long=(1 << 17) + 1024):
    """Four chips: config 3 through make_pool() at AMTPU_MESH=4 and
    through a dp=1 pool, then one long Text doc (past the sp crossover,
    `SP_CROSSOVER_ELEMS`) through dp=1, sp=1 and dp=1, sp=4.  Each pair
    identical, and identical to the oracle.  AMTPU_MESH is set once;
    the other pools are built from their axes."""
    import msgpack
    from automerge_tpu import telemetry
    from automerge_tpu.native import make_pool
    from automerge_tpu.native.mesh_pool import MeshDocPool
    first, second = text_batches(n_docs)
    log('[mesh] %d docs, %d + %d ops' % (n_docs, n_ops(first),
                                         n_ops(second)))
    meter.start()
    os.environ['AMTPU_MESH'] = '4'
    pool4 = make_pool()
    check(isinstance(pool4, MeshDocPool) and pool4.dp == 4,
          'mesh: make_pool() under AMTPU_MESH=4 built %r' % (pool4,))
    pool1 = MeshDocPool(dp=1)
    walls, outs = [], {}
    for label, pool in (('dp4', pool4), ('dp1', pool1)):
        for i, b in enumerate((first, second)):
            data = payload(b)
            t0 = time.perf_counter()
            outs[label, i] = msgpack.unpackb(pool.apply_batch_bytes(data),
                                             raw=False)
            walls.append(('%s.batch%d' % (label, i + 1),
                          time.perf_counter() - t0))
    devices = [str(p.device) for p in pool4.pools]
    log('[mesh] dp=4 chip devices: %s' % devices)
    check(len(set(devices)) == 4, 'mesh: chips share devices %s' % devices)
    for i in (0, 1):
        check(outs['dp4', i] == outs['dp1', i],
              'mesh: dp=4 batch %d patches differ from dp=1' % (i + 1))
    states = oracle_states([first, second], sample(first))
    check_parity('mesh.dp4', pool4, states)
    check_parity('mesh.dp1', pool1, states)

    long_batch = {'long': long_text_changes(n_long)}
    long_out = {}
    for sp in (1, 4):
        pool = MeshDocPool(dp=1, sp=sp)
        engaged = telemetry.metrics_snapshot().get('mesh.sp_engaged', 0)
        t0 = time.perf_counter()
        long_out[sp] = msgpack.unpackb(
            pool.apply_batch_bytes(payload(long_batch)), raw=False)
        walls.append(('long.sp%d(%d ops)' % (sp, n_ops(long_batch)),
                      time.perf_counter() - t0))
        sharded = telemetry.metrics_snapshot().get('mesh.sp_engaged', 0) \
            > engaged
        check(sharded == (sp > 1), 'mesh: long doc at sp=%d %s over sp'
              % (sp, 'sharded' if sharded else 'not sharded'))
    check(long_out[1] == long_out[4],
          'mesh: sp=4 long-doc patches differ from sp=1')
    check_parity('mesh.sp4', pool, oracle_states([long_batch], ['long']))
    meter.report('mesh', walls)
    log('[mesh] dp=4 and sp=4 identical to dp=1 and to the oracle on %d '
        'distinct devices' % len(set(devices)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1,
                    help='4: run only the mesh phase, on four chips')
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    try:
        phase_build()
        device = phase_device(args.chips)
        meter = Meter()
        if args.chips == 4:
            phase_mesh(meter)
        else:
            phase_text(meter)
            phase_maps(meter)
            phase_window(meter)
            phase_pallas(meter)
            phase_served(meter)
    except SmokeFailure as e:
        print('chip_smoke: FAIL: %s' % e, file=sys.stderr)
        return 1
    print(json.dumps({'ok': True, 'device': device}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
