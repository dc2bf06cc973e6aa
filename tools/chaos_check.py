"""CI gate: the resilience layer must actually isolate injected faults
(ISSUE 4).

Three lanes, each asserting the acceptance contract end to end:

  1. TRANSIENT -- ``AMTPU_FAULT=device.dispatch:transient:1.0:2`` (two
     forced transient faults) on a config-3 batch: the result bytes must
     be IDENTICAL to the fault-free run and ``resilience.retry.success``
     >= 1.
  2. PERMANENT -- a permanent fault pinned to one doc: exactly that doc
     quarantined (per-doc error envelope), every healthy doc's patch
     byte-identical to the fault-free run.
  3. SIDECAR -- SIGKILL the server mid-session: the client respawns,
     replays its checkpoint WAL, a subsequent get_patch matches the
     uninterrupted session, healthz reports the restart count, and the
     process tree is clean after close().

Wired into ``make check`` as ``make chaos-check``.

Usage: [JAX_PLATFORMS=cpu] python tools/chaos_check.py
"""
import os
import random
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
# the kernel path is the subject (device sites are unreachable on the
# full host path), and the smoke stays small
os.environ['AMTPU_HOST_FULL'] = '0'
os.environ['AMTPU_HOST_REG'] = '0'
os.environ.setdefault('AMTPU_BENCH_DOCS', '48')
os.environ.setdefault('AMTPU_BENCH_ACTORS', '4')

import msgpack  # noqa: E402

from automerge_tpu import faults, resilience, telemetry  # noqa: E402
from automerge_tpu.native import NativeDocPool, make_pool  # noqa: E402

ROOT_ID = '00000000-0000-0000-0000-000000000000'


def _per_doc(raw):
    """{doc: packed patch bytes} -- the chaos lanes compare per doc so
    they hold for ANY configured pool (the mesh pool's shard merge is
    doc-order-free; byte identity is per-doc, exactly what clients
    see)."""
    out = msgpack.unpackb(raw, raw=False, strict_map_key=False)
    return {d: msgpack.packb(p, use_bin_type=True) for d, p in out.items()}


def _config3_payload():
    import bench
    rng = random.Random(int(os.environ.get('AMTPU_BENCH_SEED', 7)))
    batch, _metric = bench.BUILDERS[3](rng)
    keyed = {NativeDocPool._doc_key(d): chs for d, chs in batch.items()}
    return msgpack.packb(keyed, use_bin_type=True), list(keyed)


def lane_transient(payload, want, problems):
    telemetry.metrics_reset()
    faults.reset('device.dispatch:transient:1.0:2')   # the env syntax
    got = _per_doc(make_pool().apply_batch_bytes_resilient(payload))
    faults.disarm()
    snap = telemetry.metrics_snapshot()
    if got != want:
        problems.append('transient lane: result bytes differ from the '
                        'fault-free run')
    if snap.get('resilience.retry.success', 0) < 1:
        problems.append('transient lane: resilience.retry.success = %s '
                        '(want >= 1)'
                        % snap.get('resilience.retry.success'))
    if snap.get('resilience.fault_injected', 0) != 2:
        problems.append('transient lane: %s faults fired (want 2)'
                        % snap.get('resilience.fault_injected'))
    return snap


def lane_permanent(payload, want, doc_keys, problems):
    poison = doc_keys[len(doc_keys) // 2]
    telemetry.metrics_reset()
    faults.arm('device.dispatch', 'permanent', 1.0, match=poison)
    got_raw = msgpack.unpackb(
        make_pool().apply_batch_bytes_resilient(payload),
        raw=False, strict_map_key=False)
    faults.disarm()
    snap = telemetry.metrics_snapshot()
    quarantined = [d for d in got_raw
                   if resilience.is_quarantined(got_raw[d])]
    if quarantined != [poison]:
        problems.append('permanent lane: quarantined %r (want exactly '
                        '[%r])' % (quarantined, poison))
    if snap.get('resilience.quarantined', 0) != 1:
        problems.append('permanent lane: resilience.quarantined = %s '
                        '(want 1)' % snap.get('resilience.quarantined'))
    bad = [d for d in want if d != poison and
           msgpack.packb(got_raw[d], use_bin_type=True) != want[d]]
    if bad:
        problems.append('permanent lane: %d healthy docs lost parity '
                        '(e.g. %r)' % (len(bad), bad[0]))
    return snap


def lane_sidecar(problems):
    from automerge_tpu.sidecar.client import SidecarClient
    chs = [
        {'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'bird',
             'value': 'magpie'}]},
        {'actor': 'a', 'seq': 2, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': ROOT_ID, 'key': 'fish',
             'value': 'trout'}]},
    ]
    with SidecarClient() as ref:
        for ch in chs:
            ref.apply_changes('doc', [ch])
        want = ref.get_patch('doc')
    c = SidecarClient()
    try:
        for ch in chs:
            c.apply_changes('doc', [ch])
        os.kill(c._proc.pid, signal.SIGKILL)
        time.sleep(0.2)
        got = c.get_patch('doc')
        if got != want:
            problems.append('sidecar lane: post-respawn get_patch '
                            'differs from the uninterrupted session')
        hz = c.healthz()
        if hz.get('restarts') != 1:
            problems.append('sidecar lane: healthz restarts = %s '
                            '(want 1)' % hz.get('restarts'))
    finally:
        c.close()
    if c._proc is not None and c._proc.returncode is None:
        problems.append('sidecar lane: server process leaked past '
                        'close() (pid %d)' % c._proc.pid)
    return c.restarts


def main():
    problems = []
    payload, doc_keys = _config3_payload()
    faults.disarm()
    # fault-free reference from the plain serial pool: the configured
    # pool (AMTPU_MESH included) must reproduce it per doc under faults
    want = _per_doc(NativeDocPool().apply_batch_bytes(payload))

    t_snap = lane_transient(payload, want, problems)
    p_snap = lane_permanent(payload, want, doc_keys, problems)
    restarts = lane_sidecar(problems)

    if problems:
        print('chaos-check FAILED:', file=sys.stderr)
        for p in problems:
            print('  * ' + p, file=sys.stderr)
        return 1
    print('chaos-check: transient retried to parity '
          '(retry.success=%d), poison doc quarantined alone '
          '(bisect.rounds=%d), sidecar respawn+replay OK (restarts=%d), '
          'process tree clean'
          % (t_snap.get('resilience.retry.success', 0),
             p_snap.get('resilience.bisect.rounds', 0), restarts))
    return 0


if __name__ == '__main__':
    sys.exit(main())
