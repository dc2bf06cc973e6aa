"""Device-resident incremental state (SURVEY hard part 5, VERDICT r2 #2).

The contract under test: once a big list arena is resident, a subsequent
batch uploads O(batch) rows -- not O(arena) -- and patches stay
byte-identical to the oracle through deletes, undo, and overflow-free
editing.  The C++ env knobs latch per process, so scenarios run in a
subprocess with AMTPU_RESIDENT=1 and a small AMTPU_RESIDENT_MIN.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCENARIO = r"""
import os, sys
sys.path.insert(0, REPO_PATH)
import jax; jax.config.update('jax_platforms', 'cpu')
import numpy as np
from automerge_tpu import trace, backend as Backend
from automerge_tpu.native import NativeDocPool
ROOT = '00000000-0000-0000-0000-000000000000'
trace.ENABLED = True

def counts(report, name):
    for line in report.splitlines():
        if name in line:
            return int(line.rsplit('x', 1)[1])
    return 0

pool = NativeDocPool()
st = Backend.init()

# batch 1: build a 600-element text
chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
    {'action': 'makeText', 'obj': 't'},
    {'action': 'link', 'obj': ROOT, 'key': 'text', 'value': 't'}]}]
prev, e = '_head', 0
ops = []
for i in range(600):
    e += 1
    ops.append({'action': 'ins', 'obj': 't', 'key': prev, 'elem': e})
    ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%d' % e,
                'value': chr(97 + e % 26)})
    prev = 'a0:%d' % e
chs.append({'actor': 'a0', 'seq': 2, 'deps': {}, 'ops': ops})
trace.reset()
pool.apply_changes('doc', chs)
st, _ = Backend.apply_changes(st, chs)
rep = trace.report()
assert counts(rep, 'resident.dispatch') == 1, rep
assert counts(rep, 'resident.full_upload_rows') == 600, rep

# batches 2..4: small edits (inserts + deletes) -> delta uploads only
seq = 2
for b in range(3):
    seq += 1
    ops = []
    for i in range(8):
        e += 1
        ops.append({'action': 'ins', 'obj': 't', 'key': prev, 'elem': e})
        ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%d' % e,
                    'value': 'X'})
        prev = 'a0:%d' % e
    ops.append({'action': 'del', 'obj': 't', 'key': 'a0:%d' % (b + 3)})
    batch = [{'actor': 'a0', 'seq': seq, 'deps': {}, 'ops': ops}]
    trace.reset()
    pool.apply_changes('doc', batch)
    st, _ = Backend.apply_changes(st, batch)
    rep = trace.report()
    assert counts(rep, 'resident.dispatch') == 1, rep
    # O(batch): exactly the 8 appended rows, NOT the 600-element arena
    assert counts(rep, 'resident.delta_upload_rows') == 8, rep
    assert counts(rep, 'resident.full_upload_rows') == 0, rep

assert pool.get_patch('doc') == Backend.get_patch(st)

# a second writer whose actor id sorts in the middle invalidates ranks
# (correctness over cache retention), then editing resumes resident
mid = [{'actor': 'a00', 'seq': 1,
        'deps': {'a0': seq},
        'ops': [{'action': 'ins', 'obj': 't', 'key': prev,
                 'elem': e + 1},
                {'action': 'set', 'obj': 't', 'key': 'a00:%d' % (e + 1),
                 'value': 'Z'}]}]
trace.reset()
pool.apply_changes('doc', mid)
st, _ = Backend.apply_changes(st, mid)
assert pool.get_patch('doc') == Backend.get_patch(st)

# save/load round trip of the resident doc
blob = pool.save('doc')
pool2 = NativeDocPool()
pool2.load('doc', blob)
assert pool2.get_patch('doc') == pool.get_patch('doc')
print('RESIDENT-OK')
""".replace('REPO_PATH', repr(REPO))


def test_resident_delta_uploads_and_parity():
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_RESIDENT='1',
               AMTPU_RESIDENT_MIN='16')
    out = subprocess.run([sys.executable, '-c', SCENARIO], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'RESIDENT-OK' in out.stdout


def test_resident_disabled_on_cpu_by_default():
    script = r"""
import sys
sys.path.insert(0, %r)
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu import trace
from automerge_tpu.native import NativeDocPool
ROOT = '00000000-0000-0000-0000-000000000000'
trace.ENABLED = True
pool = NativeDocPool()
chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
    {'action': 'makeText', 'obj': 't'},
    {'action': 'link', 'obj': ROOT, 'key': 'text', 'value': 't'}]}]
ops = []
prev = '_head'
for i in range(1, 101):
    ops.append({'action': 'ins', 'obj': 't', 'key': prev, 'elem': i})
    ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%%d' %% i,
                'value': 'x'})
    prev = 'a0:%%d' %% i
chs.append({'actor': 'a0', 'seq': 2, 'deps': {}, 'ops': ops})
trace.reset()
pool.apply_changes('doc', chs)
assert 'resident.dispatch' not in trace.report()
print('CPU-DEFAULT-OK')
""" % (REPO,)
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_RESIDENT_MIN='16')
    env.pop('AMTPU_RESIDENT', None)
    out = subprocess.run([sys.executable, '-c', script], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'CPU-DEFAULT-OK' in out.stdout


CROSS_PATH = r"""
import sys
sys.path.insert(0, REPO_PATH)
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu import backend as Backend
from automerge_tpu.native import NativeDocPool
ROOT = '00000000-0000-0000-0000-000000000000'
pool = NativeDocPool(); st = Backend.init()
chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
    {'action': 'makeText', 'obj': 't'},
    {'action': 'link', 'obj': ROOT, 'key': 'text', 'value': 't'}]}]
prev, e = '_head', 0
ops = []
for i in range(100):
    e += 1
    ops.append({'action': 'ins', 'obj': 't', 'key': prev, 'elem': e})
    ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%d' % e,
                'value': 'x'})
    prev = 'a0:%d' % e
chs.append({'actor': 'a0', 'seq': 2, 'deps': {}, 'ops': ops})
pool.apply_changes('doc', chs); st, _ = Backend.apply_changes(st, chs)
# batch 2 touches the text AND a second list -> NON-resident path
# deletes a char; the cached device ev must be invalidated
b2 = [{'actor': 'a0', 'seq': 3, 'deps': {}, 'ops': [
    {'action': 'makeList', 'obj': 'l2'},
    {'action': 'link', 'obj': ROOT, 'key': 'other', 'value': 'l2'},
    {'action': 'ins', 'obj': 'l2', 'key': '_head', 'elem': 1},
    {'action': 'set', 'obj': 'l2', 'key': 'a0:1', 'value': 9},
    {'action': 'del', 'obj': 't', 'key': 'a0:5'}]}]
pool.apply_changes('doc', b2); st, _ = Backend.apply_changes(st, b2)
# batch 3 is text-only again (resident; stale ev would misindex)
b3 = [{'actor': 'a0', 'seq': 4, 'deps': {}, 'ops': [
    {'action': 'ins', 'obj': 't', 'key': 'a0:10', 'elem': e + 1},
    {'action': 'set', 'obj': 't', 'key': 'a0:%d' % (e + 1),
     'value': 'Z'}]}]
pool.apply_changes('doc', b3); st, _ = Backend.apply_changes(st, b3)
assert pool.get_patch('doc') == Backend.get_patch(st)
print('CROSS-PATH-OK')
""".replace('REPO_PATH', repr(REPO))


def test_non_resident_batch_invalidates_cached_visibility():
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_RESIDENT='1',
               AMTPU_RESIDENT_MIN='16')
    out = subprocess.run([sys.executable, '-c', CROSS_PATH], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'CROSS-PATH-OK' in out.stdout


SHARDED = r"""
import sys
sys.path.insert(0, REPO_PATH)
import jax; jax.config.update('jax_platforms', 'cpu')
assert len(jax.devices()) >= 8, jax.devices()
from automerge_tpu import trace, backend as Backend
from automerge_tpu.native import NativeDocPool
ROOT = '00000000-0000-0000-0000-000000000000'
trace.ENABLED = True
pool = NativeDocPool(); st = Backend.init()
chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
    {'action': 'makeText', 'obj': 't'},
    {'action': 'link', 'obj': ROOT, 'key': 'text', 'value': 't'}]}]
prev, e = '_head', 0
ops = []
for i in range(300):
    e += 1
    ops.append({'action': 'ins', 'obj': 't', 'key': prev, 'elem': e})
    ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%d' % e,
                'value': 'x'})
    prev = 'a0:%d' % e
chs.append({'actor': 'a0', 'seq': 2, 'deps': {}, 'ops': ops})
trace.reset()
pool.apply_changes('doc', chs); st, _ = Backend.apply_changes(st, chs)
rep = trace.report()
assert 'resident.sharded_dispatch' in rep, rep
b2 = [{'actor': 'a0', 'seq': 3, 'deps': {}, 'ops': [
    {'action': 'del', 'obj': 't', 'key': 'a0:7'},
    {'action': 'ins', 'obj': 't', 'key': prev, 'elem': e + 1},
    {'action': 'set', 'obj': 't', 'key': 'a0:%d' % (e + 1),
     'value': 'Z'}]}]
pool.apply_changes('doc', b2); st, _ = Backend.apply_changes(st, b2)
assert pool.get_patch('doc') == Backend.get_patch(st)
print('SHARDED-RESIDENT-OK')
""".replace('REPO_PATH', repr(REPO))


def test_sharded_resident_on_virtual_mesh():
    """The promoted sp path: the pool's default entry point shards the
    element axis over every local device (8 virtual CPU devices here)
    with oracle-identical patches (VERDICT r2 #4).  AMTPU_MESH_SP_MIN
    routes this tiny arena past the sp fence (ISSUE 7): the lane pins
    the sharded kernel's PARITY, while the fence's routing policy has
    its own lanes in test_meshpool.py."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_RESIDENT='1',
               AMTPU_RESIDENT_MIN='16', AMTPU_MESH_SP_MIN='16',
               XLA_FLAGS='--xla_force_host_platform_device_count=8')
    out = subprocess.run([sys.executable, '-c', SHARDED], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'SHARDED-RESIDENT-OK' in out.stdout


# ---------------------------------------------------------------------------
# Pool-level resident batch state (ISSUE 6): the register/clock path's
# cross-batch cache.  Same subprocess pattern as the arena lanes: the
# AMTPU_RESIDENT* knobs latch per process.
# ---------------------------------------------------------------------------

# Multi-doc, multi-actor table workload: concurrent writes to shared
# root keys (kernel groups), single-actor private keys (the trivial
# route), a same-change duplicate assign (escalation food), deletes.
# Emitted as one builder so every lane below sees the same shape.
BATCH_WORKLOAD = r"""
ROOT = '00000000-0000-0000-0000-000000000000'

def build_round(r, docs=24, actors=4):
    payload = {}
    for d in range(docs):
        chs = []
        for a in range(actors):
            ops = [{'action': 'set', 'obj': ROOT,
                    'key': 'shared%d' % (r % 3),
                    'value': 'a%d r%d' % (a, r)},
                   {'action': 'set', 'obj': ROOT,
                    'key': 'p%d_%d' % (a, r), 'value': d * r + a}]
            if a == 0 and d % 5 == 0:
                # same-change duplicate assign: both survive as conflicts
                ops.append({'action': 'set', 'obj': ROOT,
                            'key': 'dup', 'value': 'x%d' % r})
                ops.append({'action': 'set', 'obj': ROOT,
                            'key': 'dup', 'value': 'y%d' % r})
            if a == 1 and r > 1:
                ops.append({'action': 'del', 'obj': ROOT,
                            'key': 'p0_%d' % (r - 1)})
            # deps empty: actors are mutually concurrent every round
            chs.append({'actor': 'w%d' % a, 'seq': r, 'deps': {},
                        'ops': ops})
        payload['doc%d' % d] = chs
    return payload
"""

BATCH_RESIDENT = r"""
import sys
sys.path.insert(0, REPO_PATH)
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu import backend as Backend
from automerge_tpu import faults, trace
from automerge_tpu.native import NativeDocPool
trace.ENABLED = True
WORKLOAD

pool = NativeDocPool()
states = {}

def apply_round(r, docs=24):
    payload = build_round(r, docs=docs)
    pool.apply_batch(payload)
    for d, chs in payload.items():
        st = states.get(d) or Backend.init()
        states[d], _ = Backend.apply_changes(st, chs)

def assert_parity(tag):
    for d, st in states.items():
        got, want = pool.get_patch(d), Backend.get_patch(st)
        assert got == want, '%s: %s diverged' % (tag, d)

# round 1 seeds the table; round 2 is SMALLER than round 1's pow2
# capacity slack, so it must be served by persisted rows (C++ hits)
# with a delta upload of only its own appends
apply_round(1)
apply_round(2, docs=6)
m = trace.metrics_snapshot()
assert m.get('resident.batch_full_uploads', 0) >= 1, m
assert m.get('resident.batch_hits', 0) >= 1, m
assert m.get('resident.batch_hit_rows', 0) >= 1, m
assert_parity('steady')

# cross-path invalidation: a failed batch ROLLS BACK -> the rows it
# appended are stale, the generation bumps, the next batch re-uploads
spec = faults.arm('native.mid', 'permanent')
try:
    pool.apply_batch(build_round(3, docs=6))
    raise SystemExit('armed fault did not fire')
except faults.InjectedFault:
    pass
finally:
    faults.disarm(spec)
apply_round(3, docs=6)   # the SAME round re-applies after rollback
m = trace.metrics_snapshot()
assert m.get('resident.batch_gen_invalidation', 0) >= 1, m
assert_parity('post-rollback')
print('BATCH-RESIDENT-OK')
""".replace('WORKLOAD', BATCH_WORKLOAD).replace('REPO_PATH', repr(REPO))


def test_batch_resident_steady_state_and_rollback_invalidation():
    """Pool-level clock cache: steady-state batches hit persisted rows
    (delta uploads only), rollback invalidates via the generation
    counter, and every patch stays byte-identical to the oracle."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_HOST_FULL='0',
               AMTPU_RESILIENCE='0')
    out = subprocess.run([sys.executable, '-c', BATCH_RESIDENT], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'BATCH-RESIDENT-OK' in out.stdout


WAVE_ERROR_IDENTITY = r"""
import sys
sys.path.insert(0, REPO_PATH)
import os
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu import trace
from automerge_tpu.errors import AutomergeError
from automerge_tpu.native import NativeDocPool
trace.ENABLED = True
ROOT = '00000000-0000-0000-0000-000000000000'

def build_payload():
    '''70 docs; payload-order doc 0 and doc 60 each carry a validation
    error on a DIFFERENT unknown object.  The serial contract: the
    FIRST error in application order surfaces (missing-early).'''
    payload = {}
    for d in range(70):
        obj = ROOT
        if d == 0:
            obj = 'missing-early'
        elif d == 60:
            obj = 'missing-late'
        payload['doc%03d' % d] = [
            {'actor': 'w0', 'seq': 1, 'deps': {},
             'ops': [{'action': 'set', 'obj': obj, 'key': 'k',
                      'value': d}]}]
    return payload

os.environ['AMTPU_PIPELINE_MIN_DOCS'] = '8'
errs = {}
for depth in ('1', '4'):
    os.environ['AMTPU_PIPELINE_DEPTH'] = depth
    pool = NativeDocPool()
    try:
        pool.apply_batch(build_payload())
        raise SystemExit('multi-error payload did not raise')
    except AutomergeError as e:
        errs[depth] = str(e)
assert 'missing-early' in errs['1'], errs['1']
assert errs['4'] == errs['1'], (
    'wave path surfaced a different error than serial:\n%r\n%r'
    % (errs['4'], errs['1']))
m = trace.metrics_snapshot()
assert m.get('pipeline.serial_replay', 0) >= 1, m
print('WAVE-ERROR-IDENTITY-OK')
""".replace('REPO_PATH', repr(REPO))


def test_wave_pipeline_error_identity_matches_serial():
    """A multi-error payload must surface the SAME error on the wave
    path as on the serial path (first in application order): pre-emit
    wave failures roll back atomically and replay unpipelined."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_HOST_FULL='0',
               AMTPU_RESILIENCE='0')
    out = subprocess.run([sys.executable, '-c', WAVE_ERROR_IDENTITY],
                         env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'WAVE-ERROR-IDENTITY-OK' in out.stdout
    assert 'RuntimeWarning' not in out.stderr, out.stderr


ACTOR_CAP_DROP = r"""
import sys
sys.path.insert(0, REPO_PATH)
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu import backend as Backend
from automerge_tpu import trace
from automerge_tpu.native import NativeDocPool
trace.ENABLED = True
WORKLOAD

pool = NativeDocPool()
states = {}

def apply(payload):
    pool.apply_batch(payload)
    for d, chs in payload.items():
        st = states.get(d) or Backend.init()
        states[d], _ = Backend.apply_changes(st, chs)

# 4 actors <= AMTPU_RESCLK_MAX_ACTORS=5: the pool table seeds on device
apply(build_round(1))
m = trace.metrics_snapshot()
assert m.get('resident.batch_full_uploads', 0) >= 1, m
assert pool._resclk.tab is not None

# two NEW actors push the pool past the cap: C++ permanently disables
# the cache, and the driver must release the device table (the buffer
# is pool-lifetime large and will never be read again)
over = {}
for d in range(6):
    over['doc%d' % d] = [
        {'actor': 'z%d' % a, 'seq': 1, 'deps': {},
         'ops': [{'action': 'set', 'obj': ROOT, 'key': 'shared0',
                  'value': 'z%d' % a}]}
        for a in (0, 1)]
apply(over)
m = trace.metrics_snapshot()
assert m.get('resident.batch_cache_dropped', 0) >= 1, m
assert pool._resclk.tab is None

# the pool keeps serving (non-resident) batches with oracle parity
apply(build_round(2))
m = trace.metrics_snapshot()
assert m.get('resident.batch_cache_dropped', 0) == 1, m
for d, st in states.items():
    assert pool.get_patch(d) == Backend.get_patch(st), d
print('CAP-DROP-OK')
""".replace('WORKLOAD', BATCH_WORKLOAD).replace('REPO_PATH', repr(REPO))


def test_batch_resident_actor_cap_releases_device_table():
    """Crossing AMTPU_RESCLK_MAX_ACTORS permanently disables the C++
    cache; the driver must drop its device copy of the clock table (it
    can be hundreds of MB and is never read again) and keep serving
    batches non-resident with oracle parity."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_HOST_FULL='0',
               AMTPU_RESILIENCE='0', AMTPU_RESCLK_MAX_ACTORS='5')
    out = subprocess.run([sys.executable, '-c', ACTOR_CAP_DROP], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'CAP-DROP-OK' in out.stdout


AB_PATCHES = r"""
import sys
sys.path.insert(0, REPO_PATH)
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu.native import NativeDocPool
WORKLOAD

pool = NativeDocPool()
for r in (1, 2, 3):
    pool.apply_batch(build_round(r))
for d in sorted('doc%d' % i for i in range(24)):
    sys.stdout.write('%s %r\n' % (d, pool.get_patch(d)))
""".replace('WORKLOAD', BATCH_WORKLOAD).replace('REPO_PATH', repr(REPO))


def _ab_run(**env_over):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS='cpu', **env_over)
    out = subprocess.run([sys.executable, '-c', AB_PATCHES], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


def test_batch_resident_ab_parity_both_exec_modes():
    """Byte parity of every patch across the resident-clock latch and
    both execution modes: the resident table must be unobservable."""
    ref = _ab_run(AMTPU_HOST_FULL='0', AMTPU_RESIDENT_CLK='1')
    assert ref == _ab_run(AMTPU_HOST_FULL='0', AMTPU_RESIDENT_CLK='0')
    assert ref == _ab_run(AMTPU_HOST_FULL='1')


def test_wave_pipeline_parity_and_staging_alias():
    """Cross-batch double-buffering (ISSUE 6 tentpole c): the wave path
    must be byte-identical to the unpipelined path, and the resident
    delta-upload staging must tolerate host-side mutation as soon as
    the scatter dispatch returns (jax zero-copying a still-in-flight
    numpy buffer is the PR-4 regression class this lane pins)."""
    script = r"""
import sys
sys.path.insert(0, REPO_PATH)
import os
import jax; jax.config.update('jax_platforms', 'cpu')
from automerge_tpu.native import NativeDocPool
import automerge_tpu.native.batch_resident as br
WORKLOAD

def run_rounds():
    pool = NativeDocPool()
    for r in (1, 2, 3):
        pool.apply_batch(build_round(r))
    return [pool.get_patch('doc%d' % i) for i in range(24)]

# uncorrupted reference FIRST (unpipelined, no hostile wrapper): the
# hostile arms below must match it, not merely each other -- identical
# corruption in both arms would otherwise pass
os.environ['AMTPU_PIPELINE_DEPTH'] = '1'
os.environ['AMTPU_PIPELINE_MIN_DOCS'] = '4'
ref = run_rounds()

# scribble over the delta-upload staging arrays the moment the scatter
# dispatch returns: if jax zero-copied them, the async execution reads
# garbage and parity below breaks
_orig = br._jit_row_scatter
def _hostile(donate):
    fn = _orig(donate)
    def run(tab, idx, rows):
        out = fn(tab, idx, rows)
        idx.fill(127)
        rows.fill(127)
        return out
    return run
br._jit_row_scatter = _hostile

results = {}
for depth in ('1', '4'):
    os.environ['AMTPU_PIPELINE_DEPTH'] = depth
    results[depth] = run_rounds()
assert results['1'] == ref, 'hostile staging mutation corrupted results'
assert results['4'] == ref, 'wave path diverged from clean reference'
print('WAVE-PARITY-OK')
""".replace('WORKLOAD', BATCH_WORKLOAD).replace('REPO_PATH', repr(REPO))
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_HOST_FULL='0')
    out = subprocess.run([sys.executable, '-c', script], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'WAVE-PARITY-OK' in out.stdout


# Text docs whose lists fall into several size classes, so a wave's
# list arenas cannot share one fused kernel layout: every such wave
# takes the non-fused path (`fallback.layout_batches`), the one whose
# list rank phase a leaves in flight for phase b to read.
TEXT_WORKLOAD = r"""
ROOT = '00000000-0000-0000-0000-000000000000'

def build_text_round(r, docs=24, bad=None):
    '''Round r of `docs` Text docs: round 1 creates each doc's text with
    2 to 35 characters; later rounds add two concurrent actors'
    inserts and a delete.  `bad` names a doc whose change targets an
    object that does not exist (a begin-time validation error).'''
    payload = {}
    for d in range(docs):
        tid = 'txt%d' % d
        chs = []
        if r == 1:
            ops = [{'action': 'makeText', 'obj': tid},
                   {'action': 'link', 'obj': ROOT, 'key': 'text',
                    'value': tid}]
            prev = '_head'
            for e in range(1, 3 + (d % 4) * 11):
                ops.append({'action': 'ins', 'obj': tid, 'key': prev,
                            'elem': e})
                ops.append({'action': 'set', 'obj': tid,
                            'key': 'w0:%d' % e,
                            'value': chr(97 + (d + e) % 26)})
                prev = 'w0:%d' % e
            chs.append({'actor': 'w0', 'seq': 1, 'deps': {}, 'ops': ops})
        else:
            for a in (1, 2):
                ops = []
                prev = 'w0:1'
                for k in range(1 + d % 3):
                    e = 1000 * r + 100 * a + k
                    ops.append({'action': 'ins', 'obj': tid, 'key': prev,
                                'elem': e})
                    ops.append({'action': 'set', 'obj': tid,
                                'key': 'w%d:%d' % (a, e), 'value': 'x'})
                    prev = 'w%d:%d' % (a, e)
                if a == 1 and r == 3:
                    ops.append({'action': 'del', 'obj': tid,
                                'key': 'w0:2'})
                chs.append({'actor': 'w%d' % a, 'seq': r - 1,
                            'deps': {'w0': 1}, 'ops': ops})
        if 'doc%d' % d == bad:
            chs[-1]['ops'].append({'action': 'set', 'obj': 'missing',
                                   'key': 'k', 'value': 1})
        payload['doc%d' % d] = chs
    return payload
"""


def test_wave_pipeline_parity_text_layout_fallback():
    """The wave pipeline on the non-fused layout path (multi-size-class
    Text waves): phase a leaves each wave's list rank on the device and
    phase b reads it, so wave 1's C++ begin runs before wave 0's rank
    is read.  Every patch stays byte-identical to a clean unpipelined
    reference and to the oracle, with the resident clock table's
    delta-upload staging scribbled over the moment its dispatch
    returns (jax zero-copying a host buffer still in flight)."""
    script = r"""
import sys
sys.path.insert(0, REPO_PATH)
import os
import jax; jax.config.update('jax_platforms', 'cpu')
import numpy as np
from automerge_tpu import backend as Backend
from automerge_tpu import telemetry, trace
from automerge_tpu import native
from automerge_tpu.native import NativeDocPool
import automerge_tpu.native.batch_resident as br
WORKLOAD

def run_rounds():
    pool = NativeDocPool()
    for r in (1, 2, 3):
        pool.apply_batch(build_text_round(r))
    return [pool.get_patch('doc%d' % i) for i in range(24)]

os.environ['AMTPU_PIPELINE_DEPTH'] = '1'
os.environ['AMTPU_PIPELINE_MIN_DOCS'] = '4'
ref = run_rounds()
states = {}
for r in (1, 2, 3):
    for d, chs in build_text_round(r).items():
        states[d], _ = Backend.apply_changes(states.get(d) or Backend.init(),
                                             chs)
assert ref == [Backend.get_patch(states['doc%d' % i]) for i in range(24)], \
    'unpipelined reference diverged from the oracle'

_orig = br._jit_row_scatter
def _hostile(donate):
    fn = _orig(donate)
    def run(tab, idx, rows):
        out = fn(tab, idx, rows)
        idx.fill(127)
        rows.fill(127)
        return out
    return run
br._jit_row_scatter = _hostile

# call order: each C++ begin, each wave's dispatched rank, each read
events = []
L = native.lib()
_begin = L.amtpu_begin
def begin(*a):
    events.append(('begin', None))
    return _begin(*a)
L.amtpu_begin = begin
_read = telemetry.d2h_read
def d2h_read(x):
    events.append(('read', id(x)))
    return _read(x)
telemetry.d2h_read = d2h_read
_phase_a = NativeDocPool._phase_a
def phase_a(self, payload, overlapped=False):
    ctx = _phase_a(self, payload, overlapped=overlapped)
    if overlapped and ctx.get('mode') == 'old' and ctx['rank'] is not None:
        assert not isinstance(ctx['rank'], np.ndarray), 'rank read in phase a'
        assert hasattr(ctx['rank'], 'is_ready'), type(ctx['rank'])
        events.append(('wave', id(ctx['rank'])))
    elif overlapped:
        events.append(('wave', None))
    return ctx
NativeDocPool._phase_a = phase_a

results = {}
for depth in ('1', '4'):
    os.environ['AMTPU_PIPELINE_DEPTH'] = depth
    trace.metrics_reset()
    results[depth] = run_rounds()
    m = trace.metrics_snapshot()
    assert m.get('fallback.layout_batches', 0) > 0, m
    assert m.get('collect.deferred_rank', 0) > 0, m
    assert m.get('collect.deferred_rank', 0) >= \
        m.get('collect.deferred_rank_ready', 0), m
    if depth == '4':
        assert m.get('pipeline.waves', 0) > m.get('pipeline.batches', 0), m
assert results['1'] == ref, 'hostile staging mutation corrupted results'
assert results['4'] == ref, 'wave path diverged from clean reference'

# in every wave batch whose first wave deferred its rank, the second
# wave's begin came before that rank was read
checked = 0
for i, (kind, rid) in enumerate(events):
    if kind != 'wave' or rid is None or events[i - 1][0] != 'begin':
        continue
    if i >= 2 and events[i - 2][0] == 'wave':
        continue                          # not the batch's first wave
    later = events[i + 1:]
    begins = [j for j, (k, _) in enumerate(later) if k == 'begin']
    reads = [j for j, (k, x) in enumerate(later) if k == 'read' and x == rid]
    assert reads, 'deferred rank never read'
    assert begins and begins[0] < reads[0], \
        'wave 0 read its rank before wave 1 began'
    checked += 1
assert checked > 0, events
print('TEXT-WAVE-PARITY-OK', checked)
""".replace('WORKLOAD', TEXT_WORKLOAD).replace('REPO_PATH', repr(REPO))
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_HOST_FULL='0',
               AMTPU_HOST_DOM='0')
    out = subprocess.run([sys.executable, '-c', script], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'TEXT-WAVE-PARITY-OK' in out.stdout


def test_wave_pipeline_unwind_drains_deferred_rank():
    """A later wave's begin fails while an earlier Text wave's rank is
    still deferred: the unwind drains that rank before it frees the
    wave's batch (the resolver reads zero-copied views of the batch's
    C++ columns), and the serial replay surfaces the serial error and
    leaves every doc byte-identical to the serial path."""
    script = r"""
import sys
sys.path.insert(0, REPO_PATH)
import ctypes
import os
import jax; jax.config.update('jax_platforms', 'cpu')
import msgpack
from automerge_tpu import native, trace
from automerge_tpu.errors import AutomergeError
from automerge_tpu.native import NativeDocPool
WORKLOAD

os.environ['AMTPU_PIPELINE_MIN_DOCS'] = '4'
os.environ['AMTPU_PIPELINE_DEPTH'] = '4'
_phase_a = NativeDocPool._phase_a

# which docs each wave holds (the split hashes doc ids only)
waves = []
def record_wave(self, payload, overlapped=False):
    if overlapped:
        waves.append(sorted(msgpack.unpackb(
            ctypes.string_at(*payload), raw=False)))
    return _phase_a(self, payload, overlapped=overlapped)
NativeDocPool._phase_a = record_wave
probe = NativeDocPool()
probe.apply_batch(build_text_round(1))
NativeDocPool._phase_a = _phase_a
assert len(waves) >= 2, waves
bad = waves[-1][0]

class RankProbe:
    '''Stands in for a wave's deferred device rank: records its drain.'''
    def __init__(self, arr, bh):
        self.arr, self.bh = arr, bh
    def is_ready(self):
        return self.arr.is_ready()
    def block_until_ready(self):
        events.append(('drain', self.bh))
        return self.arr.block_until_ready()
    def __array__(self, dtype=None, copy=None):
        return self.arr.__array__(dtype)

events = []
def probe_wave(self, payload, overlapped=False):
    ctx = _phase_a(self, payload, overlapped=overlapped)
    if overlapped and ctx.get('mode') == 'old' and ctx['rank'] is not None:
        ctx['rank'] = RankProbe(ctx['rank'], ctx['bh'])
        events.append(('deferred', ctx['bh']))
    return ctx
_free = native._free_batch
def free(bh):
    events.append(('free', bh))
    return _free(bh)

out = {}
for depth in ('1', '4'):
    os.environ['AMTPU_PIPELINE_DEPTH'] = depth
    NativeDocPool._phase_a = probe_wave
    native._free_batch = free
    pool = NativeDocPool()
    pool.apply_batch(build_text_round(1))
    del events[:]
    trace.metrics_reset()
    try:
        pool.apply_batch(build_text_round(2, bad=bad))
        raise SystemExit('payload with a bad doc did not raise')
    except AutomergeError as e:
        err = str(e)
    unwound = list(events)
    m = trace.metrics_snapshot()
    NativeDocPool._phase_a = _phase_a
    native._free_batch = _free
    pool.apply_batch(build_text_round(2))
    pool.apply_batch(build_text_round(3))
    out[depth] = (err, [pool.get_patch('doc%d' % i) for i in range(24)],
                  unwound, m)
    assert native.live_batch_handles() == 0

err1, patches1, _, _ = out['1']
err4, patches4, unwound, m = out['4']
assert 'missing' in err1, err1
assert err4 == err1, (err4, err1)
assert patches4 == patches1, 'wave unwind left other bytes than serial'
assert m.get('pipeline.serial_replay', 0) >= 1, m
deferred = [bh for k, bh in unwound if k == 'deferred']
assert deferred, ('no wave deferred its rank before the failing begin',
                  unwound)
for bh in deferred:
    assert ('drain', bh) in unwound, ('deferred rank never drained', unwound)
    assert unwound.index(('drain', bh)) < unwound.index(('free', bh)), \
        'deferred rank drained after its batch was freed'
print('UNWIND-DRAIN-OK', len(deferred))
""".replace('WORKLOAD', TEXT_WORKLOAD).replace('REPO_PATH', repr(REPO))
    env = dict(os.environ, JAX_PLATFORMS='cpu', AMTPU_HOST_FULL='0',
               AMTPU_HOST_DOM='0', AMTPU_RESILIENCE='0')
    out = subprocess.run([sys.executable, '-c', script], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'UNWIND-DRAIN-OK' in out.stdout
