"""Benchmark driver -- batched TPU backend vs single-thread scalar backend.

Covers all five BASELINE.json configs (select with --config N or
AMTPU_BENCH_CONFIG; default 3, the headline shape):

  1  single Text doc, 2 actors, sequential char inserts
  2  many Map docs, 8 concurrent actors, random key set ops
  3  many Text docs, concurrent actors, interleaved insert/delete (RGA
     stress) delivered as ONE causal catch-up batch -- the "1M queued ops
     across 10k docs" north-star shape
  4  Table docs: concurrent row add/update with nested Map row values
  5  Connection/DocSet sync: 64 replicas, 100k-op backlog, full causal
     catch-up (BatchedReplicaSet: device-planned gossip, bytes shipping)

Methodology (all configs):
  * baseline: the same workload through `automerge_tpu.backend` -- the
    single-threaded host backend whose semantics mirror the reference's
    Node.js backend (`/root/reference/backend/op_set.js`).  Node itself is
    not installed in this image, so this scalar path is the measured
    denominator; it is byte-compatible with the reference (see
    tests/test_backend.py golden cases).  Measured on a sampled doc
    subset, reported as per-op rate.
  * parity: native patches must equal oracle patches on >= 10% of docs
    (workloads apply changes in identical order, so patches are
    byte-identical, not just tree-equal).
  * warmup: the workload runs twice on throwaway pools (first pass pays
    jit compiles, second settles dispatch/transfer paths); timed result
    is the median of 3 fresh-pool runs.
  * backend: every line names the device it ran on (`backend`:
    platform, kind, count).  Without an accelerator the run refuses, unless the caller
    asked for the CPU with JAX_PLATFORMS=cpu.

Prints ONE json line to stdout:
  {"metric": ..., "value": ..., "unit": "ops/sec", "vs_baseline": ...}
"""

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from automerge_tpu.utils.common import ROOT_ID  # noqa: E402


def device_info():
    """{platform, kind, count} of the backend this run uses.  Without an
    accelerator the run refuses, unless the caller asked for the CPU
    with JAX_PLATFORMS=cpu."""
    from automerge_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    devs = jax.devices()
    info = {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs)}
    if info['platform'] == 'cpu' and os.environ.get('JAX_PLATFORMS') != 'cpu':
        raise SystemExit('bench: no accelerator found; set '
                         'JAX_PLATFORMS=cpu for a CPU run')
    return info


def env_int(name, default):
    return int(os.environ.get(name, default))


N_DOCS = env_int('AMTPU_BENCH_DOCS', 4096)
N_ACTORS = env_int('AMTPU_BENCH_ACTORS', 8)
N_ROUNDS = env_int('AMTPU_BENCH_ROUNDS', 2)
OPS_PER_CHANGE = env_int('AMTPU_BENCH_OPS_PER_CHANGE', 16)
ORACLE_DOCS = env_int('AMTPU_BENCH_ORACLE_DOCS', 0)   # 0 = 10% of docs
SEED = env_int('AMTPU_BENCH_SEED', 7)
# 0 = let ShardedNativePool pick its mode-aware default (20 for the
# 1-core pipeline, one per core for threads -- the 20-shard rationale is
# specific to pipeline overlap and would oversubscribe threads mode)
N_SHARDS = env_int('AMTPU_BENCH_SHARDS', 0)

# Every multiplier this harness reports divides by the repo's own
# single-thread Python scalar oracle (`automerge_tpu.backend`), byte-
# compatible with the reference backend.  The north-star target
# (BASELINE.json) names the Node.js backend as the denominator; Node is
# not installed in this image, so the oracle is the stand-in -- named
# in every JSON line so no multiplier is quoted without its
# denominator (VERDICT r4 #4).
BASELINE_NAME = 'python-scalar-oracle'


# ---------------------------------------------------------------------------
# workload builders: {doc: [change...]} per config
# ---------------------------------------------------------------------------

def _text_doc_changes(doc, rng, n_actors, n_rounds, ops_per_change):
    """Interleaved concurrent Text insert/delete (config 3 shape); the
    shared generator with bench's rng delete policy (the rng draw happens
    for every slot, keeping the stream identical to earlier rounds)."""
    from automerge_tpu.parallel.mesh_encode import text_doc_changes
    return text_doc_changes(
        'text-%d' % doc, n_actors, n_rounds, ops_per_change,
        lambda i, a, has: rng.random() < 0.15 and has)


def build_config_1(rng):
    """Single Text doc, 2 actors, sequential char inserts."""
    chars = env_int('AMTPU_BENCH_C1_CHARS', 10000)
    per_change = 50
    tid = 'text-0'
    changes = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
        {'action': 'makeText', 'obj': tid},
        {'action': 'link', 'obj': ROOT_ID, 'key': 'text', 'value': tid}]}]
    seqs = {'a0': 1, 'a1': 0}
    prev = '_head'
    elem = 0
    for start in range(0, chars, per_change):
        actor = 'a%d' % ((start // per_change) % 2)
        ops = []
        for _ in range(min(per_change, chars - start)):
            elem += 1
            ops.append({'action': 'ins', 'obj': tid, 'key': prev,
                        'elem': elem})
            ops.append({'action': 'set', 'obj': tid,
                        'key': '%s:%d' % (actor, elem),
                        'value': chr(97 + elem % 26)})
            prev = '%s:%d' % (actor, elem)
        seqs[actor] += 1
        deps = {a: s for a, s in seqs.items() if a != actor and s}
        changes.append({'actor': actor, 'seq': seqs[actor], 'deps': deps,
                        'ops': ops})
    return {0: changes}, 'text_single_doc_ops_per_sec'


def build_config_2(rng):
    """Map docs, 8 concurrent actors, random key set ops (this Automerge
    version has no Counter CRDT; "inc" models as read-modify-write set,
    see BASELINE.md)."""
    docs = env_int('AMTPU_BENCH_C2_DOCS', 1024)
    rounds = env_int('AMTPU_BENCH_C2_ROUNDS', 8)
    batch = {}
    for d in range(docs):
        changes = []
        for r in range(1, rounds + 1):
            for a in range(N_ACTORS):
                actor = 'a%d' % a
                ops = []
                # distinct keys per change: the reference frontend dedupes
                # assignments per (obj, key) within one change
                # (ensureSingleAssignment, frontend/index.js:53), so real
                # change streams never assign a key twice
                for key_n in rng.sample(range(max(32, OPS_PER_CHANGE)),
                                         OPS_PER_CHANGE):
                    key = 'k%d' % key_n
                    if rng.random() < 0.1:
                        ops.append({'action': 'del', 'obj': ROOT_ID,
                                    'key': key})
                    elif rng.random() < 0.1:
                        ops.append({'action': 'set', 'obj': ROOT_ID,
                                    'key': key, 'value': r * 1000 + a,
                                    'datatype': 'timestamp'})
                    else:
                        ops.append({'action': 'set', 'obj': ROOT_ID,
                                    'key': key, 'value': r * 1000 + a})
                changes.append({'actor': actor, 'seq': r, 'deps': {},
                                'ops': ops})
        batch[d] = changes
    return batch, 'map_concurrent_ops_per_sec'


def build_config_3(rng):
    batch = {d: _text_doc_changes(d, rng, N_ACTORS, N_ROUNDS,
                                  OPS_PER_CHANGE)
             for d in range(N_DOCS)}
    return batch, 'text_catchup_ops_per_sec'


def build_config_4(rng):
    """Table docs: concurrent row add/update, nested Map row values
    (reference Table semantics: frontend/table.js:26-196; a row add is
    makeMap + field sets + link into the table keyed by row id)."""
    docs = env_int('AMTPU_BENCH_C4_DOCS', 1024)
    rows_per_actor = env_int('AMTPU_BENCH_C4_ROWS', 16)
    batch = {}
    for d in range(docs):
        table = 'table-%d' % d
        changes = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeTable', 'obj': table},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'rows',
             'value': table}]}]
        row_ids = []
        for a in range(N_ACTORS):
            actor = 'a%d' % a
            seq = 2 if a == 0 else 1
            ops = []
            for i in range(rows_per_actor):
                row = 'row-%d-%d-%d' % (d, a, i)
                ops.extend([
                    {'action': 'makeMap', 'obj': row},
                    {'action': 'set', 'obj': row, 'key': 'name',
                     'value': 'r%d' % i},
                    {'action': 'set', 'obj': row, 'key': 'n',
                     'value': i * a},
                    {'action': 'link', 'obj': table, 'key': row,
                     'value': row}])
                row_ids.append(row)
            changes.append({'actor': actor, 'seq': seq,
                            'deps': {'a0': 1}, 'ops': ops})
        # concurrent updates of random existing rows
        for a in range(N_ACTORS):
            actor = 'a%d' % a
            seq = 3 if a == 0 else 2
            ops = []
            for _ in range(rows_per_actor):
                row = row_ids[rng.randrange(len(row_ids))]
                ops.append({'action': 'set', 'obj': row, 'key': 'n',
                            'value': rng.randrange(1000)})
            changes.append({'actor': actor, 'seq': seq,
                            'deps': {'a%d' % b: (2 if b == 0 else 1)
                                     for b in range(N_ACTORS)},
                            'ops': ops})
        batch[d] = changes
    return batch, 'table_rows_ops_per_sec'


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def _alt_mode_env(alt):
    """Context manager flipping AMTPU_HOST_FULL for a sibling-mode
    measurement, restoring the caller's env on exit."""
    import contextlib

    @contextlib.contextmanager
    def cm():
        prior = os.environ.get('AMTPU_HOST_FULL')
        os.environ['AMTPU_HOST_FULL'] = '0' if alt == 'kernel' else '1'
        try:
            yield
        finally:
            if prior is None:
                os.environ.pop('AMTPU_HOST_FULL', None)
            else:
                os.environ['AMTPU_HOST_FULL'] = prior
    return cm()


def _alt_block(rate, oracle_rate, stats, ok):
    """Sibling-mode result block; parity failure zeroes the numbers so
    the regression is loud in the artifact (and main() fails the rc)."""
    block = {'value': round(rate, 1),
             'vs_baseline': round(rate / oracle_rate, 3)}
    block.update(stats)
    if not ok:
        block.update(parity=False, value=0.0, vs_baseline=0.0)
    return block


def _current_mode():
    """Name of the execution mode the pools will resolve right now
    (per-batch knobs + platform default)."""
    from automerge_tpu.native import _host_full_on
    res = os.environ.get('AMTPU_RESIDENT')
    if res not in (None, '', '0'):
        return 'resident'
    return 'host_full' if _host_full_on() else 'kernel'


def _measure_mode(make_pool, payload, total_ops, label):
    """Warmup + 3 timed runs + fallback counters + one traced phase pass
    for whatever execution mode the current env resolves to.  Returns
    (median_rate, pool_from_last_run, stats)."""
    import gc

    from automerge_tpu import telemetry, trace

    # ---- warmup ----------------------------------------------------------
    t0 = time.perf_counter()
    make_pool().apply_batch_bytes(payload)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    make_pool().apply_batch_bytes(payload)
    warm2_s = time.perf_counter() - t0
    print('[%s] warmup (incl. jit compile): %.2fs + %.2fs'
          % (label, warm_s, warm2_s), file=sys.stderr)

    # ---- timed runs ------------------------------------------------------
    times = []
    pool = None
    # one measurement window per mode: flat metrics AND the registry
    # reset together, so the telemetry block captured below describes
    # exactly these 3 timed runs (not warmups, parity checks, or a
    # sibling mode's passes)
    trace.metrics_reset()
    telemetry.registry.reset()
    for run in range(3):
        trace.reset()
        pool = make_pool()
        t0 = time.perf_counter()
        pool.apply_batch_bytes(payload)
        times.append(time.perf_counter() - t0)
        if trace.ENABLED and run == 0:
            print(trace.report(), file=sys.stderr)
        gc.collect()
    med_s = sorted(times)[1]
    rate = total_ops / med_s
    print('[%s] pool runs: %s -> median %.0f ops/sec'
          % (label, ['%.2fs' % t for t in times], rate), file=sys.stderr)
    # oracle-fallback visibility: counts accumulated over the 3 timed
    # runs (a degraded run must be visible without AMTPU_TRACE)
    fallbacks = {k.split('.', 1)[1]: int(v) for k, v in
                 trace.metrics_snapshot().items()
                 if k.startswith('fallback.')}
    print('[%s] fallbacks (3 runs): %s' % (label, fallbacks or 'none'),
          file=sys.stderr)
    # captured HERE, before the phase pass resets the flat metrics: the
    # embedded block describes the timed runs, so a degraded run's
    # fallback counts survive into the artifact
    telemetry_block = telemetry.bench_block()

    # ---- phase pass ------------------------------------------------------
    # One extra TRACED run: per-phase seconds land in the BENCH line
    # machine-readable (the quickbench --phases table), so phase-share
    # claims -- device.collect above all -- are attributable from the
    # artifact alone (ISSUE 6).  Runs outside the timed window because
    # tracing costs a few percent; `collect_share` is pre-divided
    # against the summed native batch time, the share basis the
    # quickbench table prints.
    was_enabled = telemetry.enabled()
    telemetry.reset_all()
    telemetry.enable()
    try:
        ph_pool = make_pool()
        t0 = time.perf_counter()
        ph_pool.apply_batch_bytes(payload)
        ph_wall = time.perf_counter() - t0
        ph_block = telemetry.bench_block()
    finally:
        if not was_enabled:
            telemetry.disable()
        telemetry.reset_all()
    telemetry_block['phases'] = ph_block.get('phases') or {}
    telemetry_block['phase_wall_s'] = round(ph_wall, 4)
    share, _coll, _basis = telemetry.collect_share(ph_block)
    telemetry_block['collect_share'] = round(share, 4)
    print('[%s] phase pass: %.2fs wall, device.collect share %.1f%%'
          % (label, ph_wall, 100 * telemetry_block['collect_share']),
          file=sys.stderr)
    return rate, pool, {'fallbacks': fallbacks,
                        'telemetry': telemetry_block}


def run_batch_config(build, rng, both_modes=True):
    """Shared driver for configs 1-4: one causal catch-up batch.

    Measures the platform-default execution mode as the headline AND
    (both_modes) the opposite mode as a sibling block in the same JSON
    line -- the kernel path (AMTPU_HOST_FULL=0) when the default is the
    full host path, the host path when the default is the kernels -- so
    a regression in either mode fails loudly in every artifact
    (VERDICT r4 #1)."""
    import msgpack

    from automerge_tpu import backend as Backend
    from automerge_tpu.native import NativeDocPool, ShardedNativePool

    batch, metric = build(rng)
    doc_ids = list(batch)
    total_ops = sum(len(c['ops']) for chs in batch.values() for c in chs)
    per_doc_ops = {d: sum(len(c['ops']) for c in batch[d])
                   for d in doc_ids}
    print('workload: %d docs, %d total ops'
          % (len(doc_ids), total_ops), file=sys.stderr)

    def make_pool():
        # shard count resolves per mode: host_full wants 1, the kernel
        # pipeline wants overlap granularity (default 20)
        if N_SHARDS:
            n = min(N_SHARDS, len(doc_ids))
        else:
            n = min(ShardedNativePool.default_shards(), len(doc_ids))
        return ShardedNativePool(n) if n > 1 else NativeDocPool()

    # ---- baseline: single-thread scalar backend on a >=10% subset -------
    # median of 3 passes: the shared host core's speed wobbles between
    # windows, and a slow scalar window inflates vs_baseline dishonestly
    n_oracle = ORACLE_DOCS or max(1, len(doc_ids) // 10)
    oracle_docs = doc_ids[:min(n_oracle, len(doc_ids))]
    oracle_times = []
    for _ in range(3):
        oracle_states = {}
        t0 = time.perf_counter()
        for d in oracle_docs:
            state = Backend.init()
            state, _patch = Backend.apply_changes(state, batch[d])
            oracle_states[d] = state
        oracle_times.append(time.perf_counter() - t0)
    oracle_s = sorted(oracle_times)[1]
    oracle_ops = sum(per_doc_ops[d] for d in oracle_docs)
    oracle_rate = oracle_ops / oracle_s
    print('baseline (scalar backend, %d docs): %s -> median %.0f ops/sec'
          % (len(oracle_docs), ['%.2fs' % t for t in oracle_times],
             oracle_rate), file=sys.stderr)

    # ---- wire payload (the split-deployment protocol form) ---------------
    keyed = {NativeDocPool._doc_key(d): chs for d, chs in batch.items()}
    payload = msgpack.packb(keyed, use_bin_type=True)

    def parity_ok(pool, label):
        for d in oracle_docs:
            if pool.get_patch(d) != Backend.get_patch(oracle_states[d]):
                print('[%s] PARITY FAILURE on doc %r' % (label, d),
                      file=sys.stderr)
                return False
        print('[%s] parity: ok (%d docs byte-identical)'
              % (label, len(oracle_docs)), file=sys.stderr)
        return True

    # ---- headline: the platform-default mode -----------------------------
    mode = _current_mode()
    rate, pool, stats = _measure_mode(make_pool, payload, total_ops, mode)
    if not parity_ok(pool, mode):
        return {'metric': metric, 'value': 0.0, 'unit': 'ops/sec',
                'vs_baseline': 0.0, 'baseline': BASELINE_NAME,
                'mode': mode, 'parity': False}
    result = {'metric': metric, 'value': round(rate, 1),
              'unit': 'ops/sec',
              'vs_baseline': round(rate / oracle_rate, 3),
              'baseline': BASELINE_NAME, 'mode': mode}
    result.update(stats)

    # ---- sibling: the opposite execution mode ----------------------------
    # resident mode can't be entered here (AMTPU_RESIDENT latches in the
    # native lib's static init at the first batch above) -- `--mode
    # resident` / `--all` run it in a fresh process instead
    if both_modes and mode in ('host_full', 'kernel'):
        alt = 'kernel' if mode == 'host_full' else 'host_full'
        with _alt_mode_env(alt):
            arate, apool, astats = _measure_mode(
                make_pool, payload, total_ops, alt)
            result['%s_path' % alt] = _alt_block(
                arate, oracle_rate, astats, parity_ok(apool, alt))
    return result


def run_config_5(rng, both_modes=True):
    """64 replicas, ~100k-op backlog, full causal catch-up.  The measured
    rate counts op-APPLICATIONS (every replica ingests every foreign op --
    the work a full catch-up performs, identical to what the reference's
    64 pairwise Connections would do)."""
    from automerge_tpu import backend as Backend
    from automerge_tpu.native import NativeDocPool
    from automerge_tpu.sync.replica_set import BatchedReplicaSet, \
        patch_to_tree

    n_replicas = env_int('AMTPU_BENCH_C5_REPLICAS', 64)
    n_docs = env_int('AMTPU_BENCH_C5_DOCS', 8)
    n_changes = env_int('AMTPU_BENCH_C5_CHANGES', 13)
    ops_per_change = env_int('AMTPU_BENCH_C5_OPS', 15)

    # backlog: each replica authors one actor's stream per doc.  Keys are
    # distinct per change (the reference frontend dedupes assignments per
    # change, ensureSingleAssignment): same-change duplicate assigns have
    # history-dependent conflict-tie order in the reference itself, so no
    # realistic change stream contains them.
    by_replica = [dict() for _ in range(n_replicas)]
    union = {d: [] for d in range(n_docs)}
    key_space = range(max(64, ops_per_change))
    for d in range(n_docs):
        for r in range(n_replicas):
            actor = 'a%03d' % r
            for seq in range(1, n_changes + 1):
                ops = [{'action': 'set', 'obj': ROOT_ID,
                        'key': 'k%d' % k,
                        'value': '%s-%d-%d' % (actor, seq, i)}
                       for i, k in enumerate(
                           rng.sample(key_space, ops_per_change))]
                ch = {'actor': actor, 'seq': seq, 'deps': {}, 'ops': ops}
                by_replica[r].setdefault(d, []).append(ch)
                union[d].append(ch)
    backlog_ops = sum(len(c['ops']) for chs in union.values()
                      for c in chs)
    # full catch-up applies every foreign op at every replica
    total_applications = backlog_ops * (n_replicas - 1)
    print('workload: %d replicas x %d docs, backlog %d ops -> %d '
          'op-applications' % (n_replicas, n_docs, backlog_ops,
                               total_applications), file=sys.stderr)

    # ---- baseline: scalar backend ingesting one doc's union --------------
    oracle_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        state = Backend.init()
        state, _ = Backend.apply_changes(state, union[0])
        oracle_times.append(time.perf_counter() - t0)
    oracle_s = sorted(oracle_times)[1]
    oracle_rate = len(union[0]) * ops_per_change / oracle_s
    print('baseline (scalar, 1-doc union): %s -> median %.0f ops/sec'
          % (['%.2fs' % t for t in oracle_times], oracle_rate),
          file=sys.stderr)

    def load_set():
        rs = BatchedReplicaSet(n_replicas, pool_factory=NativeDocPool)
        for r, by_doc in enumerate(by_replica):
            rs.apply_batch(r, by_doc)
        return rs

    # warmup (jit compiles for plan + apply kernels)
    t0 = time.perf_counter()
    load_set().catch_up()
    print('warmup: %.2fs' % (time.perf_counter() - t0), file=sys.stderr)

    from automerge_tpu import trace

    def measure_catchup(label):
        times = []
        rs = None
        fallbacks = {}
        rounds = None
        for _ in range(3):
            rs = load_set()
            # metric window covers ONLY the measured catch-up --
            # fallbacks during the untimed backlog load must not flag
            # the run
            trace.metrics_reset()
            t0 = time.perf_counter()
            rounds = rs.catch_up()
            times.append(time.perf_counter() - t0)
            for k, v in trace.metrics_snapshot().items():
                if k.startswith('fallback.'):
                    key = k.split('.', 1)[1]
                    fallbacks[key] = fallbacks.get(key, 0) + int(v)
        sync_s = sorted(times)[1]
        rate = total_applications / sync_s
        print('[%s] fallbacks (3 runs): %s' % (label, fallbacks or 'none'),
              file=sys.stderr)
        print('[%s] catch-up runs: %s (rounds: %s) -> median %.0f ops/sec'
              % (label, ['%.2fs' % t for t in times], rounds, rate),
              file=sys.stderr)
        return rate, rs, fallbacks

    def parity_ok(rs, label):
        # every replica's tree equals the oracle union
        if not rs.converged():
            return False
        for d in range(n_docs):
            patch = rs.assert_identical(d)
            st = Backend.init()
            st, _ = Backend.apply_changes(st, union[d])
            want = Backend.get_patch(st)
            if patch['clock'] != want['clock'] or \
                    patch_to_tree(patch) != patch_to_tree(want):
                print('[%s] PARITY FAILURE on doc %d' % (label, d),
                      file=sys.stderr)
                return False
        print('[%s] parity: ok (%d docs, %d replicas convergent + '
              'oracle-equal)' % (label, n_docs, n_replicas),
              file=sys.stderr)
        return True

    mode = _current_mode()
    rate, rs, fallbacks = measure_catchup(mode)
    if not parity_ok(rs, mode):
        return {'metric': 'replica_catchup_ops_per_sec', 'value': 0.0,
                'unit': 'ops/sec', 'vs_baseline': 0.0,
                'baseline': BASELINE_NAME, 'mode': mode, 'parity': False}
    result = {'metric': 'replica_catchup_ops_per_sec',
              'value': round(rate, 1), 'unit': 'ops/sec',
              'vs_baseline': round(rate / oracle_rate, 3),
              'baseline': BASELINE_NAME, 'mode': mode,
              'fallbacks': fallbacks}

    if both_modes and mode in ('host_full', 'kernel'):
        alt = 'kernel' if mode == 'host_full' else 'host_full'
        with _alt_mode_env(alt):
            arate, ars, afb = measure_catchup(alt)
            result['%s_path' % alt] = _alt_block(
                arate, oracle_rate, {'fallbacks': afb},
                parity_ok(ars, alt))
    return result


def run_config_1_mesh(rng):
    """Config 1 through the MESH path (the sequence-parallel showcase):
    the single long Text doc is mesh-encoded (arena columns laid out for
    sp sharding) and resolved by the shard_map step on a 1-chip mesh --
    the same compiled path dryrun_multichip validates on N virtual
    devices.  Parity pins the kernel outputs against the pool's public
    patches."""
    from functools import partial

    import jax
    import numpy as np

    from automerge_tpu import backend as Backend
    from automerge_tpu.parallel import mesh as M
    from automerge_tpu.parallel import mesh_encode

    workload, _ = build_config_1(rng)
    total_ops = sum(len(c['ops']) for chs in workload.values()
                    for c in chs)
    print('workload: 1 doc, %d ops (mesh/sp path)' % total_ops,
          file=sys.stderr)

    oracle_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        state = Backend.init()
        state, _p = Backend.apply_changes(state, workload[0])
        oracle_times.append(time.perf_counter() - t0)
    oracle_s = sorted(oracle_times)[1]
    oracle_rate = total_ops / oracle_s
    print('baseline (scalar backend): %s -> median %.0f ops/sec'
          % (['%.2fs' % t for t in oracle_times], oracle_rate),
          file=sys.stderr)

    batch, meta = mesh_encode.encode_batch(workload, sp=1)
    n_iters = M.list_rank.ceil_log2(max(meta['max_arena'], 1)) + 1
    mesh = M.make_mesh(1, sp=1)
    step = M.build_sharded_step(mesh, n_linearize_iters=n_iters)
    sharded = M.shard_batch(mesh, batch)

    t0 = time.perf_counter()
    out = step(sharded)
    jax.block_until_ready(out)
    print('warmup (incl. jit compile): %.2fs'
          % (time.perf_counter() - t0), file=sys.stderr)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = step(sharded)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    mesh_s = sorted(times)[1]
    rate = total_ops / mesh_s
    print('mesh-step runs: %s -> median %.0f ops/sec'
          % (['%.2fs' % t for t in times], rate), file=sys.stderr)

    out = {k: np.asarray(v) for k, v in out.items()}
    try:
        mesh_encode.verify_against_pool(workload, meta, out)
    except AssertionError as e:
        print('PARITY FAILURE: %s' % e, file=sys.stderr)
        return {'metric': 'text_single_doc_mesh_ops_per_sec', 'value': 0.0,
                'unit': 'ops/sec', 'vs_baseline': 0.0,
                'baseline': BASELINE_NAME, 'mode': 'mesh', 'parity': False}
    print('parity: ok (kernel outputs match pool patches)',
          file=sys.stderr)
    return {'metric': 'text_single_doc_mesh_ops_per_sec',
            'value': round(rate, 1), 'unit': 'ops/sec',
            'vs_baseline': round(rate / oracle_rate, 3),
            'baseline': BASELINE_NAME, 'mode': 'mesh'}


def _scaling_workload_payload(n_docs):
    """MULTICHIP scaling workload as a wire payload (the one builder
    lives in mesh_encode.scaling_workload, shared with the mesh-check
    gate and the dryrun)."""
    import msgpack

    from automerge_tpu.parallel import mesh_encode
    docs = mesh_encode.scaling_workload(n_docs)
    total_ops = sum(len(c['ops']) for chs in docs.values() for c in chs)
    return msgpack.packb(docs, use_bin_type=True), total_ops


def run_multichip_child(dp):
    """One MULTICHIP line: the scaling workload through the first-class
    mesh pool mode (`make_pool` under AMTPU_MESH=dp, exported by the
    parent together with the matching device count) on the full
    `_measure_mode` protocol -- warmup, 3 fresh-pool timed steps,
    TRACED phase pass."""
    import jax

    from automerge_tpu.native import make_pool
    n_docs = env_int('AMTPU_MC_DOCS', 2048)
    payload, total_ops = _scaling_workload_payload(n_docs)
    if os.environ.get('AMTPU_MC_LIGHT'):
        # light re-measurement round (parent interleaves these across
        # the dp ladder to cancel host drift): warm + 3 timed steps,
        # no phase pass
        make_pool().apply_batch_bytes(payload)
        walls = []
        for _ in range(3):
            pool = make_pool()
            t0 = time.perf_counter()
            pool.apply_batch_bytes(payload)
            walls.append(time.perf_counter() - t0)
        med = sorted(walls)[1]
        print(json.dumps({'metric': 'multichip_pool_ops_per_sec',
                          'light': True, 'dp': dp,
                          'value': round(total_ops / med, 1),
                          'step_wall_s': round(med, 4)}))
        return 0
    rate, _pool, stats = _measure_mode(make_pool, payload, total_ops,
                                       'mesh dp=%d' % dp)
    result = {
        'metric': 'multichip_pool_ops_per_sec',
        'value': round(rate, 1), 'unit': 'ops/sec', 'mode': 'mesh',
        'baseline': 'mesh_dp1',       # parent fills vs_baseline from dp=1
        'dp': dp, 'sp': 1,
        'devices': len(jax.devices()), 'cores': os.cpu_count(),
        'docs': n_docs, 'ops': total_ops,
        'step_wall_s': round(total_ops / rate, 4) if rate else 0.0,
        'fallbacks': stats['fallbacks'],
        'telemetry': stats['telemetry'],
    }
    print(json.dumps(result))
    return 0


def run_multichip_sp_child(sp_min):
    """sp-crossover probe arm: steady-state resident edit batches on one
    long Text doc per arena size, with the sp fence pinned by the parent
    (AMTPU_MESH_SP_MIN=16 -> sharded arm, huge -> dp-only arm).  Prints
    {'rows': {elems: median_edit_s}, 'sp_engaged': ...}."""
    from automerge_tpu import telemetry
    from automerge_tpu.native import NativeDocPool
    sizes = [int(s) for s in os.environ.get(
        'AMTPU_MC_SP_SIZES', '8192,32768,131072,262144').split(',')]
    pool = NativeDocPool()
    telemetry.metrics_reset()
    rows = {}
    for n_elems in sizes:
        doc = 'sp-%d' % n_elems
        chs = [{'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeText', 'obj': 't'},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
             'value': 't'}]}]
        prev, e, ops = '_head', 0, []
        for _ in range(n_elems):
            e += 1
            ops.append({'action': 'ins', 'obj': 't', 'key': prev,
                        'elem': e})
            ops.append({'action': 'set', 'obj': 't', 'key': 'a0:%d' % e,
                        'value': 'x'})
            prev = 'a0:%d' % e
        chs.append({'actor': 'a0', 'seq': 2, 'deps': {}, 'ops': ops})
        pool.apply_changes(doc, chs)
        seq = 2
        times = []
        for k in range(6):
            seq += 1
            e += 1
            edit = [{'actor': 'a0', 'seq': seq, 'deps': {}, 'ops': [
                {'action': 'ins', 'obj': 't', 'key': prev, 'elem': e},
                {'action': 'set', 'obj': 't', 'key': 'a0:%d' % e,
                 'value': 'y'}]}]
            prev = 'a0:%d' % e
            t0 = time.perf_counter()
            pool.apply_changes(doc, edit)
            if k:                          # first edit pays jit compile
                times.append(time.perf_counter() - t0)
        rows[n_elems] = round(sorted(times)[len(times) // 2], 4)
    snap = telemetry.metrics_snapshot()
    print(json.dumps({'rows': rows, 'sp_min': sp_min,
                      'sp_engaged': int(snap.get('mesh.sp_engaged', 0)),
                      'sp_fenced': int(snap.get('mesh.sp_fenced', 0))}))
    return 0


def run_multichip(args):
    """--multichip: the MULTICHIP artifact through the first-class pool
    mode (ISSUE 7 satellite 2) -- retires the dryrun tail-scrape.  One
    fresh subprocess per dp (the device count, AMTPU_MESH topology, and
    resident knobs all latch at first backend init), plus the two-arm
    sp-crossover probe that justifies the sp fence
    (resident.SP_CROSSOVER_ELEMS)."""
    import re as _re
    import subprocess

    def spawn(extra_args, n_devices, extra_env):
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        flags = _re.sub(r'--xla_force_host_platform_device_count=\d+',
                        '', env.get('XLA_FLAGS', ''))
        env['XLA_FLAGS'] = (flags + ' --xla_force_host_platform_'
                            'device_count=%d' % n_devices).strip()
        env.update(extra_env)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + extra_args,
            env=env, capture_output=True, text=True)
        sys.stderr.write(proc.stderr)
        line = (proc.stdout.strip().splitlines() or ['{}'])[-1]
        try:
            rec = json.loads(line)
        except ValueError:
            rec = {'error': 'rc=%d no-json' % proc.returncode}
        if proc.returncode != 0:
            rec.setdefault('error', 'rc=%d' % proc.returncode)
        return rec

    lines = []
    env_dp = os.environ.get('AMTPU_MULTICHIP_DP')
    dps = [int(d) for d in (env_dp or '1,2,4,8').split(',')]
    if env_dp is None:
        # the dp axis parallelizes HOST work on this CPU stand-in, so
        # chips past the physical-core ceiling only add thread
        # contention and per-chip fixed cost (measured: dp=8 on 2 cores
        # regresses below dp=4); the default ladder stops where the
        # host can still show real scaling.  Real multi-chip hardware
        # runs the full ladder (AMTPU_MULTICHIP_DP=1,2,4,8).
        cap = max(4, 2 * (os.cpu_count() or 1))
        dropped = [d for d in dps if d > cap]
        if dropped:
            print('multichip: dp %s dropped (past the %d-core host\'s '
                  'x%d parallelism ceiling; set AMTPU_MULTICHIP_DP to '
                  'force)' % (dropped, os.cpu_count() or 1, cap),
                  file=sys.stderr)
        dps = [d for d in dps if d <= cap]
    # round 0: one FULL child per dp (phase pass, telemetry-
    # rich line); rounds 1..R-1: LIGHT children interleaved across the
    # ladder so minute-scale host drift hits every dp equally.  The
    # line's headline value is the best round (noise on a shared box
    # only ever adds time; every round is kept in `round_values`).
    rounds = env_int('AMTPU_MULTICHIP_ROUNDS', 3)
    by_dp = {}
    for dp in dps:
        print('== multichip dp=%d ==' % dp, file=sys.stderr)
        rec = spawn(['--multichip-child', str(dp)], dp,
                    {'AMTPU_MESH': str(dp)})
        rec['round_values'] = [rec.get('value', 0.0)]
        by_dp[dp] = rec
        lines.append(rec)
    for r in range(1, rounds):
        for dp in dps:
            print('== multichip dp=%d (light round %d) ==' % (dp, r),
                  file=sys.stderr)
            light = spawn(['--multichip-child', str(dp)], dp,
                          {'AMTPU_MESH': str(dp), 'AMTPU_MC_LIGHT': '1'})
            if light.get('value'):
                by_dp[dp]['round_values'].append(light['value'])
    for dp, rec in by_dp.items():
        # a failed full child has no 'ops' (and no meaning to update);
        # its light rounds still print, but the error line stands
        if rec.get('round_values') and rec.get('ops'):
            best = max(rec['round_values'])
            if best > rec.get('value', 0.0):
                rec['value'] = best
                rec['step_wall_s'] = round(rec['ops'] / best, 4)
    base = next((r for r in lines if r.get('dp') == 1 and r.get('value')),
                None)
    for rec in lines:
        if base and rec.get('value'):
            rec['vs_baseline'] = round(rec['value'] / base['value'], 3)
        print(json.dumps({k: rec[k] for k in
                          ('metric', 'value', 'dp', 'vs_baseline',
                           'round_values') if k in rec}))

    # sp-crossover probe: sharded arm vs dp-only arm, 2 devices each
    print('== multichip sp probe ==', file=sys.stderr)
    sharded = spawn(['--multichip-sp-child', '16'], 2,
                    {'AMTPU_RESIDENT': '1', 'AMTPU_RESIDENT_MIN': '16',
                     'AMTPU_MESH_SP_MIN': '16'})
    fenced = spawn(['--multichip-sp-child', '1073741824'], 2,
                   {'AMTPU_RESIDENT': '1', 'AMTPU_RESIDENT_MIN': '16',
                    'AMTPU_MESH_SP_MIN': '1073741824'})
    from automerge_tpu.native.resident import SP_CROSSOVER_ELEMS
    rows = []
    crossover = None
    for elems in sorted(int(k) for k in (sharded.get('rows') or {})):
        a = (fenced.get('rows') or {}).get(str(elems)) or \
            (fenced.get('rows') or {}).get(elems)
        b = sharded['rows'].get(str(elems)) or sharded['rows'].get(elems)
        if not a or not b:
            continue
        rows.append({'elems': elems, 'dp_only_s': a, 'sp_s': b,
                     'sp_speedup': round(a / b, 3)})
        if crossover is None and a >= b:
            crossover = elems
    sp_line = {
        'metric': 'multichip_sp_crossover',
        'rows': rows,
        'crossover_elems': crossover,
        'fence_default_elems': SP_CROSSOVER_ELEMS,
        'policy': 'sp>1 engages only past AMTPU_MESH_SP_MIN (default '
                  'fence_default_elems) or AMTPU_MESH=1,sp opt-in; '
                  'below it the dp-only kernel serves (mesh.sp_fenced)',
        'sp_probe_engaged': sharded.get('sp_engaged', 0),
    }
    if 'error' in sharded or 'error' in fenced:
        sp_line['error'] = sharded.get('error') or fenced.get('error')
    lines.append(sp_line)
    print(json.dumps(sp_line))

    if args.out:
        with open(args.out, 'w') as f:
            for rec in lines:
                f.write(json.dumps(rec) + '\n')
        print('wrote %d lines -> %s' % (len(lines), args.out),
              file=sys.stderr)
    bad = [r for r in lines if 'error' in r]
    return 1 if bad else 0


BUILDERS = {1: build_config_1, 2: build_config_2, 3: build_config_3,
            4: build_config_4}


def _rss_mb():
    """Current (not peak) resident set in MB via /proc -- the churn
    arm's flatness signal; ru_maxrss only ratchets."""
    try:
        with open('/proc/self/statm') as f:
            pages = int(f.read().split()[1])
        return pages * (os.sysconf('SC_PAGE_SIZE') / 1e6)
    except Exception:
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def run_coldstart(args):
    """--coldstart (ISSUE 14 + 17): the scale bench behind the CI
    miniature -- a timed cold restart of ``AMTPU_BENCH_COLDSTART_DOCS``
    (default 100k; 1M is the headline shape) saved docs through the
    native arena-direct decode (`amtpu_begin_columnar`), recording wall
    time, changes/s, and the process peak RSS (the "working-set >> RAM"
    soak), plus the Python-codec dict-replay arm on a subset for the
    A/B ratio and a sampled per-doc byte-parity check between the arms.
    ISSUE 17 adds (a) the parallel arena-direct `restore_from_store`
    arm from a real ColdStore -- serial (AMTPU_RESTORE_THREADS=1) vs
    auto fan-out across shard pools -- emitting `docs_per_gb` and
    `restore_s_per_doc` as first-class metrics, and (b) a steady-state
    churn arm where GC + op-state folding + clock folding must hold
    RSS FLAT, with byte-identical patches vs an unfolded
    (AMTPU_STORAGE_FOLD_CLOCKS=0) oracle twin.  Emits one
    BENCH_COLDSTART JSON line (--out writes it)."""
    import resource
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'tools'))
    import coldstart_check as cc
    from automerge_tpu import telemetry
    from automerge_tpu.native import NativeDocPool
    n_docs = env_int('AMTPU_BENCH_COLDSTART_DOCS', 100000)
    py_docs = min(n_docs, env_int('AMTPU_BENCH_COLDSTART_PYDOCS', 4096))
    step = env_int('AMTPU_BENCH_COLDSTART_BATCH', 8192)
    rng = random.Random(SEED)
    t0 = time.perf_counter()
    blobs, builder = cc._build_blobs(n_docs, rng)
    build_s = time.perf_counter() - t0
    n_changes = 17 * n_docs          # 1 init + 16 rounds per doc
    blob_bytes = sum(len(b) for b in blobs.values())
    # parity sample captured BEFORE the builder pool frees: the restore
    # must reproduce these bytes exactly
    sample_docs = sorted(blobs)[::max(1, n_docs // 64)]
    sample_saves = {d: builder.save(d) for d in sample_docs}
    del builder
    print('coldstart: built %d docs (%d changes, %.1f MB cold bytes) '
          'in %.1fs' % (n_docs, n_changes, blob_bytes / 1e6, build_s),
          file=sys.stderr)

    # Python-codec arm on a subset (the full corpus would take minutes
    # at the Python codec's changes/s -- which is the point)
    os.environ['AMTPU_STORAGE_NATIVE'] = '0'
    sub = {d: blobs[d] for d in list(blobs)[:py_docs]}
    p = NativeDocPool()
    t0 = time.perf_counter()
    p.load_batch(sub)
    py_s = time.perf_counter() - t0
    py_rate = (17 * py_docs) / py_s
    del p, sub
    print('coldstart: python arm %d docs in %.1fs (%.0f changes/s)'
          % (py_docs, py_s, py_rate), file=sys.stderr)

    # the timed native cold restart (chunked payloads bound memory)
    os.environ['AMTPU_STORAGE_NATIVE'] = '1'
    pool = NativeDocPool()
    docs = list(blobs)
    t0 = time.perf_counter()
    for i in range(0, len(docs), step):
        pool.load_batch({d: blobs[d] for d in docs[i:i + step]})
    native_s = time.perf_counter() - t0
    native_rate = n_changes / native_s
    peak_rss_mb = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 1024.0
    parity = all(pool.save(d) == sample_saves[d] for d in sample_docs)
    os.environ.pop('AMTPU_STORAGE_NATIVE', None)
    speedup = native_rate / py_rate
    print('coldstart: native restart %d docs in %.1fs (%.0f changes/s, '
          '%.1fx the python arm), peak RSS %.0f MB, parity %s'
          % (n_docs, native_s, native_rate, speedup, peak_rss_mb,
             parity), file=sys.stderr)
    del pool

    # -- ISSUE 17 (a): parallel arena-direct restore from a real cold
    # store: serial (threads=1) vs auto fan-out over shard pools
    import tempfile

    from automerge_tpu.native import ShardedNativePool, _restore_threads
    from automerge_tpu.storage.coldstore import ColdStore
    store = ColdStore(root=tempfile.mkdtemp(prefix='amtpu-coldstart-'))
    for d in docs:
        store.put(d, bytes(blobs[d]))
    shards = env_int('AMTPU_BENCH_COLDSTART_SHARDS', 4)
    serial_pool = ShardedNativePool(shards)
    t0 = time.perf_counter()
    serial_pool.restore_from_store(store, threads=1)
    serial_s = time.perf_counter() - t0
    serial_rate = n_changes / serial_s
    del serial_pool
    pool = ShardedNativePool(shards)
    t0 = time.perf_counter()
    rsum = pool.restore_from_store(store)
    par_s = time.perf_counter() - t0
    par_rate = n_changes / par_s
    par_speedup = par_rate / serial_rate
    resident_mb = _rss_mb()
    par_parity = all(pool.save(d) == sample_saves[d]
                     for d in sample_docs)
    cores = os.cpu_count() or 1
    restore_s_per_doc = par_s / n_docs
    docs_per_gb = n_docs / max(resident_mb / 1024.0, 1e-9)
    print('coldstart: store restore %d docs serial %.1fs parallel '
          '%.1fs (%.2fx, %d threads on %d cores), %.2fus/doc, '
          '%.0f docs/GB resident, parity %s'
          % (n_docs, serial_s, par_s, par_speedup,
             _restore_threads(), cores, restore_s_per_doc * 1e6,
             docs_per_gb, par_parity), file=sys.stderr)

    # -- ISSUE 17 (b): steady-state churn -- GC + op folding + clock
    # folding must hold RSS flat; patches must match an unfolded twin
    churn_rounds = env_int('AMTPU_BENCH_COLDSTART_CHURN_ROUNDS', 12)
    churn_docs = min(n_docs, env_int('AMTPU_BENCH_COLDSTART_CHURN_DOCS',
                                     2048))
    churn = None
    if churn_rounds > 0:
        cd = docs[:churn_docs]
        twin_docs = cd[::max(1, churn_docs // 128)]
        os.environ['AMTPU_STORAGE_FOLD_CLOCKS'] = '0'
        twin = NativeDocPool()
        twin.load_batch({d: blobs[d] for d in twin_docs})
        os.environ.pop('AMTPU_STORAGE_FOLD_CLOCKS', None)
        seqs, rss_series = {}, []
        muts = 6
        for r in range(churn_rounds):
            payload = {}
            for d in cd:
                seq0 = seqs.get(d, 0)
                payload[d] = [
                    {'actor': 'churn', 'seq': seq0 + i + 1,
                     'deps': {'churn': seq0 + i} if seq0 + i else {},
                     'ops': [{'action': 'set', 'obj': cc.ROOT_ID,
                              'key': 'k%d' % (i % 8),
                              'value': r * 100 + i}]}
                    for i in range(muts)]
                seqs[d] = seq0 + muts
            pool.apply_batch(payload)
            for d in cd:
                pool.compact(d)
            os.environ['AMTPU_STORAGE_FOLD_CLOCKS'] = '0'
            twin.apply_batch({d: payload[d] for d in twin_docs})
            for d in twin_docs:
                twin.compact(d)
            os.environ.pop('AMTPU_STORAGE_FOLD_CLOCKS', None)
            rss_series.append(round(_rss_mb(), 1))
        warm = max(1, churn_rounds // 3)
        early = max(rss_series[warm:2 * warm] or rss_series[:1])
        late = max(rss_series[-warm:])
        rss_flat = late <= early * 1.05 + 16
        fold_parity = all(
            pool.get_patch(d) == twin.get_patch(d)
            and pool.save(d) == twin.save(d) for d in twin_docs)
        clock_pairs = pool.clock_pairs()
        churn = {
            'docs': churn_docs, 'rounds': churn_rounds,
            'changes': churn_rounds * churn_docs * muts,
            'rss_mb_series': rss_series, 'rss_flat': rss_flat,
            'fold_parity_vs_unfolded': fold_parity,
            'clock_pairs_after': clock_pairs,
        }
        del twin
        print('coldstart: churn %d docs x %d rounds, RSS %s -> %s MB '
              '(flat %s), fold parity %s, %d sparse clock pairs left'
              % (churn_docs, churn_rounds, rss_series[0],
                 rss_series[-1], rss_flat, fold_parity, clock_pairs),
              file=sys.stderr)
    peak_rss_mb = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 1024.0
    result = {
        'metric': 'coldstart_restore',
        'value': round(native_rate, 1),
        'unit': 'changes/sec',
        'docs': n_docs,
        'changes': n_changes,
        'cold_bytes': blob_bytes,
        'build_s': round(build_s, 2),
        'native_restore_s': round(native_s, 3),
        'python_arm': {'docs': py_docs, 'restore_s': round(py_s, 3),
                       'changes_per_s': round(py_rate, 1)},
        'vs_baseline': round(speedup, 2),
        'baseline': 'python-codec-dict-replay',
        'peak_rss_mb': round(peak_rss_mb, 1),
        'parity': parity,
        # ISSUE 17 first-class economics metrics (bench_compare pairs
        # these across BENCH_COLDSTART_*.json like ops/s)
        'docs_per_gb': round(docs_per_gb, 1),
        'restore_s_per_doc': round(restore_s_per_doc, 8),
        'resident_rss_mb': round(resident_mb, 1),
        'restore_parallel': {
            'shards': shards, 'threads': _restore_threads(),
            'cores': cores,
            'serial_s': round(serial_s, 3),
            'parallel_s': round(par_s, 3),
            'serial_changes_per_s': round(serial_rate, 1),
            'parallel_changes_per_s': round(par_rate, 1),
            'speedup': round(par_speedup, 2),
            'parity': par_parity,
            'summary': {k: (len(v) if isinstance(v, dict) else v)
                        for k, v in rsum.items()},
        },
        'churn': churn,
        'telemetry': telemetry.bench_block(),
    }
    print(json.dumps(result))
    if args.out:
        with open(args.out, 'w') as f:
            f.write(json.dumps(result) + '\n')
        print('wrote %s' % args.out, file=sys.stderr)
    ok = parity and par_parity and speedup >= 4.0
    if churn is not None:
        ok = ok and churn['rss_flat'] and churn['fold_parity_vs_unfolded']
    # the >=2x parallel gate only binds on multi-core hosts (1-core
    # ceiling is 1x by construction; coldstart-check skips loudly too)
    if cores >= 2:
        ok = ok and par_speedup >= 2.0
    return 0 if ok else 1


def run_fanout(args):
    """--fanout (ISSUE 9): the real collaboration workload -- RGA-heavy
    text edits under zipfian doc popularity fanned out to 1k+
    subscribed peers through a live in-process gateway -- plus the
    vectorized-vs-scalar missing-changes classification A/B in the
    same session.  Emits one BENCH_FANOUT JSON line with p50/p99
    change->fanout latency, fan-out amplification (bytes-on-wire /
    bytes-encoded), both A/B throughputs, and the embedded telemetry
    block."""
    import tempfile
    import threading

    import numpy as np

    from automerge_tpu import telemetry
    from automerge_tpu.parallel.mesh_encode import text_doc_changes
    from automerge_tpu.scheduler import GatewayServer
    from automerge_tpu.sidecar.client import SidecarClient
    from automerge_tpu.sidecar.server import SidecarBackend
    from automerge_tpu.sync.fanout import classify_scalar, classify_vector

    n_peers = env_int('AMTPU_BENCH_FANOUT_PEERS', 1024)
    n_docs = env_int('AMTPU_BENCH_FANOUT_DOCS', 24)
    n_conns = env_int('AMTPU_BENCH_FANOUT_CONNS', 16)
    n_rounds = env_int('AMTPU_BENCH_FANOUT_ROUNDS', 96)
    zipf_s = float(os.environ.get('AMTPU_BENCH_FANOUT_ZIPF', '1.2'))
    rng = random.Random(SEED)

    # zipfian doc popularity: weight 1/k^s for doc rank k
    weights = [1.0 / (k + 1) ** zipf_s for k in range(n_docs)]
    doc_of_peer = rng.choices(range(n_docs), weights=weights, k=n_peers)
    write_docs = rng.choices(range(n_docs), weights=weights, k=n_rounds)
    subs_per_doc = [doc_of_peer.count(d) for d in range(n_docs)]

    # RGA-heavy edit streams: one change per write round per doc
    per_doc_changes = {}
    for d in range(n_docs):
        need = write_docs.count(d)
        rounds = max(1, (need + 1) // 2)
        per_doc_changes[d] = text_doc_changes(
            'text-%d' % d, 2, rounds, 40,
            lambda i, a, has: rng.random() < 0.15 and has)

    path = os.path.join(tempfile.mkdtemp(), 'bench-fanout.sock')
    telemetry.reset_all()
    gw = GatewayServer(path, backend=SidecarBackend()).start()
    drainers, counts, stop = [], [0] * n_conns, threading.Event()
    try:
        conns = [SidecarClient(sock_path=path) for _ in range(n_conns)]
        for i, doc in enumerate(doc_of_peer):
            conns[i % n_conns].subscribe('doc-%d' % doc,
                                         peer='p%04d' % i)

        def drain(ci):
            while not stop.is_set():
                try:
                    e = conns[ci].next_event(timeout=0.2)
                except ConnectionError:
                    return
                if e is not None and e.get('event') == 'change':
                    counts[ci] += 1

        drainers = [threading.Thread(target=drain, args=(ci,),
                                     daemon=True)
                    for ci in range(n_conns)]
        for t in drainers:
            t.start()

        writer = SidecarClient(sock_path=path)
        cursor = {d: 0 for d in range(n_docs)}
        expected = 0
        t0 = time.perf_counter()
        for d in write_docs:
            chs = per_doc_changes[d]
            if cursor[d] < len(chs):
                writer.apply_changes('doc-%d' % d, [chs[cursor[d]]])
                cursor[d] += 1
                expected += subs_per_doc[d]
        # frames lag the final response by at most one flush window;
        # wait for the server-side frame counter to reach/settle
        deadline = time.time() + 60
        while time.time() < deadline:
            got = telemetry.metrics_snapshot() \
                .get('sync.fanout.frames', 0)
            if got >= expected:
                break
            time.sleep(0.1)
        wall = time.perf_counter() - t0
        stop.set()
        for t in drainers:
            t.join(timeout=10)
        for c in conns + [writer]:
            c.close()
    finally:
        stop.set()
        gw.stop()

    snap = telemetry.metrics_snapshot()
    lat = telemetry.FANOUT_LATENCY.summary() or {}
    enc = snap.get('sync.fanout.bytes_encoded', 0.0)
    wire = snap.get('sync.fanout.bytes_on_wire', 0.0)

    # -- the vectorized-vs-scalar classification A/B (same session) ------
    npr = np.random.RandomState(SEED)
    A = 64
    post = npr.randint(1, 50, size=(n_peers, A)).astype(np.int64)
    pre = np.maximum(post - npr.randint(0, 3, size=(n_peers, A)), 0)
    bel = np.where(npr.random_sample((n_peers, A)) < 0.9, pre,
                   np.maximum(pre - 1, 0))

    def rate(fn, min_s=0.8):
        fn(bel, pre, post)                       # warm
        n, t = 0, time.perf_counter()
        while time.perf_counter() - t < min_s:
            fn(bel, pre, post)
            n += 1
        return n_peers * n / (time.perf_counter() - t)

    vec_rate = rate(classify_vector)
    scal_rate = rate(classify_scalar)
    speedup = vec_rate / scal_rate if scal_rate else float('inf')

    line = {
        'bench': 'fanout',
        'peers': n_peers, 'docs': n_docs, 'conns': n_conns,
        'write_rounds': n_rounds, 'zipf_s': zipf_s,
        'hot_doc_subscribers': max(subs_per_doc),
        'frames': int(snap.get('sync.fanout.frames', 0)),
        'frames_drained': sum(counts),
        'encode_reuse': int(snap.get('sync.fanout.encode_reuse', 0)),
        'coalesced_peers': int(snap.get('sync.fanout.coalesced_peers',
                                        0)),
        'straggler_peers': int(snap.get('sync.fanout.straggler_peers',
                                        0)),
        'p50_ms': lat.get('p50'), 'p95_ms': lat.get('p95'),
        'p99_ms': lat.get('p99'),
        'amplification': round(wire / enc, 2) if enc else None,
        'write_wall_s': round(wall, 3),
        'classify_ab': {
            'matrix_peers': n_peers, 'actors': A,
            'vector_peers_per_s': round(vec_rate),
            'scalar_peers_per_s': round(scal_rate),
            'speedup': round(speedup, 1),
        },
        'fallback_oracle': snap.get('fallback.oracle', 0),
        'telemetry': telemetry.bench_block(),
    }
    out = json.dumps(line)
    print(out)
    if args.out:
        with open(args.out, 'w') as f:
            f.write(out + '\n')
        print('wrote BENCH_FANOUT line -> %s' % args.out,
              file=sys.stderr)
    print('fanout bench: %d peers, hot doc %d subs, p50 %.1fms p99 '
          '%.1fms, amplification %.1fx, classify A/B %.0fk vs %.0fk '
          'peers/s (%.1fx)'
          % (n_peers, max(subs_per_doc), lat.get('p50', -1),
             lat.get('p99', -1), line['amplification'] or 0,
             vec_rate / 1e3, scal_rate / 1e3, speedup),
          file=sys.stderr)
    # the acceptance floor: the vectorized pass must beat the per-peer
    # scalar loop by >= 5x on the 1k-peer shape
    return 0 if speedup >= 5.0 and line['frames'] > 0 else 1


def run_all(args):
    """--all: every config in every execution mode, one JSON-lines
    artifact (VERDICT r4 #5: a committed all-config file per round).

    Each line runs in a FRESH subprocess: the latched native knobs
    (AMTPU_RESIDENT*) only bind at a process's first batch, jit caches
    don't leak across configs, and one config's memory high-water can't
    pollute the next config's timings on this single-core host.

    Per config: one `--mode auto` line (which itself embeds the
    opposite-mode sibling block), plus a `--mode resident` line for the
    long-list shapes (configs 1 and 3) -- the device-resident arena
    path the multichip dryrun shards."""
    import subprocess
    lines = []
    runs = [(c, 'auto') for c in (1, 2, 3, 4, 5)]
    runs += [(1, 'resident'), (3, 'resident')]
    for config, bmode in runs:
        cmd = [sys.executable, os.path.abspath(__file__),
               '--config', str(config), '--mode', bmode]
        print('== bench --config %d --mode %s ==' % (config, bmode),
              file=sys.stderr)
        proc = subprocess.run(cmd, capture_output=True, text=True)
        sys.stderr.write(proc.stderr)
        line = (proc.stdout.strip().splitlines() or [''])[-1]
        try:
            rec = json.loads(line)
        except ValueError:
            rec = {'metric': 'config_%d' % config, 'value': 0.0,
                   'unit': 'ops/sec', 'vs_baseline': 0.0,
                   'baseline': BASELINE_NAME, 'mode': bmode,
                   'error': 'rc=%d no-json' % proc.returncode}
        # the subprocess rc carries failures the top-level fields don't:
        # a sibling-mode parity regression zeroes only the *_path block
        # (main()'s sibling_bad check fails the rc) -- bench-all must be
        # exactly as loud
        if proc.returncode != 0:
            rec.setdefault('error', 'rc=%d' % proc.returncode)
        rec['config'] = config
        lines.append(rec)
        print(json.dumps(rec))
    if args.out:
        with open(args.out, 'w') as f:
            for rec in lines:
                f.write(json.dumps(rec) + '\n')
        print('wrote %d lines -> %s' % (len(lines), args.out),
              file=sys.stderr)
    bad = [r for r in lines if not r.get('vs_baseline') or 'error' in r]
    return 1 if bad else 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    # internal child entries (spawned by run_multichip with the device
    # count / AMTPU_MESH / resident knobs already in the env)
    if argv[:1] == ['--multichip-child']:
        return run_multichip_child(int(argv[1]))
    if argv[:1] == ['--multichip-sp-child']:
        return run_multichip_sp_child(int(argv[1]))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--config', type=int,
                    default=env_int('AMTPU_BENCH_CONFIG', 3),
                    choices=[1, 2, 3, 4, 5])
    ap.add_argument('--mode', default='auto',
                    choices=['auto', 'host', 'kernel', 'resident'],
                    help='execution mode: auto = platform default '
                         'headline + opposite-mode sibling block; '
                         'host/kernel/resident pin one mode (resident '
                         'requires a fresh process -- the knob latches '
                         'at the first native batch)')
    ap.add_argument('--all', action='store_true',
                    help='run every config in every mode (fresh '
                         'subprocess each) and write a JSON-lines '
                         'artifact (--out)')
    ap.add_argument('--multichip', action='store_true',
                    help='MULTICHIP artifact through the first-class '
                         'mesh pool mode: one subprocess per dp '
                         '(AMTPU_MULTICHIP_DP, default 1,2,4,8) + the '
                         'sp-crossover probe; write with --out')
    ap.add_argument('--coldstart', action='store_true',
                    help='BENCH_COLDSTART artifact (ISSUE 14): timed '
                         '100k-doc cold restart + peak-RSS soak '
                         'through the native arena-direct decode, '
                         'with the Python-codec arm on a subset; '
                         'write with --out')
    ap.add_argument('--fanout', action='store_true',
                    help='BENCH_FANOUT artifact (ISSUE 9): RGA-heavy '
                         'text edits under zipfian doc popularity '
                         'fanned to 1k+ subscribed peers through a '
                         'live gateway + the vectorized-vs-scalar '
                         'missing-changes A/B; write with --out')
    ap.add_argument('--out', default='',
                    help='with --all/--multichip: artifact path '
                         '(JSON lines)')
    args = ap.parse_args(argv)
    # argparse skips the choices check for non-string DEFAULTS, so an
    # env-supplied AMTPU_BENCH_CONFIG needs explicit validation
    if args.config not in (1, 2, 3, 4, 5):
        ap.error('invalid config %r (AMTPU_BENCH_CONFIG must be 1..5)'
                 % (args.config,))
    if args.all:
        return run_all(args)
    if args.multichip:
        return run_multichip(args)
    if args.coldstart:
        return run_coldstart(args)
    if args.fanout:
        return run_fanout(args)
    if args.mode == 'host':
        os.environ['AMTPU_HOST_FULL'] = '1'
    elif args.mode == 'kernel':
        os.environ['AMTPU_HOST_FULL'] = '0'
    elif args.mode == 'resident':
        # only meaningful in a fresh process: the native lib latches
        # AMTPU_RESIDENT in its static init at the first batch
        os.environ['AMTPU_RESIDENT'] = '1'
        # bind residency for the config-1 arena (10k elements) too, not
        # just arenas past the default 16384 threshold
        os.environ.setdefault('AMTPU_RESIDENT_MIN', '4096')
    device = device_info()
    print('device: %s' % json.dumps(device), file=sys.stderr)
    rng = random.Random(SEED)
    both = args.mode == 'auto'
    if args.config == 5:
        result = run_config_5(rng, both_modes=both)
    elif args.config == 1 and env_int('AMTPU_BENCH_C1_MESH', 0):
        result = run_config_1_mesh(rng)
    else:
        result = run_batch_config(BUILDERS[args.config], rng, both_modes=both)
    # every BENCH line embeds a telemetry block (fallback rates, device
    # seconds, batch-latency histograms) so an artifact is
    # self-describing about HOW its number was produced.  Configs 1-4
    # already carry a per-mode block scoped to their timed runs
    # (_measure_mode); this setdefault covers the remaining paths
    # (config 5, mesh) with the process-wide view
    from automerge_tpu import telemetry
    result.setdefault('telemetry', telemetry.bench_block())
    result['backend'] = device
    print(json.dumps(result))
    # a parity failure in EITHER mode fails the run: the sibling-mode
    # block exists precisely so a kernel-path regression is loud even
    # where the host path is the platform default
    sibling_bad = any(
        isinstance(v, dict) and v.get('parity') is False
        for k, v in result.items() if k.endswith('_path'))
    return 0 if result.get('vs_baseline') and not sibling_bad else 1


if __name__ == '__main__':
    sys.exit(main())
