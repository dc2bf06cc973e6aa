# Top-level developer entry points.

.PHONY: all native test bench bench-all bench-tpu bench-multichip check \
	clean wheel telemetry-check fallback-check perf-smoke chaos-check \
	serve-check mesh-check static-check asan-check fanout-check \
	bench-fanout storage-check obs-check backpressure-check \
	coldstart-check bench-coldstart capacity-check route-check \
	failover-check readpath-check

all: native

native:
	$(MAKE) -C native

test: native
	python -m pytest tests/ -q

bench: native
	python bench.py

# One committed all-config artifact per round (VERDICT r4 #5): every
# config, every execution mode, fresh subprocess each, JSON lines.
bench-all: native
	python bench.py --all --out BENCH_ALL.json

# All five configs on the accelerator: platform default (= kernel on
# TPU) + host sibling embedded per line, plus the resident-arena lines
# for the long-list shapes.  bench.py refuses to run without an
# accelerator.  The parent stays off JAX; each config runs in a child
# that holds the chip.
bench-tpu: native
	python bench.py --all --out BENCH_TPU.json

# The pre-commit gate: native build + full test suite + a bench smoke
# covering BOTH execution modes (the default line embeds the
# opposite-mode sibling block; rc fails on either mode's parity or a
# missing kernel measurement) + the driver's multi-chip dryrun, all
# on the CPU (JAX_PLATFORMS=cpu).  Run before EVERY
# snapshot commit; nothing ships unless this is green (the reference's
# analogue: `npm test`, /root/reference/package.json:7).
check: native
	JAX_PLATFORMS=cpu python -m pytest tests/ -q
	JAX_PLATFORMS=cpu AMTPU_BENCH_DOCS=192 AMTPU_BENCH_ORACLE_DOCS=24 \
	  python bench.py --config 3 > .bench_smoke.json
	python -c "import json; \
	  r = json.load(open('.bench_smoke.json')); \
	  k = r.get('kernel_path') or r.get('host_full_path'); \
	  assert k and k.get('value'), 'no sibling-mode measurement'; \
	  assert r['baseline'] == 'python-scalar-oracle', r.get('baseline'); \
	  print('bench smoke: %s %.0f ops/s + sibling %.0f ops/s' \
	        % (r['mode'], r['value'], k['value']))"
	JAX_PLATFORMS=cpu python -c "import __graft_entry__ as g; \
	  g.dryrun_multichip(8); print('dryrun ok')"
	@# soft bench trajectory: diff this smoke against the previous
	@# GREEN check's (report-only -- the hard perf gates stay below);
	@# the baseline rolls forward only after every gate passes
	-@[ -f .bench_smoke.prev.json ] && \
	  python tools/bench_compare.py --soft .bench_smoke.prev.json \
	    .bench_smoke.json || true
	$(MAKE) static-check
	$(MAKE) fallback-check
	$(MAKE) perf-smoke
	$(MAKE) chaos-check
	$(MAKE) serve-check
	$(MAKE) fanout-check
	$(MAKE) readpath-check
	$(MAKE) backpressure-check
	$(MAKE) storage-check
	$(MAKE) coldstart-check
	$(MAKE) capacity-check
	$(MAKE) obs-check
	$(MAKE) route-check
	$(MAKE) failover-check
	$(MAKE) mesh-check
	$(MAKE) asan-check
	@cp .bench_smoke.json .bench_smoke.prev.json
	@echo "CHECK GREEN"

# Escalation-ladder gate (ISSUE 2): a config-4-shaped smoke on the
# FORCED kernel path must report fallback.oracle == 0 with the per-tier
# escalation counters present in the BENCH telemetry block -- the table
# workload may never fall back to host-oracle register resolution again.
fallback-check: native
	JAX_PLATFORMS=cpu python tools/fallback_check.py

# Packed-epilogue gate (ISSUE 3): the same config-4 smoke must be served
# by the packed member epilogue (collect.packed_member_batches > 0) with
# ZERO full-matrix readbacks and fallback.oracle == 0 -- the collect
# transfer wall may not silently return.
perf-smoke: native
	JAX_PLATFORMS=cpu python tools/perf_smoke.py

# Resilience gate (ISSUE 4, docs/RESILIENCE.md): injected faults must
# actually be isolated -- two forced transient device faults retry to a
# byte-identical config-3 result, a doc-pinned permanent fault
# quarantines exactly that doc with healthy-doc parity intact, and a
# SIGKILLed sidecar server respawns + replays its checkpoint WAL with a
# clean process tree afterwards.
chaos-check: native
	JAX_PLATFORMS=cpu python tools/chaos_check.py

# Serve-gateway gate (ISSUE 5, docs/SERVING.md): 32 concurrent
# connections of mixed-doc traffic must coalesce (median batch
# occupancy > 4 docs/flush) with every patch byte-identical to serial
# application; with the queue capped low, overloaded requests must get
# the typed Overloaded envelope and the server must stay healthy after
# the burst; no oracle fallback, no leaked batch handles at drain.
serve-check: native
	JAX_PLATFORMS=cpu python tools/serve_check.py

# Batched-sync-fan-out gate (ISSUE 9, docs/SERVING.md fan-out section):
# 1 popular doc x 200 subscribers must show encode_reuse >= 199 (the
# coalesced delta encodes once), every subscriber's received-change
# stream byte-identical to a serial per-Connection replay (incl. a
# mid-run straggler at a stale clock), change->fanout p99 under the
# smoke gate, and fallback.oracle == 0.
fanout-check: native
	JAX_PLATFORMS=cpu python tools/fanout_check.py

# Read-path gate (ISSUE 20, docs/SERVING.md read path): patch-mode
# fan-out must beat change shipping on thin-client apply CPU with both
# end states byte-identical to the get_patch oracle, a ReadReplica
# must stay inside its staleness SLO under writer churn and close a
# forced gap via resync, a snapshot cold-open must be byte-identical
# to a full history replay (repeat fetch cache-hit), and
# fallback.oracle == 0.  Writes BENCH_READPATH_r20.json.
readpath-check: native
	JAX_PLATFORMS=cpu python tools/readpath_check.py

# Backpressure gate (ISSUE 13, docs/SERVING.md backpressure section):
# one deliberately wedged consumer while 32 healthy connections stream
# -- every healthy peer still receives every change, healthy p99 stays
# within 2x the no-wedge baseline (floored for CI jitter), the wedged
# peer is resynced with a typed envelope or evicted, its
# post-reconnect backfill is byte-identical to a serial replay, and
# fallback.oracle == 0.
backpressure-check: native
	JAX_PLATFORMS=cpu python tools/backpressure_check.py

# The BENCH_FANOUT artifact (ISSUE 9): RGA-heavy text edits under
# zipfian doc popularity fanned to 1k+ subscribed peers, with the
# vectorized-vs-scalar missing-changes A/B in the same session.
bench-fanout: native
	JAX_PLATFORMS=cpu python bench.py --fanout --out BENCH_FANOUT.json

# Cold-state gate (ISSUE 10, docs/STORAGE.md): the config-4 change
# corpus must columnar-encode >= 5x smaller than its JSON bytes, a
# rolling churn workload with settled-history GC must end with a
# strictly smaller retained arena than the no-GC arm (byte-identical
# patches), save -> evict -> reload -> mutate must equal a never-
# evicted twin, and fallback.oracle must stay 0 throughout.  Writes
# the BENCH_STORAGE artifact.
storage-check: native
	JAX_PLATFORMS=cpu python tools/storage_check.py

# Cold-start gate (ISSUE 14, docs/STORAGE.md): the native columnar
# codec must decode >= 10x the Python codec's changes/s (scaled text
# corpus AND the config-4 acceptance corpus), the end-to-end 2k-doc
# restore through the arena-direct load must beat the dict-replay arm
# >= 4x with per-doc byte parity vs the never-evicted twin, a durable-
# mode kill-mid-save must recover via the manifest, and
# fallback.oracle == 0 throughout.
coldstart-check: native
	JAX_PLATFORMS=cpu python tools/coldstart_check.py

# The BENCH_COLDSTART artifact (ISSUE 14): timed 100k-doc cold restart
# + peak-RSS soak through the native arena-direct decode, with the
# Python-codec arm measured on a subset for the A/B ratio.
bench-coldstart: native
	JAX_PLATFORMS=cpu python bench.py --coldstart --out BENCH_COLDSTART.json

# Capacity gate (ISSUE 15, docs/OBSERVABILITY.md capacity section):
# per-doc accounting must reconcile BIT-EXACTLY with the pool-wide
# counters under churn + GC + fold + evict + reload in both exec modes
# and on a dp=4 mesh pool, the hot-doc sketch must rank a zipfian
# stream correctly, and memory-pressure eviction must fire BEFORE the
# modeled AMTPU_MEM_BUDGET_MB is breached.  The always-on accounting
# cost is priced by telemetry-check (raw arm no-ops capacity.note_*).
capacity-check: native
	JAX_PLATFORMS=cpu python tools/capacity_check.py

# Observability gate (ISSUE 12, docs/OBSERVABILITY.md): flight
# recorder + critical-path attribution + SLO surface against a LIVE
# gateway -- per-stage attribution must sum to the request wall, a
# slow request must land an exemplar span tree in the trace file, a
# fault-triggered quarantine must dump a recorder file containing the
# injected event, the on-demand `dump` request must round-trip a file,
# and amtpu_top must render from the live /metrics + /healthz.
obs-check: native
	JAX_PLATFORMS=cpu python tools/obs_check.py

# Telemetry idle-cost gate (docs/OBSERVABILITY.md): idle telemetry must
# be free.  Interleaved A/B of the disabled path vs a no-op-patched "raw"
# pipeline on the quickbench workload (target ~2% overhead; the assert
# tolerance is padded for this single-core host's +-15% jitter), plus
# an enabled-path sanity pass.  On the CPU: host-phase cost is
# device-independent.
telemetry-check: native
	JAX_PLATFORMS=cpu python tools/telemetry_check.py

# Static-analysis gate (ISSUE 8, docs/ANALYSIS.md): the four
# project-specific checkers -- env-latch spec/ABI/docs lockstep,
# telemetry-key pre-seed + glossary lockstep, dispatch-alias (post-
# dispatch mutation of jax-staged host buffers), lock-discipline
# (`# guarded-by:` annotations) -- plus the generic ruff/pyflakes
# baseline when installed.  Needs the native build: the env checker
# cross-checks spec defaults against the amtpu_latch_defaults ABI.
static-check: native
	python tools/static_check.py

# Native-sanitizer gate (ISSUE 8, docs/ANALYSIS.md): core.cpp rebuilt
# with -fsanitize=address,undefined and driven by the native-heavy test
# subset (driver + atomicity + differential) through AMTPU_NATIVE_LIB
# with libasan LD_PRELOADed -- the batch-column use-after-free and OOB
# classes every hardening round re-found by hand now fail CI.
asan-check: native
	JAX_PLATFORMS=cpu python tools/asan_check.py

# Fleet-router gate (ISSUE 18, docs/SERVING.md routing section): 3
# replica server subprocesses behind the consistent-hash RouterGateway
# must serve a zipfian workload with per-doc byte parity vs ONE
# single-pool serial replay and fallback.oracle == 0 on every replica;
# a cost-driven rebalance under sustained load must commit >= 1
# migration with every (doc, seq) acked exactly once and strictly
# lower occupancy skew after; and a migration whose TARGET replica is
# SIGKILLed between migrate_out and migrate_in must recover off the
# durable handoff manifest with no lost acks.  Writes the
# BENCH_ROUTER artifact (per-replica ops/s, routed p50/p99, skew).
route-check: native
	JAX_PLATFORMS=cpu python tools/route_check.py

# Fleet-failover gate (ISSUE 19, docs/RESILIENCE.md fleet degradation
# tiers): a supervised 3-replica fleet under zipfian load must survive
# a SIGKILL of one replica mid-flush -- death detected, docs restored
# onto survivors from the write-through store, parked frames replayed,
# a new generation respawned and rejoined pinned -- with exactly-once
# acks, per-doc byte parity vs a serial replay, subscribers resynced
# gapless, rebalance draining docs back onto the rejoiner, and
# fallback.oracle == 0 throughout.  Writes the BENCH_FAILOVER artifact
# (time-to-detect / time-to-restore / time-to-rejoin, retry counts).
failover-check: native
	JAX_PLATFORMS=cpu python tools/failover_check.py

# Mesh-execution gate (ISSUE 7, docs/ARCHITECTURE.md mesh section):
# MeshDocPool under AMTPU_MESH=4 must serve a mixed real workload with
# per-doc byte parity vs a serial replay and fallback.oracle == 0, and
# dp=4 must beat dp=1 by >= 1.5x on the MULTICHIP scaling workload
# (interleaved A/B, bounded retries; the JSON records the physical-core
# ceiling this CPU stand-in can offer).
mesh-check: native
	JAX_PLATFORMS=cpu python tools/mesh_check.py

# The MULTICHIP artifact through the first-class pool mode (ISSUE 7):
# one fresh subprocess per dp in {1,2,4,8} + the sp-crossover probe,
# JSON lines with per-phase seconds and the mesh.* telemetry block.
# Replaces the dryrun tail-scrape as the source of MULTICHIP_r0N.json.
bench-multichip: native
	python bench.py --multichip --out MULTICHIP.json

wheel: native
	python -m pip wheel --no-deps -w dist .

clean:
	$(MAKE) -C native clean
