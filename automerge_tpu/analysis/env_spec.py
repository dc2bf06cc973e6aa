"""The ONE machine-readable spec of every ``AMTPU_*`` environment flag.

Each flag records its type (which `utils/common` helper reads it), its
default (cross-checked against the literal at every call site AND, for
the C++ latches, against the ``amtpu_latch_defaults`` ABI), whether it
LATCHES at the process's first batch (cross-checked against
``native._RESIDENT_LATCH_KEYS`` -- the flip-guard list), and its
consumers.  `check_env` fails `make static-check` when any of those
drifts, and when a flag here is missing from the env-variable table in
docs/OBSERVABILITY.md (or vice versa).

Registering a new flag (docs/ANALYSIS.md has the walkthrough):
  1. add an `EnvFlag` row here;
  2. read it ONLY through the `utils/common` helper matching its type;
  3. add its row to docs/OBSERVABILITY.md's env table;
  4. if it latches at first batch, add it to `_RESIDENT_LATCH_KEYS`.
`make static-check` verifies you did all four.
"""

import collections

#: type -> the utils/common helper that must read it.  `raw` flags are
#: tri-state (consumers distinguish unset from any value); `special`
#: flags have a dedicated parser (AMTPU_MESH -> parse_mesh_env).
EnvFlag = collections.namedtuple(
    'EnvFlag', ('name', 'type', 'default', 'latched', 'consumer'))

ENV_FLAGS = (
    # -- observability ------------------------------------------------------
    EnvFlag('AMTPU_TRACE', 'bool', False, False, 'telemetry/spans.py'),
    EnvFlag('AMTPU_TRACE_FILE', 'str', '', False, 'telemetry/spans.py'),
    EnvFlag('AMTPU_TRACE_FILE_MAX_MB', 'int', 256, False,
            'telemetry/spans.py (keep-1 rotation cap; <=0 disables)'),
    EnvFlag('AMTPU_TRACE_WIRE', 'bool', True, False,
            'sidecar/client.py (stamp the wire trace context on every '
            'outbound request; read once per client)'),
    EnvFlag('AMTPU_REPLICA_ID', 'str', '', False,
            'telemetry/__init__.py (fleet replica identity; empty -> '
            'hostname:pid)'),
    EnvFlag('AMTPU_RECORDER_EVENTS', 'int', 4096, False,
            'telemetry/recorder.py (ring size; read once at import)'),
    EnvFlag('AMTPU_RECORDER_DIR', 'str', '', False,
            'telemetry/recorder.py (dump dir; empty -> per-process '
            'tempdir)'),
    EnvFlag('AMTPU_RECORDER_MIN_DUMP_S', 'float', 5.0, False,
            'telemetry/recorder.py (per-reason dump rate limit)'),
    EnvFlag('AMTPU_SLOW_MS', 'float', 250.0, False,
            'telemetry/attribution.py (exemplar-trace threshold)'),
    EnvFlag('AMTPU_SLO_P99_MS', 'float', 100.0, False,
            'telemetry/attribution.py (p99 target the burn rates '
            'measure against)'),
    EnvFlag('AMTPU_EXEMPLAR_MIN_S', 'float', 0.05, False,
            'telemetry/attribution.py (min interval between exemplar '
            'emissions; bounds the tail sampler under error storms)'),
    EnvFlag('AMTPU_DEGRADED_WINDOW_S', 'float', 300.0, False,
            'telemetry/__init__.py'),
    EnvFlag('AMTPU_SIDECAR_RESTARTS', 'int', 0, False,
            'telemetry/__init__.py (exported by sidecar/client.py)'),
    EnvFlag('AMTPU_METRICS_PORT', 'int', -1, False, 'sidecar/server.py'),
    EnvFlag('AMTPU_METRICS_HOST', 'str', '127.0.0.1', False,
            'sidecar/server.py'),
    # -- per-doc capacity accounting + headroom (ISSUE 15) ------------------
    EnvFlag('AMTPU_MEM_BUDGET_MB', 'int', 0, False,
            'telemetry/capacity.py (memory budget the headroom '
            'estimator measures against; 0 = unbudgeted)'),
    EnvFlag('AMTPU_MEM_PRESSURE_EVICT', 'float', 0.85, False,
            'telemetry/capacity.py (pressure fraction past which the '
            'gateway evicts cold docs proactively; <=0 disables)'),
    EnvFlag('AMTPU_PRESSURE_EVICT_DOCS', 'int', 16, False,
            'storage/coldstore.py (max LRU docs one pressure-eviction '
            'pass checkpoints out)'),
    EnvFlag('AMTPU_PRESSURE_EVICT_COOLDOWN_S', 'float', 30.0, False,
            'telemetry/capacity.py (min seconds between pressure '
            'passes: a stuck-high RSS signal must not evict per flush)'),
    EnvFlag('AMTPU_CAPACITY_TOPK', 'int', 10, False,
            'telemetry/capacity.py (hot-doc table depth)'),
    EnvFlag('AMTPU_CAPACITY_REFRESH_S', 'float', 1.0, False,
            'telemetry/capacity.py (min seconds between native per-doc '
            'stats passes; scrapes + pressure checks share one)'),
    EnvFlag('AMTPU_CAPACITY_SKETCH', 'int', 128, False,
            'telemetry/capacity.py (space-saver sketch capacity for '
            'the streaming fanned/egress tiers)'),
    # -- kernel path --------------------------------------------------------
    EnvFlag('AMTPU_PACKED_EPILOGUE', 'bool', True, False,
            'native/__init__.py'),
    EnvFlag('AMTPU_CONF_DENSE_THRESH', 'int', 4, False,
            'native/__init__.py'),
    EnvFlag('AMTPU_HOST_DOM', 'raw', None, False, 'native/__init__.py'),
    EnvFlag('AMTPU_HOST_FULL', 'raw', None, False,
            'native/__init__.py, native/mesh_pool.py'),
    EnvFlag('AMTPU_HOST_REG', 'bool', True, False, 'native/__init__.py'),
    EnvFlag('AMTPU_WEFF', 'raw', None, False,
            'native/__init__.py (test-only window narrowing)'),
    EnvFlag('AMTPU_SHARD_MODE', 'str', '', False, 'native/__init__.py'),
    EnvFlag('AMTPU_NO_PALLAS', 'bool', False, False,
            'ops/pallas_common.py'),
    EnvFlag('AMTPU_ESCALATE', 'bool', True, False, 'ops/registers.py'),
    EnvFlag('AMTPU_MAX_TIER', 'int', 1024, False, 'ops/registers.py'),
    EnvFlag('AMTPU_ESCALATE_BUDGET_MB', 'int', -1, False,
            'ops/registers.py (unset -> built-in 256MB; explicit 0 '
            'forces the oracle)'),
    EnvFlag('AMTPU_ESC_CHUNK', 'int', 32768, False, 'ops/registers.py'),
    EnvFlag('AMTPU_DEVICE_MERGE', 'bool', True, False, 'ops/registers.py'),
    EnvFlag('AMTPU_PIPELINE_DEPTH', 'int', 2, False, 'native/__init__.py'),
    EnvFlag('AMTPU_PIPELINE_MIN_DOCS', 'int', 64, False,
            'native/__init__.py'),
    EnvFlag('AMTPU_NATIVE_LIB', 'str', '', False,
            'native/__init__.py (alternate .so path; the asan gate)'),
    # -- resident-state latches (C++ statics; bind at first batch) ----------
    EnvFlag('AMTPU_RESIDENT', 'raw', None, True,
            'native/__init__.py, native/core.cpp'),
    EnvFlag('AMTPU_RESIDENT_MIN', 'int', 16384, True, 'native/core.cpp'),
    EnvFlag('AMTPU_RESIDENT_CLK', 'raw', None, True, 'native/core.cpp'),
    EnvFlag('AMTPU_RESCLK_MAX_ACTORS', 'int', 512, True,
            'native/core.cpp'),
    EnvFlag('AMTPU_RESCLK_MAX_ROWS', 'int', 1048576, True,
            'native/core.cpp'),
    EnvFlag('AMTPU_TRIVIAL_HOST', 'bool', True, True, 'native/core.cpp'),
    EnvFlag('AMTPU_TRACE_BEGIN', 'raw', None, False,
            'native/core.cpp (per-begin debug trace)'),
    # -- mesh ---------------------------------------------------------------
    # overrides the layout the chips decide (native.make_pool: dp = the
    # chips of a multi-chip TPU process); a test seam, not a switch
    EnvFlag('AMTPU_MESH', 'special', None, True,
            'utils/common.py parse_mesh_env (factory override + fence + '
            'guard)'),
    EnvFlag('AMTPU_MESH_SP_MIN', 'int', 131072, False,
            'native/resident.py (default SP_CROSSOVER_ELEMS)'),
    EnvFlag('AMTPU_MESH_CONNECT_DEADLINE_S', 'float', 60, False,
            'sync/distributed.py'),
    # -- resilience / faults ------------------------------------------------
    EnvFlag('AMTPU_RESILIENCE', 'bool', True, False, 'resilience.py'),
    EnvFlag('AMTPU_RETRY_MAX', 'int', 3, False, 'resilience.py'),
    EnvFlag('AMTPU_RETRY_BACKOFF_S', 'float', 0.05, False,
            'resilience.py'),
    EnvFlag('AMTPU_DEGRADE', 'bool', False, False, 'resilience.py'),
    EnvFlag('AMTPU_FAULT', 'str', '', False, 'faults.py'),
    EnvFlag('AMTPU_FAULT_SEED', 'raw', None, False, 'faults.py'),
    # -- columnar storage / cold-state tier (ISSUE 10, 14) ------------------
    EnvFlag('AMTPU_STORAGE_FORMAT', 'str', 'columnar', False,
            'storage/__init__.py (json = v1 parity-oracle arm)'),
    EnvFlag('AMTPU_STORAGE_NATIVE', 'bool', True, False,
            'storage/columnar.py (0 = Python codec + dict-replay load, '
            'the parity-oracle arm; checked per call)'),
    EnvFlag('AMTPU_STORAGE_FOLD', 'bool', True, False,
            'native/__init__.py (0 = no op-state folding, the A/B arm '
            'of the folding lane)'),
    EnvFlag('AMTPU_STORAGE_CHUNK_MAX', 'int', 8, False,
            'native/__init__.py (snapshot chunks per doc before '
            're-compaction merges them; 0 disables)'),
    EnvFlag('AMTPU_STORAGE_DURABLE', 'bool', False, False,
            'storage/coldstore.py (fsync + per-dir manifest: the '
            'crash-safe replica-handoff transport)'),
    EnvFlag('AMTPU_STORAGE_DIR', 'str', '', False,
            'storage/coldstore.py (empty -> fresh tempdir)'),
    EnvFlag('AMTPU_STORAGE_GC_MIN', 'int', 256, False,
            'storage/coldstore.py (mutations per doc between settled '
            '-history folds; 0 disables GC)'),
    EnvFlag('AMTPU_RESIDENT_DOCS_MAX', 'int', 0, False,
            'storage/coldstore.py (0 = no cold-doc eviction)'),
    # -- clock folding + parallel restore (ISSUE 17) ------------------------
    EnvFlag('AMTPU_STORAGE_FOLD_CLOCKS', 'bool', True, False,
            'native/__init__.py (0 = keep per-change all_deps clock '
            'vectors sparse, the unfolded A/B-oracle arm)'),
    EnvFlag('AMTPU_FOLDCLK_MAX_ACTORS', 'int', 256, False,
            'native/__init__.py (per-doc actor-population cap for the '
            'densified clock-fold table; busier docs stay sparse)'),
    EnvFlag('AMTPU_RESTORE_THREADS', 'int', 0, False,
            'native/__init__.py (restore_from_store fan-out; 0 = auto '
            'min(8, cores), 1 = the serial A/B arm)'),
    EnvFlag('AMTPU_RESTORE_BATCH', 'int', 8192, False,
            'native/__init__.py (docs per decode+apply batch during '
            'restore_from_store)'),
    # -- sidecar client -----------------------------------------------------
    EnvFlag('AMTPU_WAL_COMPACT', 'int', 32, False, 'sidecar/client.py'),
    EnvFlag('AMTPU_WAL_MAX_BYTES', 'int', 67108864, False,
            'sidecar/client.py (log-byte compaction trigger; <=0 '
            'disables the byte bound)'),
    EnvFlag('AMTPU_SIDECAR_DEADLINE_S', 'float', 0, False,
            'sidecar/client.py (0 -> no deadline)'),
    EnvFlag('AMTPU_SIDECAR_HEARTBEAT_S', 'float', 0, False,
            'sidecar/client.py (0 -> no heartbeat)'),
    EnvFlag('AMTPU_SIDECAR_MAX_RESPAWNS', 'int', 3, False,
            'sidecar/client.py'),
    EnvFlag('AMTPU_SIDECAR_RESPAWN_DEADLINE_S', 'float', 30.0, False,
            'sidecar/client.py'),
    # -- serve gateway ------------------------------------------------------
    EnvFlag('AMTPU_GATEWAY', 'bool', True, False, 'sidecar/server.py'),
    EnvFlag('AMTPU_FLUSH_DEADLINE_MS', 'float', 2.0, False,
            'scheduler/queue.py'),
    EnvFlag('AMTPU_MAX_BATCH_DOCS', 'int', 256, False,
            'scheduler/queue.py'),
    EnvFlag('AMTPU_MAX_BATCH_OPS', 'int', 2048, False,
            'scheduler/queue.py'),
    EnvFlag('AMTPU_QUEUE_MAX_OPS', 'int', 4096, False,
            'scheduler/queue.py'),
    EnvFlag('AMTPU_QUEUE_LOW_FRAC', 'float', 0.5, False,
            'scheduler/queue.py'),
    # -- bounded egress / backpressure (ISSUE 13) ---------------------------
    EnvFlag('AMTPU_EGRESS_MAX_BYTES', 'int', 1048576, False,
            'scheduler/egress.py (per-conn queued-byte bound before '
            'tier-1 event shedding)'),
    EnvFlag('AMTPU_EGRESS_WEDGE_S', 'float', 10.0, False,
            'scheduler/egress.py (zero-progress seconds before tier-3 '
            'wedge eviction)'),
    EnvFlag('AMTPU_EGRESS_RESYNC_SHEDS', 'int', 3, False,
            'scheduler/egress.py (consecutive sheds before tier-2 '
            'drop-to-resubscribe)'),
    # -- batched sync fan-out -----------------------------------------------
    EnvFlag('AMTPU_FANOUT', 'bool', True, False, 'scheduler/gateway.py'),
    EnvFlag('AMTPU_FANOUT_VECTOR', 'bool', True, False,
            'sync/fanout.py (0 = per-peer scalar loop; A/B + oracle)'),
    EnvFlag('AMTPU_FANOUT_PRESENCE', 'bool', True, False,
            'sync/fanout.py'),
    # -- analysis / sanitizer ----------------------------------------------
    EnvFlag('AMTPU_SANITIZE', 'bool', False, False,
            'analysis/sanitize.py (poisons staging buffers post-dispatch)'),
    # -- fleet router / rebalancer (ISSUE 18) ------------------------------
    EnvFlag('AMTPU_ROUTE_VNODES', 'int', 64, False,
            'router/ring.py (virtual nodes per replica on the '
            'consistent-hash ring)'),
    EnvFlag('AMTPU_ROUTE_REDIRECTS', 'int', 3, False,
            'router/gateway.py + sidecar/client.py (max WrongReplica '
            'redirect hops per request before the error surfaces)'),
    EnvFlag('AMTPU_ROUTE_HANDOFF_DIR', 'str', '', False,
            'router/rebalance.py (root dir for durable migration '
            'handoff stores; empty -> per-process tempdir)'),
    EnvFlag('AMTPU_REBALANCE_INTERVAL_S', 'float', 5.0, False,
            'router/rebalance.py (seconds between rebalancer scrape '
            'passes)'),
    EnvFlag('AMTPU_REBALANCE_TOPK', 'int', 4, False,
            'router/rebalance.py (max hot-doc victims one rebalance '
            'pass migrates)'),
    EnvFlag('AMTPU_REBALANCE_MIN_SKEW', 'float', 0.5, False,
            'router/rebalance.py (relative occupancy spread '
            '(max-min)/mean below which the fleet counts as balanced)'),
    EnvFlag('AMTPU_REBALANCE_PRESSURE', 'float', 0.8, False,
            'router/rebalance.py (memory pressure on any replica past '
            'which a rebalance triggers regardless of skew)'),
    # -- fleet failover (ISSUE 19) ------------------------------------------
    EnvFlag('AMTPU_FLEET_HEARTBEAT_S', 'float', 0.5, False,
            'router/health.py (seconds between heartbeat probe sweeps '
            'over the ring members)'),
    EnvFlag('AMTPU_FLEET_DEADLINE_S', 'float', 0.5, False,
            'router/health.py (per-probe answer deadline; a hung '
            'replica counts as a miss)'),
    EnvFlag('AMTPU_FLEET_MISS_MAX', 'int', 3, False,
            'router/health.py (consecutive misses before a suspect '
            'member is declared dead and failed over)'),
    EnvFlag('AMTPU_FLEET_PARK_S', 'float', 10.0, False,
            'router/gateway.py (max seconds a frame parks for a '
            'suspect/dead member before the retryable envelope)'),
    EnvFlag('AMTPU_FLEET_PARK_MB', 'int', 8, False,
            'router/gateway.py (byte budget across all fleet-parked '
            'frames; overflow answers the retryable envelope)'),
    EnvFlag('AMTPU_FLEET_FLAP_MAX', 'int', 3, False,
            'router/supervisor.py (lineage deaths before respawns '
            'stop and the member is quarantined)'),
    EnvFlag('AMTPU_STORAGE_SYNC', 'bool', False, False,
            'scheduler/gateway.py (write-through checkpoint every '
            'acked mutation into the durable store pre-ack; the '
            'failover byte-parity guarantee rests on it)'),
    # -- read path (patch shipping / replicas / snapshots) ------------------
    EnvFlag('AMTPU_READ_PATCH', 'bool', True, False,
            'sync/fanout.py (0 refuses mode:"patch" subscriptions '
            'with a typed RangeError; change-mode fan-out unaffected)'),
    EnvFlag('AMTPU_READ_SNAPSHOT_CACHE', 'int', 64, False,
            'readview/snapshot.py (max resident frontier-clock-keyed '
            'container blobs, LRU)'),
    EnvFlag('AMTPU_READ_STALENESS_SLO_S', 'float', 5.0, False,
            'readview/replica.py (seconds a replica doc may lag the '
            'upstream frontier before a forced catch-up)'),
    EnvFlag('AMTPU_READ_RESYNC_S', 'float', 2.0, False,
            'readview/replica.py (staleness probe cadence against the '
            'upstream get_clock frontier)'),
)

SPEC = {f.name: f for f in ENV_FLAGS}

#: the three numeric C++ latch defaults exposed through the
#: `amtpu_latch_defaults` ABI, in ABI order -- check_env compares the
#: spec rows against the built library so a core.cpp constant bump
#: cannot drift past this table (or the flip guard reading the ABI)
ABI_LATCH_DEFAULTS = ('AMTPU_RESIDENT_MIN', 'AMTPU_RESCLK_MAX_ACTORS',
                      'AMTPU_RESCLK_MAX_ROWS')

#: bench/tools harness knob families: allowed in the docs env table and
#: in harness code without individual spec rows (they configure the
#: measurement harnesses, not the serving process)
HARNESS_PREFIXES = ('AMTPU_BENCH_', 'AMTPU_TCHECK_', 'AMTPU_MESHCHECK_',
                    'AMTPU_MC_', 'AMTPU_MULTICHIP_', 'AMTPU_DRYRUN_',
                    'AMTPU_SMOKE_')
