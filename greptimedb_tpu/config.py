"""Layered configuration (defaults < TOML file < env < CLI flags).

Capability counterpart of the reference's options system
(/root/reference/src/cmd/src/options.rs GreptimeOptions::load_layered_
options: serde defaults, `--config-file` TOML, `GREPTIMEDB_<ROLE>__`
double-underscore env keys, CLI overrides — last wins).

Every role process (standalone/frontend/datanode/metasrv/flownode)
resolves its options through `load_options`; values are kept as a
nested dict with dotted-path access so new sections need no schema
changes here.
"""

from __future__ import annotations

import os

ENV_PREFIX = "GREPTIMEDB_TPU"

# role-shared defaults; role sections are only consulted by their role
DEFAULTS: dict = {
    "data_home": "./greptimedb_tpu_data",
    "default_timezone": "UTC",
    "http": {
        "addr": "127.0.0.1:4000", "enable": True,
        "tls": {"cert_path": "", "key_path": ""},
    },
    # self-import node metrics into the TSDB every write_interval_s
    # (reference: src/servers/src/export_metrics.rs)
    "export_metrics": {
        "enable": False, "db": "greptime_metrics",
        "write_interval_s": 30.0,
    },
    # anonymous usage reporting (ref src/common/greptimedb-telemetry);
    # nothing is sent unless enable=true AND an endpoint is configured
    "telemetry": {"enable": False, "endpoint": "", "interval_s": 1800.0},
    # arrow flight; advertise_addr overrides the address peers dial
    # (bind-addr with the resolved port otherwise — port-0 binds and
    # wildcard hosts need it)
    "grpc": {"addr": "127.0.0.1:4001", "enable": True,
             "advertise_addr": ""},
    "mysql": {"addr": "127.0.0.1:4002", "enable": True},
    "postgres": {"addr": "127.0.0.1:4003", "enable": True},
    "opentsdb": {"enable": True},
    "influxdb": {"enable": True},
    "wal": {"sync": False, "backend": "fs", "topics": 4},
    "storage": {
        "type": "fs",            # fs | memory | s3
        # s3: bucket/endpoint/access_key_id/secret_access_key/region/root
        "cache_capacity_bytes": 0,
        # optional dedicated cold-tier store ([storage.cold], same
        # keys as [storage]): compaction rewrites windows past
        # [compaction] cold_horizon_ms onto it. Absent, cold files ride
        # the primary store BENEATH any local read cache.
        "cold": {},
    },
    "flow": {"enable": True, "tick_interval_s": 1.0},
    # pipelined wire-ingest dataplane (greptimedb_tpu/ingest/): the
    # frontend write path batches, coalesces, and streams region writes
    # to every datanode concurrently over long-lived Flight streams
    "ingest": {
        "pipeline": True,            # false = serial blocking DoPut
        "batch_max_rows": 262144,    # per coalesced wire batch group
        "coalesce_min_rows": 4096,   # group-commit target batch size
        "max_delay_ms": 4.0,         # max adaptive coalesce hold
        "queue_max_rows": 1048576,   # per-datanode backpressure bound
        "block_timeout_s": 2.0,      # blocked past this => 429 shed
        "max_inflight_groups": 2,    # double-buffered send/apply
        "ack_timeout_s": 60.0,       # unacked past this => overloaded
        "idle_stream_s": 60.0,       # close parked streams after this
    },
    # distributed query dataplane (dist/): datanode merged-scan cache,
    # intra-datanode region-scan parallelism, frontend fan-out pool
    "dist_query": {
        "scan_cache_bytes": 268435456,   # datanode LRU byte budget
        "region_scan_parallelism": 4,    # bounded pool per datanode
        "fanout_pool_size": 8,           # shared frontend fan-out pool
    },
    "engine": {
        "enable_background": True,
        "background_interval_s": 5.0,
    },
    # recovery & startup dataplane (storage/recovery.py): bounded
    # region-parallel open, pipelined SST restore with a readahead
    # window, manifest checkpoint cadence, and the post-replay flush
    # that truncates the WAL so the next restart replays nothing
    "recovery": {
        "open_parallelism": 0,          # 0 = min(8, regions in batch)
        "sst_prefetch_depth": 4,        # ranged gets in flight / region
        "checkpoint_interval_edits": 64,
        "flush_after_replay": True,
        "restore_ssts": False,          # eager fetch+verify+warm at open
    },
    # compaction + tiered-storage dataplane (storage/compaction.py):
    # leveled TWCS merges on a bounded per-engine pool with
    # device-accelerated merge, tombstone GC when a merge covers every
    # overlapping live file, and hot/cold tiering past the horizon.
    # The L0 trigger + window stay per-table (WITH(...) options).
    "compaction": {
        "workers": 1,                    # bounded merge pool size
        "l1_trigger_files": 4,           # L1 -> L2 file-count trigger
        "l1_trigger_bytes": 268435456,   # L1 -> L2 byte trigger (0=off)
        "l2_trigger_files": 4,           # L2 self-merge trigger
        "cold_horizon_ms": 0,            # rewrite older windows cold; 0=off
        "device_merge_min_rows": 262144, # device merge threshold; <=0 host
        "verify_device_merge": False,    # assert device == host per merge
        "prefetch_depth": 4,             # pipelined compaction-read window
        "cleanup_orphans": True,         # drop unreferenced SSTs at open
    },
    # query admission control + scheduling (sched/): per-tenant token
    # buckets and concurrency limits over a bounded priority queue,
    # queue-time SLOs, end-to-end deadlines, graceful degradation.
    # 0 = unlimited for every limit knob; the permissive defaults keep
    # the controller on the hot path without ever queueing or shedding
    "scheduler": {
        "enable": True,
        "max_concurrency": 0,        # global execution slots
        "queue_depth": 256,          # bounded wait queue (0 = unbounded)
        "queue_timeout_s": 10.0,     # queue-time SLO => 503 shed (0 = none)
        "default_deadline_s": 0.0,   # absolute per-query deadline
        "tenant_qps": 0.0,           # per-tenant token bucket rate
        "tenant_burst": 0.0,         # 0 => max(1, 2*qps)
        "tenant_concurrency": 0,     # per-tenant execution slots
        "allow_partial_results": False,  # degrade instead of fail
        # per-tenant overrides: [scheduler.tenants.<name>]
        # qps/burst/concurrency/priority (lower priority runs first)
        "tenants": {},
    },
    # adaptive control plane (autotune/): feedback controllers over the
    # observability surfaces move the runtime-mutable knobs through the
    # validated registry (ADMIN set_config rides the same path).
    # Off by default — enabling it hands the listed knobs to the
    # controllers; durability/correctness knobs are never registered.
    "autotune": {
        "enable": False,
        "tick_interval_s": 5.0,      # control-loop cadence
        "history": 256,              # decision audit-log ring size
        # shared guardrails (controllers.py Guardrails)
        "step": 0.25,                # max relative knob move per decision
        "band": 0.15,                # hysteresis dead-band
        "cooldown_ticks": 2,         # hold ticks after a decision
        # per-controller enables
        "admission": True,           # [scheduler] max_concurrency
        "planner": True,             # [mesh] shard_min_series/rows
        "hbm": True,                 # session/result/scan byte budgets
        "compaction": True,          # [compaction] workers/trigger
    },
    # multi-chip sharded query execution (parallel/mesh.py): one
    # process-wide mesh over the visible devices; large grids shard the
    # series axis across it and the shard_map reduction programs
    # recombine with explicit collectives. The replicate-vs-shard
    # thresholds feed query/planner.decide_mesh_execution.
    "mesh": {
        "enabled": False,
        "axis_size": 0,                 # shard-axis devices; 0 = all
        "time_parallel": 1,             # devices on the time axis
        "force_host_device_count": 0,   # CPU simulation (virtual devices)
        "shard_min_series": 4096,       # grids below this replicate
        "shard_min_rows": 262144,       # row reductions below this replicate
    },
    # secondary tag-index dataplane (index/): per-region inverted
    # tag-value -> sid postings over the dictionary-coded label plane,
    # version-validated, with a memoized per-matcher-set sid cache and
    # (device_plane) the label plane HBM-resident so matcher masks are
    # computed on device. enable=false falls every matcher back to the
    # full label-plane compare (the bit-identical oracle).
    "index": {
        "enable": True,
        "device_plane": True,
        "result_cache_entries": 256,   # per-index memoized matcher sets
        "rebuild_threshold": 4096,     # delta series before CSR rebuild
    },
    "frontend": {
        # flight addresses of the datanodes this frontend fans out to
        "datanode_addrs": [],
        # flight address of the flownode continuous-aggregation flows
        # run on ("" = run flows in-process on the frontend)
        "flownode_addr": "",
    },
    "metasrv": {
        "addr": "127.0.0.1:4010", "selector": "round_robin",
        # phi-accrual failure detection (meta/failure_detector.py):
        # threshold + acceptable heartbeat pause drive how fast a
        # silent node flips UNHEALTHY -> DOWN on the cluster surfaces
        "phi_threshold": 8.0,
        "acceptable_pause_ms": 10000.0,
    },
    "datanode": {"node_id": 0, "metasrv_addr": ""},
    # fleet observability plane (dist/fleet.py + telemetry/
    # node_stats.py): every role attaches a compact node-stats payload
    # to its metasrv heartbeat; the frontend serves cluster-wide
    # information_schema.cluster_* tables by fanning the bounded
    # node_telemetry Flight action to every peer, /v1/cluster/metrics
    # federates every node's metric families behind a TTL cache, and
    # /health?deep=1 + /v1/cluster/health run real readiness probes
    "fleet": {
        "enable": True,
        "stats_interval_s": 2.0,     # min spacing of heartbeat payloads
        "heartbeat_interval_s": 2.0,  # heartbeat loop cadence
        "history": 32,               # metasrv per-node sample ring size
        "fanout_timeout_s": 5.0,     # per-peer bound for cluster_* fan-out
        "cache_ttl_s": 5.0,          # federated-scrape cache TTL
    },
    # gtsan cooperative concurrency sanitizer (tools/san): off by
    # default — the concurrency facade hands out raw stdlib objects
    # and adds no per-operation cost. enable=true (or GTPU_SAN=1)
    # switches to instrumented locks/threads/pools
    "sanitizer": {
        "enable": False,
        "hold_time_ms": 1000.0,   # GTS103 lock hold-time threshold
        "fail_on_cycle": True,    # findings fail the run (vs report)
    },
    # end-to-end distributed tracing (telemetry/tracing.py): every
    # query/ingest batch produces one stitched trace across processes
    # (frontend sched/plan/fan-out + datanode scan + device
    # compile/execute/transfer spans under a shared trace_id), served
    # by /v1/traces + information_schema.traces. Sampling is
    # TAIL-BASED: slow (>= slow_ms), errored and shed statements are
    # kept for cause (against sampling, and evicted from the ring after
    # the traces kept by chance); the rest keep with probability
    # sample_ratio
    "tracing": {
        "enable": True,
        "sample_ratio": 1.0,    # head probability for unremarkable traces
        "capacity": 256,        # trace ring size (0 = unbounded)
        "slow_ms": 5000.0,      # always-keep threshold for slow traces
    },
    # query execution device preference (None = row-count heuristic);
    # true forces the grid/device fast paths — what the dist-process
    # tracing test uses to exercise device attribution on CPU jax
    "query": {"prefer_device": None},
    # persistent query sessions (query/sessions.py): folded device
    # RESULT buffers stay HBM-resident across polls, so a repeated
    # dashboard query skips the program dispatch round trip and delta
    # polls slice device-side. LRU byte budget over HBM.
    "sessions": {
        "enable": True,
        "hbm_bytes": 1073741824,
    },
    # frontend result-set cache (query/result_cache.py): completed
    # result payloads keyed on (statement fingerprint, physical
    # versions), served without touching datanode or device while
    # versions match. Off by default: turning it on makes REPEATED
    # identical statements answer from the frontend (dashboards want
    # this; debugging repeated-execution behavior does not).
    # validate_interval_ms > 0 bounds how often a dist frontend
    # re-validates versions against the datanodes (staleness bound);
    # 0 validates every poll (free locally, one cheap metadata action
    # per datanode for dist tables).
    "result_cache": {
        "enable": False,
        "bytes": 268435456,
        "validate_interval_ms": 0.0,
    },
    # unified memory observability (telemetry/memory.py): every
    # byte-budgeted pool (device grid/session caches, host scan/result/
    # page caches, trace ring, ingest queues) registers with one
    # process-wide accountant. device_budget_bytes > 0 adds a GLOBAL
    # HBM watermark below the sum of individual pool budgets, enforced
    # by demand-driven proportional eviction across the device pools;
    # census_on_scrape reconciles owner-tagged buffers against
    # jax.live_arrays() on every /metrics render so
    # gtpu_mem_unaccounted_device_bytes is an always-on leak detector
    "memory": {
        "enable": True,
        "device_budget_bytes": 0,   # 0 = per-pool budgets only
        "census_on_scrape": True,
    },
    # statement statistics (telemetry/stmt_stats.py): every executed
    # statement folds into a registry row keyed by its normalized
    # fingerprint (literals/IN-lists folded) — calls, errors, latency
    # percentiles, exec path, compile/cache hits, transfer bytes, shed
    # counts, last trace id. Surfaced as information_schema.
    # statement_statistics, /v1/stats/statements and gtpu_stmt_*
    # metrics. max_fingerprints bounds the registry (LRU rows collapse
    # into "_other"); metric_fingerprints bounds the /metrics label
    # cardinality (first-come, later fingerprints export as "_other").
    # Reset at runtime with ADMIN reset_statement_statistics().
    "stmt_stats": {
        "enable": True,
        "max_fingerprints": 512,
        "metric_fingerprints": 64,
    },
    # device program profiler (telemetry/device_programs.py): every
    # jit/shard_map program dispatched through a device_call registers
    # one row — calls, compile_ms, execute p50/p99, transfer bytes,
    # XLA cost_analysis flops / bytes accessed, memory_analysis
    # temp/output bytes, and a roofline verdict (bound=compute|memory,
    # %-of-peak) against the hardware peaks. Surfaced as
    # information_schema.device_programs, /debug/prof/device and
    # gtpu_device_program_* metrics; reset with ADMIN
    # reset_device_profiler(). peak_tflops / peak_hbm_gbps at 0 mean
    # auto: a TPU's peaks come from device_programs.DEVICE_PEAKS by
    # its device_kind (v5e: 197 TFLOP/s bf16, 819 GB/s HBM); a TPU
    # kind not in the table and every CPU run report achieved-only.
    # analysis=false skips the lazy XLA cost/memory analysis (rows
    # keep per-call stats only). trace_dir is where
    # /debug/prof/device/trace?seconds= writes its TensorBoard/
    # perfetto-loadable captures ("" = the system temp dir).
    # metric_programs bounds the /metrics label cardinality (first-
    # come, like stmt_stats' metric_fingerprints — exported series can
    # never be evicted, so programs past the cap export under
    # program="_other").
    "profiling": {
        "enable": True,
        "max_programs": 256,
        "metric_programs": 128,
        "peak_tflops": 0.0,
        "peak_hbm_gbps": 0.0,
        "analysis": True,
        "trace_dir": "",
    },
    "logging": {
        "level": "info",
        # statements slower than threshold land in the slow-query log +
        # information_schema.slow_queries (ref [logging.slow_query])
        "slow_query": {
            "enable": True, "threshold_s": 5.0, "sample_ratio": 1.0,
        },
    },
}


class Options:
    """Nested options with dotted-path access: opts.get('http.addr')."""

    def __init__(self, values: dict):
        self.values = values

    def get(self, path: str, default=None):
        cur = self.values
        for part in path.split("."):
            if not isinstance(cur, dict) or part not in cur:
                return default
            cur = cur[part]
        return cur

    def section(self, name: str) -> dict:
        v = self.get(name, {})
        return v if isinstance(v, dict) else {}

    def set(self, path: str, value):
        cur = self.values
        parts = path.split(".")
        for part in parts[:-1]:
            cur = cur.setdefault(part, {})
        cur[parts[-1]] = value


def _deep_merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _parse_scalar(raw: str):
    """Env values parse like TOML scalars; unparseable stays a string."""
    low = raw.strip().lower()
    if low in ("true", "false"):
        return low == "true"
    try:
        return int(raw)
    except ValueError:
        pass
    try:
        return float(raw)
    except ValueError:
        pass
    if raw.startswith("[") and raw.endswith("]"):
        inner = raw[1:-1].strip()
        if not inner:
            return []
        return [
            _parse_scalar(x.strip().strip("'\""))
            for x in inner.split(",")
        ]
    return raw


def _env_overrides(env, prefixes: list[str]) -> dict:
    out: dict = {}
    for key, raw in env.items():
        for pfx in prefixes:
            if not key.startswith(pfx + "__"):
                continue
            path = key[len(pfx) + 2:].lower().split("__")
            cur = out
            for part in path[:-1]:
                cur = cur.setdefault(part, {})
            cur[path[-1]] = _parse_scalar(raw)
            break
    return out


def load_options(
    role: str = "standalone",
    config_file: str | None = None,
    env: dict | None = None,
    cli_overrides: dict | None = None,
) -> Options:
    """Resolve options for a role: defaults < TOML < env < CLI.

    env keys: GREPTIMEDB_TPU__SECTION__KEY (or the role-scoped
    GREPTIMEDB_TPU_<ROLE>__SECTION__KEY, which wins over the generic
    prefix). cli_overrides maps dotted paths to values; None values are
    skipped so unset flags never mask lower layers.
    """
    import copy

    # deep copy: Options.set writes into nested dicts, which must never
    # reach back into the shared module-level DEFAULTS
    values = copy.deepcopy(DEFAULTS)
    if config_file:
        try:
            import tomllib  # 3.11+
        except ModuleNotFoundError:  # 3.10: same API, external name
            import tomli as tomllib

        with open(config_file, "rb") as f:
            values = _deep_merge(values, tomllib.load(f))
    env = dict(os.environ if env is None else env)
    for prefixes in (
        [ENV_PREFIX],
        [f"{ENV_PREFIX}_{role.upper()}"],
    ):
        ov = _env_overrides(env, prefixes)
        if ov:
            values = _deep_merge(values, ov)
    opts = Options(values)
    for path, value in (cli_overrides or {}).items():
        if value is not None:
            opts.set(path, value)
    return opts
