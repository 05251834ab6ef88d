"""Declarative validated configs + dynamic config delivery.

Ref shape: core/ytree/yson_struct.h (TYsonStruct: registered parameters with
defaults, validators, postprocessors, recursive merge) and
library/dynamic_config/dynamic_config_manager.h:23 (polls a Cypress path,
diffs, applies, keeps the last good config on validation failure).

Redesign: instead of C++ macro registration, a `YsonStruct` base class scans
class-level `param(...)` declarations at subclass creation.  Values load
from YSON-shaped dicts (bytes keys tolerated), merge recursively, and
round-trip through `to_dict` for persistence in Cypress documents.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Optional

from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.utils.logging import get_logger

logger = get_logger("Config")


class _Param:
    """One declared parameter: default, type, constraints."""

    __slots__ = ("name", "default", "default_factory", "type", "ge", "le",
                 "choices", "validator")

    def __init__(self, default=None, *, default_factory=None, type=None,
                 ge=None, le=None, choices=None, validator=None):
        self.name: str = ""            # filled by __set_name__
        self.default = default
        self.default_factory = default_factory
        self.type = type
        self.ge = ge
        self.le = le
        self.choices = choices
        self.validator = validator

    def __set_name__(self, owner, name):
        self.name = name

    def make_default(self):
        if self.default_factory is not None:
            return self.default_factory()
        if isinstance(self.type, type) and issubclass(self.type, YsonStruct) \
                and self.default is None:
            return self.type()
        return self.default

    def check(self, value, path: str) -> Any:
        if value is None:
            # Explicit null resets to the default (it must NOT bypass
            # validation and poison consumers with unexpected Nones).
            return self.make_default()
        if self.type is not None:
            if isinstance(self.type, type) and issubclass(self.type,
                                                          YsonStruct):
                if isinstance(value, dict):
                    value = self.type.from_dict(value, path=path)
                elif not isinstance(value, self.type):
                    raise YtError(f"Config {path}: expected map for "
                                  f"{self.type.__name__}, got {value!r}",
                                  code=EErrorCode.InvalidConfig)
            elif self.type is float and isinstance(value, int) \
                    and not isinstance(value, bool):
                value = float(value)
            elif self.type is str and isinstance(value, bytes):
                value = value.decode("utf-8")
            elif not isinstance(value, self.type) \
                    or (self.type is int and isinstance(value, bool)):
                raise YtError(f"Config {path}: expected "
                              f"{self.type.__name__}, got {value!r}",
                              code=EErrorCode.InvalidConfig)
        if self.ge is not None and value < self.ge:
            raise YtError(f"Config {path}: {value!r} < minimum {self.ge!r}",
                          code=EErrorCode.InvalidConfig)
        if self.le is not None and value > self.le:
            raise YtError(f"Config {path}: {value!r} > maximum {self.le!r}",
                          code=EErrorCode.InvalidConfig)
        if self.choices is not None and value not in self.choices:
            raise YtError(f"Config {path}: {value!r} not one of "
                          f"{sorted(self.choices)!r}",
                          code=EErrorCode.InvalidConfig)
        if self.validator is not None:
            self.validator(value)
        return value


def param(default=None, **kwargs) -> Any:
    """Declare a config parameter on a YsonStruct subclass."""
    return _Param(default, **kwargs)


class YsonStruct:
    """Base for declarative configs; see module docstring.

    Subclasses declare parameters:

        class StoreConfig(YsonStruct):
            capacity_bytes = param(1 << 30, type=int, ge=0)
            codec = param("lz4", type=str, choices={"none", "lz4", "zstd"})

    Unknown keys raise by default; set `keep_unrecognized = True` to retain
    them (exposed via `.unrecognized`).
    """

    keep_unrecognized = False
    _params: dict[str, _Param] = {}

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        merged: dict[str, _Param] = dict(cls.__mro__[1]._params) \
            if hasattr(cls.__mro__[1], "_params") else {}
        for name, value in list(vars(cls).items()):
            if isinstance(value, _Param):
                merged[name] = value
        cls._params = merged

    def __init__(self, **overrides):
        self.unrecognized: dict[str, Any] = {}
        for name, p in self._params.items():
            setattr(self, name, p.make_default())
        for name, value in overrides.items():
            if name not in self._params:
                raise YtError(f"Unknown config parameter {name!r}",
                              code=EErrorCode.InvalidConfig)
            setattr(self, name, self._params[name].check(value, name))
        self.postprocess()

    # -- hooks -----------------------------------------------------------------

    def postprocess(self) -> None:
        """Cross-field validation; override in subclasses."""

    # -- load / dump -----------------------------------------------------------

    @classmethod
    def from_dict(cls, data: dict, path: str = "") -> "YsonStruct":
        self = cls.__new__(cls)
        self.unrecognized = {}
        data = {(k.decode("utf-8") if isinstance(k, bytes) else k): v
                for k, v in (data or {}).items()}
        for name, p in cls._params.items():
            here = f"{path}/{name}" if path else name
            if name in data:
                setattr(self, name, p.check(data.pop(name), here))
            else:
                setattr(self, name, p.make_default())
        if data:
            if cls.keep_unrecognized:
                self.unrecognized = data
            else:
                raise YtError(
                    f"Unrecognized config keys at {path or '/'}: "
                    f"{sorted(data)!r}", code=EErrorCode.InvalidConfig)
        self.postprocess()
        return self

    def to_dict(self) -> dict:
        out = {}
        for name in self._params:
            value = getattr(self, name)
            out[name] = value.to_dict() if isinstance(value, YsonStruct) \
                else value
        out.update(self.unrecognized)
        return out

    # -- merge -----------------------------------------------------------------

    def merge(self, patch: Optional[dict]) -> "YsonStruct":
        """Recursive merge: returns a NEW validated instance; `self` is
        untouched (the dynamic-config manager keeps the old config when the
        merged one fails validation)."""
        merged = _deep_merge(self.to_dict(), patch or {})
        return type(self).from_dict(merged)

    def __eq__(self, other):
        return type(other) is type(self) and other.to_dict() == self.to_dict()

    def __repr__(self):
        inner = ", ".join(f"{k}={getattr(self, k)!r}" for k in self._params)
        return f"{type(self).__name__}({inner})"


def _deep_merge(base: dict, patch: dict) -> dict:
    out = dict(base)
    for key, value in patch.items():
        if isinstance(key, bytes):
            key = key.decode("utf-8")
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _deep_merge(out[key], value)
        else:
            out[key] = value
    return out


# ---------------------------------------------------------------------------
# Daemon configs (static YSON file; every server role loads one of these).
# ---------------------------------------------------------------------------

class RetryPolicyConfig(YsonStruct):
    """Jittered-exponential-backoff retry knobs shared by every recovery
    ladder (RPC channels, replicated chunk reads, per-shard query
    retries).  Delay for attempt i is
    `min(backoff * 2^i, backoff_cap) * (1 - jitter * U[0,1))` — the
    jitter decorrelates retry storms after a common-cause failure."""

    attempts = param(5, type=int, ge=1)
    backoff = param(0.2, type=float, ge=0.0)
    backoff_cap = param(3.0, type=float, ge=0.0)
    jitter = param(0.2, type=float, ge=0.0, le=1.0)
    # Token-bucket retry budget (ISSUE 17): each retry spends one token,
    # each SUCCESSFUL call deposits `retry_budget_refill` tokens (capped
    # at `retry_budget`), and a throttled outcome deposits NOTHING — an
    # overloaded cluster sees its retry traffic decay instead of a
    # retry storm.  0 disables the budget (unbounded retries, the
    # pre-ISSUE-17 behavior).
    retry_budget = param(0, type=int, ge=0)
    retry_budget_refill = param(0.1, type=float, ge=0.0)

    def delay(self, attempt: int, rng=None) -> float:
        base = min(self.backoff * (2 ** attempt), self.backoff_cap)
        if self.jitter <= 0.0 or base <= 0.0:
            return base
        import random as _random
        u = (rng or _random).random()
        return base * (1.0 - self.jitter * u)


# Process-wide retry policies, keyed by ladder.  Call sites read these
# instead of hardcoding attempts/backoff (ISSUE 2 satellite); tests and
# daemons override via set_retry_policy.
_RETRY_POLICIES: dict[str, RetryPolicyConfig] = {}
_RETRY_DEFAULTS: dict[str, dict] = {
    # General RPC transport retries (RetryingChannel's historical 5/0.2).
    "rpc": {},
    # Remote job start/poll: fail fast so the job revives on another node.
    "job_rpc": dict(attempts=2, backoff=0.1, backoff_cap=1.0),
    # Replicated chunk read ladder: rotate fast, short waits.
    "chunk_read": dict(attempts=3, backoff=0.05, backoff_cap=1.0,
                       jitter=0.5),
    # Per-shard retry inside coordinate_and_execute.
    "query_shard": dict(attempts=3, backoff=0.05, backoff_cap=0.5,
                        jitter=0.5),
}


def retry_policy(name: str) -> RetryPolicyConfig:
    policy = _RETRY_POLICIES.get(name)
    if policy is None:
        defaults = _RETRY_DEFAULTS.get(name)
        if defaults is None:
            raise YtError(f"Unknown retry policy {name!r}",
                          code=EErrorCode.InvalidConfig)
        policy = _RETRY_POLICIES[name] = RetryPolicyConfig(**defaults)
    return policy


def set_retry_policy(name: str, policy: RetryPolicyConfig) -> None:
    if name not in _RETRY_DEFAULTS:
        raise YtError(f"Unknown retry policy {name!r}",
                      code=EErrorCode.InvalidConfig)
    _RETRY_POLICIES[name] = policy


class TabletConfig(YsonStruct):
    """Tablet read-path knobs (tablet/tablet.py):

    - `host_plane_cache_capacity`: entries in the per-tablet LRU of
      host-side numpy plane views (promote-on-hit; the lookup probe's
      device→host staging cache).
    - `snapshot_cache_enabled`: memoize the materialized visible chunk
      per (flush generation, store mutation count) for latest-timestamp
      reads; invalidated by any write/flush/compact.
    - `vectorized_scan_min_rows`: version count at/above which the MVCC
      merge (read_snapshot/flush/compact) runs as the columnar XLA
      pipeline; below it the Python reference merge wins (per-program
      dispatch overhead dominates tiny stores — the same dispatch
      economics as coordinator shard coalescing).  0 forces the
      vectorized path always (parity tests use this)."""

    host_plane_cache_capacity = param(64, type=int, ge=1)
    snapshot_cache_enabled = param(True, type=bool)
    vectorized_scan_min_rows = param(1024, type=int, ge=0)


_TABLET_CONFIG: "Optional[TabletConfig]" = None


def tablet_config() -> TabletConfig:
    global _TABLET_CONFIG
    if _TABLET_CONFIG is None:
        _TABLET_CONFIG = TabletConfig()
    return _TABLET_CONFIG


def set_tablet_config(config: "Optional[TabletConfig]") -> None:
    """Install a process-wide tablet config (None restores defaults)."""
    global _TABLET_CONFIG
    _TABLET_CONFIG = config


class TracingConfig(YsonStruct):
    """Query flight recorder knobs (utils/tracing.py + query/profile.py):

    - `enabled`: master switch; False turns every span site into the
      NULL fast path (one contextvar read and the `NULL_SPAN` singleton:
      `child_span(...) is NULL_SPAN`, tests/test_flight_recorder.py).
    - `sample_rate`: probability a new ROOT trace records its spans
      (entry points: gateway select/lookup, scheduler operations, HTTP
      proxy).  explain_analyze and X-YT-Trace-Id requests always sample.
    - `slow_query_threshold`: queries at/above this wall time (seconds)
      are ALWAYS retained in the flight recorder's slow-query log;
      faster queries are retained at `sample_rate`.
    - `slow_log_capacity` / `recent_log_capacity`: bounded profile logs.
    - `ring_capacity`: finished-span ring buffer size (bounded memory:
      34.5 MB of host memory full, at 527 B a span; the default holds a
      51 s window of the benchmark's Q1 cell, 12 spans a select, down
      to a 9.4 ms call, PERF.md §7).
    """

    enabled = param(True, type=bool)
    sample_rate = param(1.0, type=float, ge=0.0, le=1.0)
    slow_query_threshold = param(0.5, type=float, ge=0.0)
    slow_log_capacity = param(128, type=int, ge=1)
    recent_log_capacity = param(128, type=int, ge=1)
    ring_capacity = param(65536, type=int, ge=1)


_TRACING_CONFIG: "Optional[TracingConfig]" = None


def tracing_config() -> TracingConfig:
    global _TRACING_CONFIG
    if _TRACING_CONFIG is None:
        _TRACING_CONFIG = TracingConfig()
    return _TRACING_CONFIG


def set_tracing_config(config: "Optional[TracingConfig]") -> None:
    """Install a process-wide tracing config (None restores defaults);
    pushes the fast-path mirrors into utils/tracing."""
    global _TRACING_CONFIG
    _TRACING_CONFIG = config
    from ytsaurus_tpu.utils import tracing
    tracing.configure(config)


class SloConfig(YsonStruct):
    """One service-level objective, evaluated over the metrics-history
    rings (utils/profiling.MetricsHistory) with multi-window burn-rate
    alerting (utils/slo.SloTracker).

    Two SLI shapes cover the fleet's objectives:

    - `availability`/`ratio`: good/bad event counters.  The SLI over a
      window is bad/(good+bad) from the counters' history deltas —
      e.g. admission rejects vs admits, or compile-cache misses vs hits
      (`compile_cache_hit_rate`, the ROADMAP item 1 acceptance gate).
    - `latency`: a histogram sensor plus `bound_ms`.  Error events are
      observations above the bound (from bucket-count deltas), so
      "`objective` of requests finish within `bound_ms`" — the p99-style
      objective — needs no per-request log, just the bucket rings.
      `bound_ms` should align with a bucket bound; the evaluator uses
      the tightest bucket that contains it (errors only over-count).

    Burn rate = error_rate / (1 - objective): 1.0 burns the whole error
    budget exactly over the SLO period.  The alert FIRES when both the
    fast and the slow window exceed `burn_threshold` (the classic
    multi-window rule: fast catches the regression quickly, slow keeps
    one blip from paging) and RESOLVES once the fast window recovers."""

    kind = param("availability", type=str,
                 choices={"availability", "ratio", "latency"})
    # latency: the histogram series name (registry path, e.g.
    # "/serving/select_latency_seconds").
    sensor = param("", type=str)
    # availability/ratio: counter series names.
    good_sensor = param("", type=str)
    bad_sensor = param("", type=str)
    # Tag filter (subset match): {"pool": "prod"} evaluates one pool's
    # series; empty sums every tagged series of the sensor.
    tags = param(default_factory=dict, type=dict)
    objective = param(0.99, type=float, ge=0.0, le=1.0)
    bound_ms = param(0.0, type=float, ge=0.0)
    fast_window = param(300.0, type=float, ge=0.0)
    slow_window = param(3600.0, type=float, ge=0.0)
    burn_threshold = param(10.0, type=float, ge=0.0)

    def postprocess(self):
        if self.kind == "latency":
            if not self.sensor or self.bound_ms <= 0:
                raise YtError(
                    "latency SLO requires `sensor` (a histogram) and a "
                    "positive `bound_ms`", code=EErrorCode.InvalidConfig)
        elif not self.good_sensor or not self.bad_sensor:
            raise YtError(
                f"{self.kind} SLO requires `good_sensor` and "
                f"`bad_sensor` counters", code=EErrorCode.InvalidConfig)


class TelemetryConfig(YsonStruct):
    """Cluster telemetry plane knobs (utils/profiling.MetricsHistory +
    utils/slo.SloTracker + query/accounting.ResourceAccountant):

    - `sample_period`: the sampler thread snapshots every registered
      sensor this often into the history rings (0 disables sampling;
      tests drive `sample_once()` manually with synthetic timestamps).
    - `fine_capacity`/`coarse_every`/`coarse_capacity`: ring tiers.
      Defaults hold 1h at 10s resolution plus 24h at 5min resolution
      (10s x 360 + 5min x 288) in bounded memory per sensor.
    - `slos`: name -> SloConfig, evaluated after every sample.
    - `mesh_telemetry`: arm the in-program mesh telemetry block (ISSUE
      20) — per-shard row counts, transfer matrices, quota headroom —
      stacked onto the whole-plan final transfer (same single host
      sync).  The flag folds into every SPMD cache key.
    - `mesh_max_imbalance`: max-shard/mean-shard output-row ratio above
      which an execution counts as SKEWED for the `/query/mesh/*`
      balanced-vs-skewed counters (the MESH_SKEW_SLO denominator)."""

    enabled = param(True, type=bool)
    sample_period = param(10.0, type=float, ge=0.0)
    fine_capacity = param(360, type=int, ge=1)
    # Every Nth fine sample is folded into the coarse ring.
    coarse_every = param(30, type=int, ge=1)
    coarse_capacity = param(288, type=int, ge=1)
    slos = param(default_factory=dict, type=dict)
    mesh_telemetry = param(True, type=bool)
    mesh_max_imbalance = param(4.0, type=float, ge=1.0)

    def postprocess(self):
        parsed = {}
        for name, spec in (self.slos or {}).items():
            if isinstance(name, bytes):
                name = name.decode("utf-8")
            if isinstance(spec, SloConfig):
                parsed[name] = spec
            elif isinstance(spec, dict):
                parsed[name] = SloConfig.from_dict(spec,
                                                   path=f"slos/{name}")
            else:
                raise YtError(f"SLO {name!r}: expected map, got {spec!r}",
                              code=EErrorCode.InvalidConfig)
        self.slos = parsed

    def to_dict(self) -> dict:
        out = super().to_dict()
        out["slos"] = {name: slo.to_dict()
                       for name, slo in self.slos.items()}
        return out


_TELEMETRY_CONFIG: "Optional[TelemetryConfig]" = None


def telemetry_config() -> TelemetryConfig:
    global _TELEMETRY_CONFIG
    if _TELEMETRY_CONFIG is None:
        _TELEMETRY_CONFIG = TelemetryConfig()
    return _TELEMETRY_CONFIG


def set_telemetry_config(config: "Optional[TelemetryConfig]") -> None:
    """Install a process-wide telemetry config (None restores defaults);
    rebuilds the global history rings + SLO tracker to the new shape."""
    global _TELEMETRY_CONFIG
    _TELEMETRY_CONFIG = config
    from ytsaurus_tpu.utils import profiling, slo
    # Tracker first: configure_telemetry restarts a running sampler,
    # and the restarted thread must hook the NEW tracker's evaluate.
    slo.configure(config)
    profiling.configure_telemetry(config)


class WorkloadConfig(YsonStruct):
    """Workload recorder + compilation observatory knobs (ISSUE 8,
    query/workload.py + query/engine/evaluator.py):

    - `enabled`: master switch for the workload recorder; False turns
      every observe site into one config read.
    - `sample_rate`: probability an admitted query folds a record into
      the workload log (1.0 = record everything; high-rate fleets dial
      this down — the log is a statistical capture, not an audit log).
    - `capacity`: bounded in-memory record ring (what `/workload` and
      `yt workload capture` serve).
    - `fingerprint_capacity`: bounded per-fingerprint roll-up map; new
      fingerprints past the cap count as dropped instead of growing it.
    - `log_dir`: when set, sampled records ALSO append to a rotated
      on-disk JSONL log (`workload.jsonl`, header line carries the
      schema version) bounded by `rotate_bytes` x `max_files`.
    - `lookup_keys_per_record`: lookup records retain at most this many
      key tuples (enough to replay; bounds record size).
    - `capture_artifacts`: the compilation observatory captures each
      compiled executable's HLO text + XLA `cost_analysis()`
      FLOPs/bytes (bounded by `artifact_capacity`, HLO truncated to
      `hlo_max_chars`).  Off by default: artifacts are debugging
      payloads, not steady-state telemetry.
    - `compile_cache_capacity`: LRU bound on the evaluator's compiled
      program cache (0 = unbounded, the historical behavior).  With a
      bound, evictions are counted per fingerprint and a re-miss on an
      evicted key is tagged cause=eviction."""

    enabled = param(True, type=bool)
    sample_rate = param(1.0, type=float, ge=0.0, le=1.0)
    capacity = param(4096, type=int, ge=1)
    fingerprint_capacity = param(1024, type=int, ge=1)
    log_dir = param(None, type=str)
    rotate_bytes = param(4 << 20, type=int, ge=4096)
    max_files = param(4, type=int, ge=1)
    lookup_keys_per_record = param(16, type=int, ge=0)
    capture_artifacts = param(False, type=bool)
    artifact_capacity = param(64, type=int, ge=1)
    hlo_max_chars = param(20_000, type=int, ge=0)
    compile_cache_capacity = param(0, type=int, ge=0)


_WORKLOAD_CONFIG: "Optional[WorkloadConfig]" = None


def workload_config() -> WorkloadConfig:
    global _WORKLOAD_CONFIG
    if _WORKLOAD_CONFIG is None:
        _WORKLOAD_CONFIG = WorkloadConfig()
    return _WORKLOAD_CONFIG


def set_workload_config(config: "Optional[WorkloadConfig]") -> None:
    """Install a process-wide workload config (None restores defaults);
    rebinds the global workload log to the new shape."""
    global _WORKLOAD_CONFIG
    _WORKLOAD_CONFIG = config
    from ytsaurus_tpu.query import workload
    workload.configure(config)


class CompileConfig(YsonStruct):
    """Compile-once serving knobs (ISSUE 10, query/parameterize.py +
    query/engine/evaluator.py + query/engine/aot_cache.py):

    - `parameterize`: auto-parameterize plans — the evaluator (and the
      distributed SPMD evaluator) key their compiled-program caches on
      the SHAPE fingerprint (hoistable literal values and bucketed
      LIMIT/OFFSET collapsed; see ir.fingerprint(omit_values=True)),
      and the lowering feeds literals/limits to the program as runtime
      bindings, so `WHERE user_id = ?` traffic compiles ONCE per shape
      instead of once per constant.  Off restores the historical
      per-constant fingerprints (bench A/B leg).
    - `disk_cache_dir`: when set, AOT-compiled executables ALSO persist
      to this directory (jax serialize_executable of lower().compile()
      products), keyed (fingerprint, capacity bucket, binding shapes,
      backend, jax version).  A fresh process warm-starts from disk
      instead of cold-compiling the fleet after a rolling restart.
      None (default) disables the disk tier.
    - `disk_cache_capacity_bytes`: size cap on the artifact directory;
      the writer evicts oldest-mtime files past it (loads touch mtime,
      so eviction is LRU-ish).
    - `disk_cache_min_compile_seconds`: programs that compiled faster
      than this are not worth a disk round-trip; 0 persists everything
      (tests).
    - `whole_plan`: lower fusable distributed plans as ONE
      jit(shard_map) program (parallel/whole_plan.py, ISSUE 12) — the
      top rung of the degradation ladder.  Off forces the stitched
      rungs (bench A/B leg, escape hatch).
    - `whole_plan_headroom`: multiplier applied when an OVERFLOW
      escalates a fused program's static exchange/expansion quota (the
      estimate has proven short, so the re-run takes extra slack).
      First guesses and settled steady-state quotas round the
      estimate/measured demand to pow2 WITHOUT it — the rounding is
      the slack, and doubling accurate capacities taxes every
      downstream stage."""

    parameterize = param(True, type=bool)
    disk_cache_dir = param(None, type=str)
    disk_cache_capacity_bytes = param(256 << 20, type=int, ge=0)
    disk_cache_min_compile_seconds = param(0.0, type=float, ge=0.0)
    whole_plan = param(True, type=bool)
    whole_plan_headroom = param(1.5, type=float, ge=1.0)
    # Cost-based join planning (query/planner.py, ISSUE 14): reorder
    # multiway equi-joins by estimated cardinality (chunk-stats NDV
    # sketches), choose broadcast-vs-partition per side, and push
    # semi-join key ranges from selective sides into the scan stage.
    # Off restores the declared left-to-right cascade (bench A/B leg,
    # escape hatch).  `broadcast_join_rows`: foreign sides at or below
    # this row count replicate to every device instead of riding the
    # co-partition exchange (they must also prove unique join keys).
    cost_join_planner = param(True, type=bool)
    broadcast_join_rows = param(65536, type=int, ge=0)
    # Encoded-plane kernel execution (ISSUE 19, query/engine/expr.py +
    # interp.py): string predicates against literals compare the column's
    # dict CODES with a host-bound code — no merged-vocab remap tables,
    # no per-row gathers.  Off restores the decoded remap-table path
    # (the bit-identity oracle the dual-check corpus runs both ways).
    encoded_predicates = param(True, type=bool)
    # Buffer donation (ISSUE 19, evaluator/joins/distributed dispatch):
    # OWNED chunk-sized temporaries (join-cascade intermediates, phase-1
    # join products) are donated to their consuming program so XLA can
    # reuse the buffers in place.  Persistent table chunks are NEVER
    # donated.  Off = copying fallback (escape hatch + A/B leg).
    donate_buffers = param(True, type=bool)


_COMPILE_CONFIG: "Optional[CompileConfig]" = None


def compile_config() -> CompileConfig:
    global _COMPILE_CONFIG
    if _COMPILE_CONFIG is None:
        _COMPILE_CONFIG = CompileConfig()
    return _COMPILE_CONFIG


def set_compile_config(config: "Optional[CompileConfig]") -> None:
    """Install a process-wide compile config (None restores defaults);
    rebinds the global disk compile-artifact cache to the new shape."""
    global _COMPILE_CONFIG
    _COMPILE_CONFIG = config
    from ytsaurus_tpu.query.engine import aot_cache
    aot_cache.configure(config)


class TieringConfig(YsonStruct):
    """Adaptive tiered execution knobs (ISSUE 18, query/engine/interp.py +
    query/engine/evaluator.py + query/engine/prewarm.py):

    - `enabled`: master switch for the interpreter tier.  Off (the
      default — rollout gate, same convention as `disk_cache_dir`)
      restores the pre-tiering behavior exactly: every cold fingerprint
      compiles inline.  On, a fingerprint that misses ALL THREE AOT
      rungs (memory LRU, disk, cluster artifact store) is served by the
      no-compile numpy interpreter immediately when its plan shape is
      inside the interpreter's declared coverage, while the background
      compiler promotes it off-thread.
    - `hot_threshold`: interpreted executions of one fingerprint before
      the background compiler is asked to promote it.  1 promotes on
      first sight (bench/prewarm-adjacent workloads); higher values
      keep one-shot ad-hoc shapes from burning compile capacity.
    - `queue_depth`: bound on the background-compiler work queue.
      Enqueues past it are dropped (the fingerprint re-arms on a later
      interpreted run) — promotion is an optimization, never backlog.
    - `prewarm_capture`: path to an exported workload capture (JSONL,
      `yt workload capture` shape); daemon startup replays it through
      compile-only prewarm so a restarted daemon joins hot.  None skips
      the startup prewarm."""

    enabled = param(False, type=bool)
    hot_threshold = param(2, type=int, ge=1)
    queue_depth = param(64, type=int, ge=1)
    prewarm_capture = param(None, type=str)


_TIERING_CONFIG: "Optional[TieringConfig]" = None


def tiering_config() -> TieringConfig:
    global _TIERING_CONFIG
    if _TIERING_CONFIG is None:
        _TIERING_CONFIG = TieringConfig()
    return _TIERING_CONFIG


def set_tiering_config(config: "Optional[TieringConfig]") -> None:
    """Install a process-wide tiering config (None restores defaults)."""
    global _TIERING_CONFIG
    _TIERING_CONFIG = config


class ViewsConfig(YsonStruct):
    """Continuous-query (materialized view) plane knobs (ISSUE 13,
    query/views.py + server/view_daemon.py):

    - `enable`: master switch for the view daemon's refresh loop — off
      pauses EVERY view (dynamic-config brown-out lever; the committed
      offset cursors make resume lossless).
    - `poll_interval`: daemon sleep between passes over the registry
      when every view is drained.
    - `default_batch_rows`: micro-batch size for views created without
      an explicit one.  Batches pad to the pow2 capacity bucket, so the
      steady-state loop replays one compiled program per view.
    - `max_batches_per_pass`: per-view cap on batches drained in one
      daemon pass (fairness across views; 0 = drain to the head).
    - `lag_slo_rows`: the freshness-lag objective — each refresh pass
      votes the per-view `/views/lag_ok` vs `/views/lag_breach`
      counters against it, the SLI pair the view-lag burn-rate SLO
      (`view_lag_slo()`) evaluates over the history rings.
    - `paused`: view names force-paused by dynamic config (additive to
      per-view `yt view pause` registry state)."""

    enable = param(True, type=bool)
    poll_interval = param(0.05, type=float, ge=0.0)
    default_batch_rows = param(1024, type=int, ge=1)
    max_batches_per_pass = param(64, type=int, ge=0)
    lag_slo_rows = param(65536, type=int, ge=0)
    paused = param(default_factory=list, type=list)


_VIEWS_CONFIG: "Optional[ViewsConfig]" = None


def views_config() -> ViewsConfig:
    global _VIEWS_CONFIG
    if _VIEWS_CONFIG is None:
        _VIEWS_CONFIG = ViewsConfig()
    return _VIEWS_CONFIG


def set_views_config(config: "Optional[ViewsConfig]") -> None:
    """Install a process-wide views config (None restores defaults)."""
    global _VIEWS_CONFIG
    _VIEWS_CONFIG = config


def view_lag_slo(view: "Optional[str]" = None,
                 objective: float = 0.99,
                 burn_threshold: float = 10.0,
                 fast_window: float = 300.0,
                 slow_window: float = 3600.0) -> SloConfig:
    """The view-freshness SLO spec (ISSUE 13 satellite): a ratio SLI
    over the per-view lag vote counters — `objective` of refresh passes
    must meet the configured `lag_slo_rows` freshness bound.  Evaluated
    by utils/slo.SloTracker over the telemetry history rings with the
    standard fast+slow burn-rate windows; `view=None` sums every view's
    series (the fleet-wide objective)."""
    return SloConfig(
        kind="ratio", good_sensor="/views/lag_ok",
        bad_sensor="/views/lag_breach",
        tags={"view": view} if view else {},
        objective=objective, burn_threshold=burn_threshold,
        fast_window=fast_window, slow_window=slow_window)


class FailpointsConfig(YsonStruct):
    """Deterministic fault-injection schedule (utils/failpoints.py):
    `spec` uses the YT_FAILPOINTS syntax, `seed` fixes p-based rolls.
    Applied with `failpoints.configure(cfg)`; spawned daemons arm from
    the YT_FAILPOINTS / YT_FAILPOINTS_SEED environment instead."""

    spec = param("", type=str)
    seed = param(0, type=int)


class SanitizerConfig(YsonStruct):
    """Runtime concurrency sanitizer (utils/sanitizers.py): the
    instrumented-lock layer recording held-lock sets, acquisition-order
    edges, lock-order inversions, hold-budget violations, and blocking
    operations under hot-path locks.  Disabled by default — the
    registration helper then hands out PLAIN `threading.Lock`s (zero
    wrappers, zero per-acquire cost; tests/test_sanitizer.py asserts
    the type).  Enablement applies to locks
    created AFTER `sanitizers.configure(cfg)` runs (or set
    YT_TPU_SANITIZE=1 before the process constructs its daemons, the
    tests/conftest pattern)."""

    enabled = param(False, type=bool)
    # A registered hot lock held longer than this is a violation
    # (counted + bounded-reported, never fatal: the serving plane keeps
    # serving while operators read /sanitizer).
    hold_budget_seconds = param(0.25, type=float, ge=0.0)


def sanitizer_config() -> SanitizerConfig:
    return _sanitizer_config if _sanitizer_config is not None \
        else SanitizerConfig()


def set_sanitizer_config(config: "Optional[SanitizerConfig]") -> None:
    """Install + APPLY a sanitizer config (None restores the defaults —
    disabled — matching the other setters' convention; the env gate
    YT_TPU_SANITIZE is independent and wins when set)."""
    global _sanitizer_config
    _sanitizer_config = config
    from ytsaurus_tpu.utils import sanitizers
    sanitizers.configure(config if config is not None
                         else SanitizerConfig())


_sanitizer_config: "Optional[SanitizerConfig]" = None


class RpcConfig(YsonStruct):
    bind_host = param("127.0.0.1", type=str)
    port = param(0, type=int, ge=0, le=65535)
    max_workers = param(16, type=int, ge=1)
    call_timeout = param(30.0, type=float, ge=0.0)
    retry_attempts = param(2, type=int, ge=1)
    retry_backoff = param(0.1, type=float, ge=0.0)


class ChunkStoreConfig(YsonStruct):
    cache_capacity_bytes = param(1 << 30, type=int, ge=0)
    replication_factor = param(2, type=int, ge=1)
    erasure_codec = param("none", type=str,
                          choices={"none", "rs_6_3", "rs_3_2"})


class MasterConfig(YsonStruct):
    snapshot_every = param(1024, type=int, ge=1)
    journal_nodes = param(2, type=int, ge=0)
    bootstrap_timeout = param(60.0, type=float, ge=0.0)


class SchedulerConfig(YsonStruct):
    fair_share_update_period = param(0.1, type=float, ge=0.0)
    max_running_jobs = param(8, type=int, ge=1)
    speculative_after = param(5.0, type=float, ge=0.0)


class ServingConfig(YsonStruct):
    """Query serving plane knobs (query/serving.py QueryGateway):
    admission control (weighted per-pool concurrency slots over a bounded
    wait queue), deadline propagation, and continuous micro-batching of
    lookups.  Ref shape: the reference query service's in-flight window
    + lookup sessions (query_agent/query_service.cpp)."""

    enabled = param(True, type=bool)
    # Total concurrent query slots, shared by every pool under fair-share
    # admission (ISSUE 17): min-share guarantees first, then weight-
    # proportional water filling capped by live demand — the scalar
    # collapse of vector HDRF (operations/fair_share.py).
    slots = param(16, type=int, ge=1)
    # pool name -> weight; pools not listed here use default_pool's slots.
    pools = param(default_factory=lambda: {"default": 1.0}, type=dict)
    default_pool = param("default", type=str)
    # pool name -> guaranteed share of `slots` in [0, 1] (vector-HDRF
    # min_share_ratio): honored before weight-proportional filling, so
    # an idle pool's guarantee survives a neighbor's storm.
    min_shares = param(default_factory=dict, type=dict)
    # pool name -> hard cap on concurrently running queries (fair share
    # never raises a pool past its cap).
    pool_limits = param(default_factory=dict, type=dict)
    # Brown-out ladder (ISSUE 17): under sustained overload reads degrade
    # explicitly — rung 0 full execution, rung 1 bounded-staleness
    # snapshot-cache reads, rung 2 reject-with-retry_after.  The signal
    # is estimated queue drain time: total_waiting * hold_ewma / slots
    # (queue depth AND observed drain rate in one number).  Rungs step
    # UP immediately and step DOWN one at a time, only after
    # `brownout_min_dwell_seconds` in the rung with the signal below
    # `threshold * brownout_hysteresis` — no flapping at the boundary.
    brownout_enabled = param(True, type=bool)
    brownout_rung1_seconds = param(0.5, type=float, ge=0.0)
    brownout_rung2_seconds = param(2.0, type=float, ge=0.0)
    brownout_hysteresis = param(0.5, type=float, ge=0.0, le=1.0)
    brownout_min_dwell_seconds = param(1.0, type=float, ge=0.0)
    # pool name -> max staleness (seconds) a rung-1 degraded read may
    # serve from the tablet snapshot cache; pools absent here use
    # `default_staleness_seconds`.  0 opts the pool out of degradation
    # (its reads stay full-execution until rung 2 sheds them).
    staleness_bounds = param(default_factory=dict, type=dict)
    default_staleness_seconds = param(5.0, type=float, ge=0.0)
    # Admitted-but-waiting requests per pool; overflow => ThrottledError.
    max_queue = param(128, type=int, ge=0)
    # Deadline applied when the caller passes none (0 = no deadline).
    default_timeout = param(30.0, type=float, ge=0.0)
    # Lookup micro-batching: requests against one (table, timestamp)
    # coalesce inside this window, up to max_batch_size keys.
    flush_window_ms = param(2.0, type=float, ge=0.0)
    max_batch_size = param(1024, type=int, ge=1)
    # Pow2 floor for the batched chunk probe's key (needle) arrays
    # (tablet._pad_needles): bounds the spectrum of gather shapes so a
    # shape-keyed compiled-gather cache stays bounded.
    min_bucket = param(8, type=int, ge=1)
    # Parallel per-tablet fan-out width for one batched read.
    max_tablet_fanout = param(8, type=int, ge=1)

    def postprocess(self):
        # YSON-loaded maps may carry bytes keys; pool names are strings.
        self.pools = {
            (k.decode("utf-8") if isinstance(k, bytes) else k): v
            for k, v in (self.pools or {}).items()}
        for name, weight in self.pools.items():
            if isinstance(weight, bool) or \
                    not isinstance(weight, (int, float)) or weight < 0:
                raise YtError(
                    f"Serving pool {name!r}: weight must be a "
                    f"non-negative number, got {weight!r}",
                    code=EErrorCode.InvalidConfig)
        if self.default_pool not in self.pools:
            raise YtError(
                f"Serving default_pool {self.default_pool!r} is not in "
                f"pools {sorted(self.pools)!r}",
                code=EErrorCode.InvalidConfig)
        self.min_shares = {
            (k.decode("utf-8") if isinstance(k, bytes) else k): v
            for k, v in (self.min_shares or {}).items()}
        for name, ratio in self.min_shares.items():
            if isinstance(ratio, bool) or \
                    not isinstance(ratio, (int, float)) or \
                    not 0.0 <= ratio <= 1.0:
                raise YtError(
                    f"Serving pool {name!r}: min_share must be in "
                    f"[0, 1], got {ratio!r}", code=EErrorCode.InvalidConfig)
        if sum(self.min_shares.values()) > 1.0 + 1e-9:
            raise YtError(
                f"Serving min_shares sum to "
                f"{sum(self.min_shares.values()):.3f} > 1.0 — the "
                f"guarantees are not satisfiable",
                code=EErrorCode.InvalidConfig)
        self.pool_limits = {
            (k.decode("utf-8") if isinstance(k, bytes) else k): v
            for k, v in (self.pool_limits or {}).items()}
        for name, limit in self.pool_limits.items():
            if isinstance(limit, bool) or not isinstance(limit, int) \
                    or limit < 1:
                raise YtError(
                    f"Serving pool {name!r}: pool_limit must be a "
                    f"positive int, got {limit!r}",
                    code=EErrorCode.InvalidConfig)
        self.staleness_bounds = {
            (k.decode("utf-8") if isinstance(k, bytes) else k): v
            for k, v in (self.staleness_bounds or {}).items()}
        for name, bound in self.staleness_bounds.items():
            if isinstance(bound, bool) or \
                    not isinstance(bound, (int, float)) or bound < 0:
                raise YtError(
                    f"Serving pool {name!r}: staleness bound must be a "
                    f"non-negative number, got {bound!r}",
                    code=EErrorCode.InvalidConfig)
        if self.brownout_rung2_seconds < self.brownout_rung1_seconds:
            raise YtError(
                "Serving brownout_rung2_seconds must be >= "
                "brownout_rung1_seconds",
                code=EErrorCode.InvalidConfig)


class DaemonConfig(YsonStruct):
    """Top-level daemon config (`--config file.yson`)."""

    role = param("primary", type=str, choices={"primary", "node", "proxy"})
    root = param(None, type=str)
    rpc = param(type=RpcConfig)
    chunk_store = param(type=ChunkStoreConfig)
    master = param(type=MasterConfig)
    scheduler = param(type=SchedulerConfig)
    serving = param(type=ServingConfig)
    tablet = param(type=TabletConfig)
    tracing = param(type=TracingConfig)
    telemetry = param(type=TelemetryConfig)
    workload = param(type=WorkloadConfig)
    compile = param(type=CompileConfig)
    tiering = param(type=TieringConfig)
    sanitizer = param(type=SanitizerConfig)

    def postprocess(self):
        if self.role == "node" and self.chunk_store.replication_factor < 1:
            raise YtError("node role requires replication_factor >= 1",
                          code=EErrorCode.InvalidConfig)

    @classmethod
    def load(cls, path: str) -> "DaemonConfig":
        from ytsaurus_tpu import yson
        with open(path, "rb") as f:
            return cls.from_dict(yson.loads(f.read()))


# ---------------------------------------------------------------------------
# Dynamic config manager
# ---------------------------------------------------------------------------

class DynamicConfigManager:
    """Polls a Cypress document for config patches and applies them.

    Ref: library/dynamic_config/dynamic_config_manager.h:23 — the manager
    periodically fetches `//sys/<component>/@config`-style state, validates
    the merged config, fires subscriber callbacks on change, and keeps
    serving the last good config when a bad patch lands (the error is
    logged + exported via `last_error`).
    """

    def __init__(self, fetch: Callable[[], Optional[dict]],
                 base_config: YsonStruct, period: float = 1.0):
        self._fetch = fetch
        self._base = base_config
        self._period = period
        self._lock = threading.Lock()
        self._current = base_config
        self._last_patch: Optional[dict] = None
        self.last_error: Optional[YtError] = None
        self.update_count = 0
        self._subscribers: list[Callable[[YsonStruct], None]] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def config(self) -> YsonStruct:
        with self._lock:
            return self._current

    def subscribe(self, callback: Callable[[YsonStruct], None]) -> None:
        self._subscribers.append(callback)

    def poll_once(self) -> bool:
        """One fetch+merge+apply cycle; True if the config changed."""
        try:
            patch = self._fetch()
        except Exception as exc:   # noqa: BLE001 — fetch is an RPC boundary;
            # the poll loop must survive transport/teardown errors.
            self.last_error = exc if isinstance(exc, YtError) else \
                YtError(f"dynamic config fetch failed: {exc!r}")
            return False
        if patch == self._last_patch:
            return False
        try:
            new_config = self._base.merge(patch)
        except YtError as exc:
            # Keep the last good config; surface the failure.
            self.last_error = exc
            logger.warning("rejecting dynamic config patch: %s", exc)
            return False
        self._last_patch = patch
        self.last_error = None
        with self._lock:
            if new_config == self._current:
                return False
            self._current = new_config
        self.update_count += 1
        for callback in self._subscribers:
            try:
                callback(new_config)
            except Exception as exc:   # noqa: BLE001 — subscriber boundary
                logger.error("dynamic config subscriber failed: %r", exc)
        return True

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="dynamic-config")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self._period):
            self.poll_once()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
