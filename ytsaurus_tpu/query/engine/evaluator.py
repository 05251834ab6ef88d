"""The query evaluator: plan + chunks → result chunk, with a compile cache.

Analog of TEvaluator::Run (library/query/engine/evaluator.cpp:40-120): looks
up / populates a compiled-program cache keyed by (plan fingerprint, capacity
bucket, binding shapes) — the XLA counterpart of the reference's LLVM image
cache keyed by llvm::FoldingSet fingerprint (engine_api/cg_cache.h) — then
runs the program over the chunk's planes.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Mapping, Optional, Sequence

import jax
import numpy as np

from ytsaurus_tpu.chunks.columnar import (
    Column,
    ColumnarChunk,
    concat_chunks,
    project_chunk,
)
from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.parameterize import plan_fingerprint
from ytsaurus_tpu.query.engine.joins import execute_join
from ytsaurus_tpu.query.engine.lowering import prepare
from ytsaurus_tpu.query.statistics import QueryStatistics
from ytsaurus_tpu.schema import EValueType, TableSchema
from ytsaurus_tpu.utils.profiling import PoolSensorCache, Profiler
from ytsaurus_tpu.utils import sanitizers
from ytsaurus_tpu.utils.tracing import child_span

# Process-wide compile-cache counters, tagged by the admitted query's
# pool (identity rides the CancellationToken): the steady-state
# compile-cache hit-rate SLO (ROADMAP item 1's acceptance gate, a
# TIME-SERIES claim) reads these from the telemetry history rings.
# The compilation observatory's per-fingerprint totals reconcile
# EXACTLY with these (same dispatch event increments both; the
# reconciliation is test-enforced).
_cache_counters = PoolSensorCache("/query/compile_cache",
                                  ("hits", "misses"))
_evictions_counter = Profiler("/query/compile_cache").counter("evictions")

# Execution-tier telemetry (ISSUE 18): which tier served each dispatch
# (interpreted vs compiled), background promotions, the promotion
# queue's depth, and prewarm compiles.  Deliberately a SEPARATE sensor
# family from /query/compile_cache — tier traffic must never perturb
# the hit/miss counters the compile-storm SLO and the observatory
# reconciliation are built on.
_tier_counters = PoolSensorCache("/query/tiers",
                                 ("interpreted", "compiled"))
_tiers_profiler = Profiler("/query/tiers")
_promotions_counter = _tiers_profiler.counter("promotions")
_prewarm_counter = _tiers_profiler.counter("prewarm_compiles")
_tier_queue_gauge = _tiers_profiler.gauge("queue_depth")

# Kernel-execution telemetry (ISSUE 19): dispatches whose string
# predicates ran on encoded dictionary planes vs the decoded fallback,
# and dispatches that armed buffer donation.
_kernel_profiler = Profiler("/query/kernels")
_encoded_scans_counter = _kernel_profiler.counter("encoded_scans")
_decoded_fallbacks_counter = _kernel_profiler.counter("decoded_fallbacks")
_donated_buffers_counter = _kernel_profiler.counter("donated_buffers")


def _flat_notes(structure_key) -> "set[str]":
    """Leading tags of every bind-notebook note tuple nested anywhere in
    a structure key (("strlit", op, digest) -> "strlit")."""
    out: set[str] = set()

    def walk(node):
        if isinstance(node, tuple):
            if node and isinstance(node[0], str):
                out.add(node[0])
            for item in node:
                walk(item)

    walk(structure_key)
    return out

# Buffer donation (ISSUE 19): XLA reuses donated input buffers for
# outputs of matching shape, halving peak residency for chunk-sized
# temporaries.  CPU backends ignore donation (it is inert there) but
# warn per call — suppress exactly that message so the armed path stays
# quiet on the CPU bench/test floor.
import warnings as _warnings

_warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def _jit_run(run, donate_columns: bool = False):
    """jit a prepared `run` with ISSUE 19 buffer donation.

    `row_valid` (argnum 1) is always donatable: `chunk.row_valid` is a
    property that builds a fresh iota-compare plane per access, so every
    dispatch owns its copy and nothing reads it after the call.  The
    column planes (argnum 0) are donated only when the caller owns the
    chunk — a join-cascade intermediate built by this very dispatch —
    never for persistent table chunks (the compile-cache key carries the
    donation mode so the two executables cannot alias)."""
    from ytsaurus_tpu.config import compile_config
    if not compile_config().donate_buffers:
        return jax.jit(run)
    return jax.jit(run, donate_argnums=(0, 1) if donate_columns else (1,))


class CompileObservatory:
    """Per-fingerprint compile telemetry (ISSUE 8 tentpole, piece b).

    Every evaluator dispatch folds here: compile count + cumulative
    compile seconds per plan fingerprint (the "compile burn" `/compile`
    and `yt compile-cache top` rank by — Flare's adaptive-compilation
    feedback signal, arxiv 1703.08219), the shape-spectrum cardinality
    (distinct (capacity, binding-shape) programs one fingerprint
    compiled — an unbounded spectrum IS the recompilation pathology),
    evictions, and the LAST MISS CAUSE:

      new_fingerprint   this plan shape never compiled before
      new_shape         known shape, but a capacity bucket / binding
                        shape it never met (shape-spectrum growth)
      eviction          the exact program existed and was LRU-evicted
                        (the cache is too small for the working set)

    Optionally captures each compiled executable's XLA artifacts (HLO
    text + cost_analysis() FLOPs/bytes) behind
    `WorkloadConfig.capture_artifacts` — bounded, for debugging a hot
    fingerprint, not steady-state telemetry."""

    SHAPE_SET_CAP = 512

    def __init__(self):
        # guards: _fps, _artifacts, _evicted, hits_n, misses_n, evictions_n
        self._lock = sanitizers.register_lock(
            "evaluator.CompileObservatory._lock")
        self._fps: dict[str, dict] = {}
        self._artifacts: deque = deque(maxlen=64)
        # Bounded memory of evicted program keys: a re-miss on one is
        # cause=eviction, not cause=new_shape.
        self._evicted: "OrderedDict[tuple, None]" = OrderedDict()
        self.hits_n = 0
        self.misses_n = 0
        self.evictions_n = 0
        self.disk_hits_n = 0
        self.background_n = 0

    def _entry_locked(self, fp: str) -> dict:
        entry = self._fps.get(fp)
        if entry is None:
            entry = self._fps[fp] = {
                "compiles": 0, "hits": 0, "disk_hits": 0,
                "compile_seconds": 0.0,
                "shapes": set(), "shape_count": 0, "evictions": 0,
                "last_miss_cause": None, "last_compile_at": 0.0,
                "background_compiles": 0, "background_seconds": 0.0,
            }
        return entry

    def classify_miss(self, fp: str, key: tuple) -> str:
        with self._lock:
            if key in self._evicted:
                return "eviction"
            if fp in self._fps:
                return "new_shape"
            return "new_fingerprint"

    def observe_hit(self, fp: str) -> None:
        with self._lock:
            self.hits_n += 1
            self._entry_locked(fp)["hits"] += 1

    def observe_miss(self, fp: str, key: tuple, cause: str,
                     seconds: float) -> None:
        shape_sig = key[1:]
        with self._lock:
            self.misses_n += 1
            entry = self._entry_locked(fp)
            if cause == "disk_hit":
                # A memory miss served by the persistent tier: no fresh
                # compile burn — count it apart so `compiles` stays the
                # honest "programs actually built here" number.
                self.disk_hits_n += 1
                entry["disk_hits"] += 1
            else:
                entry["compiles"] += 1
                entry["compile_seconds"] += seconds
            entry["last_miss_cause"] = cause
            entry["last_compile_at"] = time.time()
            shapes = entry["shapes"]
            if shape_sig not in shapes:
                entry["shape_count"] += 1
                if len(shapes) < self.SHAPE_SET_CAP:
                    shapes.add(shape_sig)
            self._evicted.pop(key, None)

    def observe_background(self, fp: str, key: tuple,
                           seconds: float) -> None:
        """A DELIBERATE off-the-query-path compile (background
        promotion or capture-driven prewarm, ISSUE 18).  Kept in
        SEPARATE books from observe_miss: these are warm-up, not
        misses — they must not move the `/query/compile_cache`
        hit/miss counters the compile-storm SLO burns against, and the
        sensor<->observatory reconciliation (test-enforced) only holds
        if both keep counting the same dispatch events."""
        shape_sig = key[1:]
        with self._lock:
            self.background_n += 1
            entry = self._entry_locked(fp)
            entry["background_compiles"] += 1
            entry["background_seconds"] += seconds
            entry["last_miss_cause"] = "background_promotion"
            entry["last_compile_at"] = time.time()
            shapes = entry["shapes"]
            if shape_sig not in shapes:
                entry["shape_count"] += 1
                if len(shapes) < self.SHAPE_SET_CAP:
                    shapes.add(shape_sig)
            self._evicted.pop(key, None)

    def observe_eviction(self, key: tuple) -> None:
        with self._lock:
            self.evictions_n += 1
            if key[0] in self._fps:
                self._fps[key[0]]["evictions"] += 1
            self._evicted[key] = None
            while len(self._evicted) > 4096:
                self._evicted.popitem(last=False)

    def capture_artifact(self, fp: str, key: tuple, hlo: str,
                         cost: Optional[dict],
                         seconds: float) -> None:
        from ytsaurus_tpu.config import workload_config
        cfg = workload_config()
        cost = cost or {}
        artifact = {
            "fingerprint": fp,
            "capacity": key[1],
            "binding_shapes": repr(key[2]),
            "compile_seconds": round(seconds, 6),
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed",
                                       cost.get("bytes_accessed")),
            "hlo": hlo[:cfg.hlo_max_chars] if cfg.hlo_max_chars else "",
            "captured_at": time.time(),
        }
        with self._lock:
            if self._artifacts.maxlen != cfg.artifact_capacity:
                self._artifacts = deque(self._artifacts,
                                        maxlen=cfg.artifact_capacity)
            self._artifacts.append(artifact)

    # -- views -----------------------------------------------------------------

    def totals(self) -> dict:
        with self._lock:
            return {"hits": self.hits_n, "misses": self.misses_n,
                    "evictions": self.evictions_n,
                    "disk_hits": self.disk_hits_n,
                    "background_compiles": self.background_n,
                    "fingerprints": len(self._fps)}

    def top(self, n: int = 20,
            by: str = "compile_seconds") -> list[dict]:
        """Fingerprints ranked by compile burn (or any numeric field)."""
        with self._lock:
            rows = [{"fingerprint": fp,
                     **{k: v for k, v in entry.items() if k != "shapes"}}
                    for fp, entry in self._fps.items()]
        for row in rows:
            row["compile_seconds"] = round(row["compile_seconds"], 6)
        rows.sort(key=lambda r: (-float(r.get(by) or 0.0),
                                 r["fingerprint"]))
        return rows[:n] if n else rows

    def artifacts(self) -> list[dict]:
        with self._lock:
            return list(self._artifacts)

    def snapshot(self, top: int = 50) -> dict:
        from ytsaurus_tpu.query.engine.aot_cache import get_disk_cache
        disk = get_disk_cache()
        return {"totals": self.totals(),
                "fingerprints": self.top(top),
                # The persistent artifact tier's view (ISSUE 10): None
                # when the disk cache is disabled.
                "disk": disk.snapshot() if disk is not None else None,
                "artifacts": [{k: v for k, v in a.items() if k != "hlo"}
                              for a in self.artifacts()]}

    def reset(self) -> None:
        with self._lock:
            self._fps.clear()
            self._artifacts.clear()
            self._evicted.clear()
            self.hits_n = self.misses_n = self.evictions_n = 0
            self.disk_hits_n = 0
            self.background_n = 0


_observatory = CompileObservatory()


def get_compile_observatory() -> CompileObservatory:
    return _observatory


def _cost_analysis(compiled) -> Optional[dict]:
    """Normalized XLA cost analysis of a compiled executable: jax
    returns a dict on recent versions, a one-element list of dicts on
    older ones, and some backends return None."""
    try:
        cost = compiled.cost_analysis()
    except Exception:   # noqa: BLE001 — backend-dependent, optional
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return dict(cost) if isinstance(cost, dict) else None


class _PendingResult:
    """A DISPATCHED (not yet synchronized) plan execution: the output
    planes and the device-resident row count.  `finish()` performs the
    one device→host sync (`int(count)`) and wraps the chunk — callers
    fanning out over many shards dispatch every program first and
    synchronize once (`finish_all`), instead of stalling the dispatch
    queue on a host read per shard."""

    __slots__ = ("planes", "count", "output", "stats", "_t0", "_chunk",
                 "compile_seconds", "execution_tier", "fingerprint")

    def __init__(self, planes, count, output, stats=None, t0=None):
        self.planes = planes
        self.count = count
        self.output = output
        self.stats = stats
        self._t0 = t0
        self.compile_seconds = 0.0
        self.execution_tier = "compiled"
        self.fingerprint: Optional[str] = None
        self._chunk: Optional[ColumnarChunk] = None

    def finish(self, host_count: Optional[int] = None) -> ColumnarChunk:
        import time as _time
        if self._chunk is None:
            if host_count is None:
                # The sanctioned host-sync point (jax pass): int(count)
                # below blocks on a device→host read — the sanitizer
                # flags it when it runs under a registered hot lock.
                # With host_count supplied, finish_all already did ONE
                # stacked transfer for the batch (noted there).
                sanitizers.note_host_sync("evaluator.finish")
                # The wait for the device, apart from prepare and launch
                # (runs on the caller's thread, so under its trace).
                with child_span("evaluator.sync", pendings=1):
                    n = int(self.count)
            else:
                n = int(host_count)
            out_columns: dict[str, Column] = {}
            out_schema_cols = []
            for out_col, (data, valid) in zip(self.output, self.planes):
                out_schema_cols.append((out_col.name, out_col.type.value))
                out_columns[out_col.name] = Column(
                    type=out_col.type, data=data, valid=valid,
                    dictionary=out_col.vocab)
            out_schema = TableSchema.make(out_schema_cols)
            self._chunk = ColumnarChunk(schema=out_schema, row_count=n,
                                        columns=out_columns)
            if self.stats is not None and self._t0 is not None:
                self.stats.execute_time += _time.perf_counter() - self._t0
        return self._chunk


class _ReadyResult:
    """Already-materialized result (totals plans sync internally)."""

    __slots__ = ("_chunk", "fingerprint")
    count = None
    execution_tier = "compiled"

    def __init__(self, chunk: ColumnarChunk,
                 fingerprint: Optional[str] = None):
        self._chunk = chunk
        self.fingerprint = fingerprint

    def finish(self, host_count: Optional[int] = None) -> ColumnarChunk:
        return self._chunk


def finish_all(pendings: Sequence) -> list[ColumnarChunk]:
    """Synchronize a batch of dispatched plans with ONE host transfer:
    the per-shard row counts cross device→host as a single stacked
    array instead of one blocking read per shard."""
    import jax.numpy as jnp
    open_ = [p for p in pendings
             if isinstance(p, _PendingResult) and p._chunk is None]
    host: dict[int, int] = {}
    if len(open_) > 1:
        # The one stacked transfer happens HERE; a single open pending
        # falls through to finish(), which notes its own sync.
        sanitizers.note_host_sync("evaluator.finish_all")
        with child_span("evaluator.sync", pendings=len(open_)):
            counts = np.asarray(jnp.stack([p.count for p in open_]))
        host = {id(p): int(c) for p, c in zip(open_, counts)}
    return [p.finish(host_count=host.get(id(p))) for p in pendings]


class TierGovernor:
    """Per-fingerprint interpreter-tier roll-up (ISSUE 18 tentpole,
    piece b): interpreted run count and cumulative interpreted seconds
    per fingerprint — the promotion signal.  `note_interpreted` returns
    True exactly once per fingerprint, when the run count crosses the
    configured hot threshold, so the caller enqueues ONE background
    promotion; a dropped enqueue re-arms via `rearm` (promotion is an
    optimization, a full queue must not silently orphan a hot shape)."""

    CAP = 4096

    def __init__(self):
        # guards: _fps
        self._lock = sanitizers.register_lock(
            "evaluator.TierGovernor._lock")
        self._fps: "OrderedDict[str, dict]" = OrderedDict()

    def note_interpreted(self, fp: str, seconds: float,
                         threshold: int) -> bool:
        with self._lock:
            entry = self._fps.get(fp)
            if entry is None:
                entry = self._fps[fp] = {"runs": 0, "seconds": 0.0,
                                         "armed": True}
                while len(self._fps) > self.CAP:
                    self._fps.popitem(last=False)
            entry["runs"] += 1
            entry["seconds"] += seconds
            if entry["armed"] and entry["runs"] >= threshold:
                entry["armed"] = False
                return True
            return False

    def rearm(self, fp: str) -> None:
        with self._lock:
            entry = self._fps.get(fp)
            if entry is not None:
                entry["armed"] = True

    def runs(self, fp: str) -> int:
        with self._lock:
            entry = self._fps.get(fp)
            return entry["runs"] if entry else 0

    def snapshot(self) -> list[dict]:
        with self._lock:
            rows = [{"fingerprint": fp, "runs": e["runs"],
                     "interpreted_seconds": round(e["seconds"], 6)}
                    for fp, e in self._fps.items()]
        rows.sort(key=lambda r: (-r["runs"], r["fingerprint"]))
        return rows

    def reset(self) -> None:
        with self._lock:
            self._fps.clear()


class BackgroundCompiler:
    """Bounded off-thread promotion pipeline (ISSUE 18 tentpole, piece
    b): hot interpreted fingerprints compile HERE — single-flight per
    cache key, bounded queue (overflow drops, never blocks a serving
    thread), cache insert under the evaluator's cache lock — and the
    compiled program atomically replaces the interpreter mid-traffic:
    the very next dispatch of that key takes the memory-LRU hit path.

    `_lock` guards ONLY queue/bookkeeping state and is NEVER held
    across a compile or while taking the evaluator's cache lock, so the
    lock-order graph gains no edges from this thread."""

    IDLE_EXIT_SECONDS = 1.0

    def __init__(self, evaluator: "Evaluator"):
        self._evaluator = evaluator
        # guards: _queue, _queued, _promoted, _thread, compiled_n, dropped_n
        self._lock = sanitizers.register_lock(
            "evaluator.BackgroundCompiler._lock")
        self._queue: deque = deque()
        self._queued: set = set()
        # Fingerprints promoted but not yet observed by a serving
        # thread: the first compiled hit after promotion reports
        # execution_tier="promoted-midstream" (consume-once).
        self._promoted: set = set()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.compiled_n = 0
        self.dropped_n = 0

    def enqueue(self, key: tuple, prepared, args,
                depth: int) -> str:
        """Returns "queued", "duplicate", or "full"."""
        with self._lock:
            if key in self._queued:
                return "duplicate"
            if len(self._queue) >= depth:
                self.dropped_n += 1
                return "full"
            self._queued.add(key)
            self._queue.append((key, prepared, args))
            _tier_queue_gauge.set(len(self._queue))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name="background-compiler")
                self._thread.start()
        self._wake.set()
        return "queued"

    def consume_promoted(self, fp: str) -> bool:
        if not self._promoted:     # lock-free fast path: usually empty
            return False
        with self._lock:
            if fp in self._promoted:
                self._promoted.discard(fp)
                return True
        return False

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def drain(self, timeout: float = 30.0) -> None:
        """Block until the queue is empty and no compile is in flight
        (tests + graceful shutdown; the serving path never calls it)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not self._queue and not self._queued:
                    return
            time.sleep(0.005)

    def snapshot(self) -> dict:
        with self._lock:
            return {"queue_depth": len(self._queue),
                    "compiled": self.compiled_n,
                    "dropped": self.dropped_n,
                    "pending_promoted_tags": len(self._promoted)}

    # -- worker ----------------------------------------------------------------

    def _run(self) -> None:
        while True:
            self._wake.wait(timeout=self.IDLE_EXIT_SECONDS)
            self._wake.clear()
            while True:
                with self._lock:
                    item = self._queue.popleft() if self._queue else None
                    _tier_queue_gauge.set(len(self._queue))
                if item is None:
                    break
                try:
                    self._work(item)
                except Exception:   # noqa: BLE001 — promotion is an
                    # optimization; a failed compile must never kill
                    # the worker (the interpreter keeps serving, and
                    # _work's finally already released the key).
                    pass
            with self._lock:
                if not self._queue and not self._wake.is_set():
                    # Park: exit the thread; a later enqueue restarts
                    # one (bounded threads across idle evaluators).
                    self._thread = None
                    return

    def _work(self, item) -> None:
        key, prepared, args = item
        evaluator = self._evaluator
        try:
            with evaluator._cache_lock:
                done = key in evaluator._cache
            if not done:
                self._promote(key, prepared, args)
        finally:
            with self._lock:
                self._queued.discard(key)

    def _promote(self, key: tuple, prepared, args) -> None:
        import time as _time

        from ytsaurus_tpu.config import workload_config
        from ytsaurus_tpu.query.engine.aot_cache import (
            get_cluster_store, get_disk_cache)
        cfg = workload_config()
        t0 = _time.perf_counter()
        lowered = None
        jitted = _jit_run(prepared.run)
        try:
            lowered = jitted.lower(*args)
            fn = lowered.compile()
        except Exception:   # noqa: BLE001 — AOT is an optimization;
            # anything it cannot lower promotes through the jit
            # wrapper (the call below compiles it fused, off-thread).
            lowered = None
            fn = jitted
            fn(*args)
        seconds = _time.perf_counter() - t0
        if lowered is not None:
            disk = get_disk_cache()
            cluster = get_cluster_store()
            if disk is not None:
                disk.store(key, fn, key[0], seconds)
            if cluster is not None:
                cluster.publish(key, fn, key[0], seconds)
        with self._evaluator._cache_lock:
            self._evaluator._cache[key] = fn
            evicted_keys = []
            if cfg.compile_cache_capacity:
                while len(self._evaluator._cache) > \
                        cfg.compile_cache_capacity:
                    evicted_keys.append(
                        self._evaluator._cache.popitem(last=False)[0])
        for evicted_key in evicted_keys:
            _observatory.observe_eviction(evicted_key)
            _evictions_counter.increment()
        _observatory.observe_background(key[0], key, seconds)
        _promotions_counter.increment()
        with self._lock:
            self._promoted.add(key[0])
            self.compiled_n += 1
        # The flight recorder's slow-query surface records the
        # promotion event (ISSUE 18 satellite): which fingerprint, how
        # long the background compile ran, how many interpreted runs
        # preceded it.
        from ytsaurus_tpu.query.profile import get_flight_recorder
        get_flight_recorder().note_promotion(
            key[0], seconds,
            runs_interpreted=self._evaluator._governor.runs(key[0]),
            capacity=int(key[1]))


class Evaluator:
    """Caches compiled query programs and executes plans over chunks."""

    def __init__(self):
        # LRU order (promote on hit); bounded when
        # WorkloadConfig.compile_cache_capacity > 0, with evictions fed
        # to the compilation observatory.  The lock covers every cache
        # mutation — concurrent gateway threads share one evaluator, and
        # an unlocked move_to_end could KeyError against a concurrent
        # eviction (compiles themselves run outside the lock).
        self._cache: OrderedDict = OrderedDict()
        # guards: _cache, _inflight
        self._cache_lock = sanitizers.register_lock(
            "evaluator.Evaluator._cache_lock")
        # Single-flight compilation (ISSUE 10): concurrent dispatches
        # missing on the SAME key elect one compiler; the rest wait on
        # its event and take the cached program — a cold shape under an
        # 8-thread replay burst used to compile 4-8 identical programs
        # (thundering herd), each counted as a miss against the
        # steady-state hit-rate SLO.
        self._inflight: dict = {}
        self._join_cache: dict = {}
        # Adaptive tiering (ISSUE 18): interpreted-run roll-up (the
        # promotion signal) + the background promotion pipeline.  Both
        # are inert — no threads, a few allocations — until
        # TieringConfig.enabled turns the tier decision on.
        self._governor = TierGovernor()
        self._background = BackgroundCompiler(self)

    def cache_size(self) -> int:
        return len(self._cache)

    def tier_snapshot(self, top: int = 50) -> dict:
        """Monitoring/orchid view of the tiering plane (ISSUE 18)."""
        from ytsaurus_tpu.config import tiering_config
        cfg = tiering_config()
        return {"enabled": cfg.enabled,
                "hot_threshold": cfg.hot_threshold,
                "background": self._background.snapshot(),
                "fingerprints": self._governor.snapshot()[:top]}

    def _acquire_inflight(self, key: tuple):
        """Single-flight gate for one cache key: returns the compiled
        program if a concurrent leader finished it, or None with THIS
        caller elected leader (it must call _release_inflight)."""
        while True:
            with self._cache_lock:
                fn = self._cache.get(key)
                if fn is not None:
                    self._cache.move_to_end(key)
                    return fn
                event = self._inflight.get(key)
                if event is None:
                    self._inflight[key] = threading.Event()
                    return None
            # A leader is compiling this key: wait, then re-check (the
            # loop re-elects if the leader failed or the entry was
            # evicted before we woke).
            event.wait(timeout=600)

    def _release_inflight(self, key: tuple) -> None:
        with self._cache_lock:
            event = self._inflight.pop(key, None)
        if event is not None:
            event.set()

    # -- plan execution --------------------------------------------------------

    def run_plan(self, plan: "ir.Query | ir.FrontQuery",
                 chunk: ColumnarChunk,
                 foreign_chunks: Optional[Mapping[str, ColumnarChunk]] = None,
                 stats: Optional[QueryStatistics] = None,
                 token=None) -> ColumnarChunk:
        """Execute a plan over one input chunk (plus join tables).

        `token` (query/serving.CancellationToken) is checked BEFORE any
        device program launches: a query past its deadline stops here
        instead of consuming device time on a result nobody will read."""
        with self._run_plan_span(chunk) as span:
            return self._start_plan(plan, chunk, foreign_chunks, stats,
                                    token, span).finish()

    def run_plan_async(self, plan: "ir.Query | ir.FrontQuery",
                       chunk: ColumnarChunk,
                       foreign_chunks: Optional[Mapping[str, ColumnarChunk]] = None,
                       stats: Optional[QueryStatistics] = None,
                       token=None):
        """Dispatch a plan's device program WITHOUT synchronizing;
        returns a pending handle whose `.finish()` yields the chunk.
        The coordinator's shard fan-out uses this to enqueue every
        shard's program before the first host sync (its
        `evaluator.run_plan` span therefore ends at the launch; the
        batch's `evaluator.sync` is `finish_all`'s)."""
        with self._run_plan_span(chunk) as span:
            return self._start_plan(plan, chunk, foreign_chunks, stats,
                                    token, span)

    @staticmethod
    def _run_plan_span(chunk: ColumnarChunk):
        # Span per plan execution (ref: evaluator.cpp:67-75 annotates
        # spans with query fingerprints; the tag lands once the dispatch
        # has computed it).  INTERIOR site: records only under a live
        # trace (gateway/scheduler root), so untraced evaluator use stays
        # on the null fast path.  Children: evaluator.prepare / compile /
        # launch, and on the synchronous path evaluator.sync.
        return child_span("evaluator.run_plan", rows=chunk.row_count)

    def _start_plan(self, plan, chunk, foreign_chunks, stats, token, span):
        import time as _time
        if token is not None:
            token.check()
        t0 = _time.perf_counter()
        pending = self._dispatch_traced(plan, chunk, foreign_chunks, stats,
                                        t0, pool=getattr(token, "pool",
                                                         None))
        span.add_tag("fingerprint", pending.fingerprint)
        span.add_tag("compile_seconds",
                     round(getattr(pending, "compile_seconds", 0.0), 6))
        span.add_tag("execution_tier",
                     getattr(pending, "execution_tier", "compiled"))
        return pending

    def _dispatch_traced(self, plan, chunk, foreign_chunks, stats, t0,
                         pool=None):
        import time as _time
        jplan = None
        if isinstance(plan, ir.Query) and len(plan.joins) > 1:
            # Cost-based join order (ISSUE 14, query/planner.py): the
            # cascade below runs most-selective-first off the foreign
            # chunks' stats (memoized per chunk).  MUST happen before
            # the fingerprint: the reordered plan's fingerprint is how
            # the order reaches the compile cache — stable stats hit the
            # same program, a stats-driven flip compiles a fresh one.
            # The order is decided here, off the STAGED chunks, after
            # the client's `query.plan` has closed: a second span of that
            # name carries it (`join_order`: the foreign tables as they
            # execute; `join_reordered`: the planner left the declared
            # order).
            from ytsaurus_tpu.query import planner
            with child_span("query.plan") as plan_span:
                declared = plan.joins
                plan, jplan = planner.reorder_for_chunks(
                    plan, chunk.row_count, foreign_chunks)
                plan_span.add_tag("join_order", [
                    join.foreign_table for join in plan.joins])
                plan_span.add_tag("join_reordered",
                                  plan.joins != declared)
        owned_chunk = False
        if isinstance(plan, ir.Query) and plan.joins:
            foreign_chunks = foreign_chunks or {}
            # Materialize joins in (planner) execution order.  Each
            # stage carries only the columns that are live after it
            # (ir.join_cascade), and the main program is dispatched with
            # the plan cut the same way: its prepare, cache key and args
            # see the chunk they get.  Each stage's actual cardinality
            # folds into the EXPLAIN ANALYZE join plan next to the
            # estimate.
            cascade = ir.join_cascade(plan)
            plan = cascade.query
            current = project_chunk(chunk, cascade.from_schema)
            decisions = jplan.decisions if jplan is not None else None
            for pos, stage in enumerate(cascade.stages):
                join = stage.join
                if join.foreign_table not in foreign_chunks:
                    raise YtError(
                        f"No data provided for join table {join.foreign_table!r}",
                        code=EErrorCode.QueryExecutionError)
                foreign = foreign_chunks[join.foreign_table]
                # One span per join stage: key bind, phase 1's dispatch
                # and wait (its child `join.count_sync`), phase 2's
                # dispatch; phase 2 runs on under `evaluator.sync`.
                t_join = _time.perf_counter()
                with child_span("evaluator.join", table=join.foreign_table,
                                stage=pos, self_rows=current.row_count,
                                foreign_rows=foreign.row_count,
                                columns_out=len(stage.schema),
                                columns_pruned=stage.columns_pruned
                                ) as join_span:
                    current = execute_join(
                        current, stage.schema, join, foreign,
                        self._join_cache, stats=stats, span=join_span)
                    join_span.add_tag("out_rows", current.row_count)
                if stats is not None:
                    stats.joins_executed += 1
                    stats.note_join_seconds(
                        pos, _time.perf_counter() - t_join)
                    stats.join_rows_out += current.row_count
                    stats.note_join_stage(
                        pos, join.foreign_table, "local",
                        est_rows=decisions[pos].est_out
                        if decisions is not None else 0,
                        actual_rows=current.row_count,
                        columns_out=len(stage.schema),
                        columns_pruned=stage.columns_pruned)
            chunk = current
            # The cascade built `chunk`; this dispatch is its only
            # consumer, so its column planes are donatable (a totals
            # plan dispatches the same chunk twice — excluded below).
            owned_chunk = True
        # A plain scan's chunk is projected to the plan's columns inside
        # the dispatch, under its `evaluator.prepare` span.
        project = isinstance(plan, ir.Query) and not owned_chunk

        # GROUP BY ... WITH TOTALS: one extra grand-total row (null keys)
        # aggregated over the same filtered input, appended after the groups
        # (ref: totals handling in GroupOpHelper/GroupTotalsOpHelper,
        # cg_routines/registry.cpp:1920; totals_mode=before_having).
        # The concat needs both row counts, so totals plans materialize
        # eagerly.
        if plan.group is not None and plan.group.totals:
            if project:
                chunk = project_chunk(chunk, plan.schema)
            main = self._dispatch(plan, chunk, stats, pool=pool)
            result = main.finish()
            totals_plan = _make_totals_plan(plan)
            totals_pending = self._dispatch(totals_plan, chunk, stats,
                                            pool=pool)
            totals = totals_pending.finish()
            result = concat_chunks([result, totals])
            if stats is not None:
                # Compile time is tallied separately inside _dispatch;
                # keep it out of the execute bucket.
                stats.execute_time += _time.perf_counter() - t0 - \
                    main.compile_seconds - totals_pending.compile_seconds
            return _ReadyResult(result, main.fingerprint)

        pending = self._dispatch(plan, chunk, stats, pool=pool,
                                 donate_columns=owned_chunk,
                                 project=project)
        pending.stats = stats
        # The execute clock starts after compilation: wall = compile +
        # execute, reported separately (EXPLAIN ANALYZE's first split).
        pending._t0 = t0 + pending.compile_seconds
        return pending

    def _dispatch(self, plan, chunk: ColumnarChunk,
                  stats: Optional[QueryStatistics] = None,
                  pool: Optional[str] = None,
                  donate_columns: bool = False,
                  project: bool = False) -> _PendingResult:
        # Everything between the call and the cache decision: the plan's
        # fingerprint (with CompileConfig.parameterize the SHAPE
        # fingerprint — literal values hoisted, limits bucketed, ISSUE 10
        # — so one cache entry serves every constant of a query shape),
        # the projection of a plain scan's chunk, the lowering's bind,
        # the cache key and its lookup.  `cache` says how it ended.
        with child_span("evaluator.prepare") as span:
            fp = plan_fingerprint(plan)
            if project:
                chunk = project_chunk(chunk, plan.schema)
            prepared = prepare(plan, chunk)
            key = (fp, chunk.capacity, prepared.binding_shapes())
            if donate_columns:
                # A donating executable consumes its column planes; it
                # must never be served to a dispatch over a persistent
                # chunk.
                key = key + ("donate-cols",)
            columns = {c.name: (chunk.columns[c.name].data,
                                chunk.columns[c.name].valid)
                       for c in plan.schema}
            args = (columns, chunk.row_valid, tuple(prepared.bindings))
            with self._cache_lock:
                fn = self._cache.get(key)
                if fn is not None:
                    self._cache.move_to_end(key)
            cache = "hit"
            if fn is None:
                # Single-flight: either a concurrent leader hands us the
                # finished program (counted as a hit below), or WE are
                # elected leader (None back) and must release the gate.
                fn = self._acquire_inflight(key)
                cache = "miss" if fn is None else "inflight"
            span.add_tag("fingerprint", fp)
            span.add_tag("cache", cache)
        compile_seconds = 0.0
        result = None
        if stats is not None:
            # The pow2 capacity bucket this program runs against:
            # bucket churn (a shape-spectrum leak) becomes visible PER
            # QUERY in EXPLAIN ANALYZE, not just in aggregate.
            stats.capacity_buckets.add(int(chunk.capacity))
        if fn is None:
            # Tier decision (ISSUE 18): with tiering on and the plan
            # inside the interpreter's DECLARED coverage, _compile_miss
            # probes only the persistent AOT rungs — when all of them
            # miss it returns fn=None with ZERO miss bookkeeping and
            # the interpreter serves this dispatch (off the compile
            # ladder entirely) while the background compiler owns the
            # fingerprint's promotion.  Coverage fallthrough
            # (try_prepare -> None) and the kill switch both take the
            # pre-tiering inline-compile path below, unchanged.
            interp_query = None
            tier_cfg = None
            from ytsaurus_tpu.config import tiering_config
            tier_cfg = tiering_config()
            if tier_cfg.enabled:
                from ytsaurus_tpu.query.engine import interp
                interp_query = interp.try_prepare(plan, chunk)
            try:
                fn, compile_seconds, result = self._compile_miss(
                    key, prepared, chunk, args, stats, pool,
                    interp_query=interp_query,
                    donate_columns=donate_columns)
            finally:
                self._release_inflight(key)
            if fn is None and result is None:
                return self._interpreted(interp_query, key, chunk,
                                         prepared, args, stats, pool,
                                         tier_cfg)
        else:
            _cache_counters.counters(pool)["hits"].increment()
            _observatory.observe_hit(key[0])
            if stats is not None:
                stats.cache_hits += 1
        execution_tier = "compiled"
        # Encoded-plane accounting (ISSUE 19): the bind notebook says
        # which mode the string predicates compiled in — code-space
        # compares ("strlit" notes) vs the merged-vocab remap fallback
        # ("str-decoded" notes).  A query with both counts as decoded:
        # one remap gather re-materializes the cost the encoded path
        # exists to avoid.
        notes = _flat_notes(prepared.structure_key)
        if "str-decoded" in notes:
            _decoded_fallbacks_counter.increment()
            if stats is not None:
                stats.execution_encoding = "decoded"
        elif "strlit" in notes:
            _encoded_scans_counter.increment()
        from ytsaurus_tpu.config import compile_config as _cc
        if _cc().donate_buffers:
            # Donation armed for this compiled dispatch: row_valid
            # always, the column planes too for owned (join-cascade)
            # chunks.  Inert on CPU, but the counter tracks arming, not
            # the backend's ability to honor it.
            _donated_buffers_counter.increment(
                1 + (len(args[0]) if donate_columns else 0))
        if self._background.consume_promoted(key[0]):
            # First compiled serve after a mid-traffic background
            # promotion: the atomic swap, made visible.
            execution_tier = "promoted-midstream"
        _tier_counters.counters(pool)["compiled"].increment()
        if stats is not None:
            stats.execution_tier = execution_tier
        if result is None:
            # The dispatch call until it returns: the device runs on.
            with child_span("evaluator.launch"):
                try:
                    planes, count = fn(*args)
                except Exception:
                    if hasattr(fn, "lower"):
                        raise         # plain jitted fn: a genuine error
                    # AOT-compiled rejects an aval drift the cache key
                    # did not capture: rebuild through the tolerant jit
                    # wrapper (a genuine execution error re-raises
                    # identically).
                    fn = _jit_run(prepared.run, donate_columns)
                    with self._cache_lock:
                        self._cache[key] = fn
                    planes, count = fn(*args)
        else:
            planes, count = result
        pending = _PendingResult(planes, count, prepared.output)
        pending.compile_seconds = compile_seconds
        pending.execution_tier = execution_tier
        pending.fingerprint = fp
        return pending

    def _interpreted(self, interp_query, key, chunk, prepared, args,
                     stats, pool, tier_cfg) -> _PendingResult:
        """Serve one dispatch from the interpreter tier (ISSUE 18):
        executes the no-compile numpy program, rolls the fingerprint up
        in the governor, and enqueues a background promotion once the
        hot threshold is crossed.  Runs with the single-flight gate
        ALREADY RELEASED — concurrent dispatches of the same cold key
        each interpret in parallel (interpretation is cheap; the gate
        exists to prevent compile herds, not numpy herds)."""
        import time as _time
        t0 = _time.perf_counter()
        planes, count = interp_query.execute(chunk)
        seconds = _time.perf_counter() - t0
        _tier_counters.counters(pool)["interpreted"].increment()
        if stats is not None:
            stats.execution_tier = "interpreted"
        if self._governor.note_interpreted(key[0], seconds,
                                           tier_cfg.hot_threshold):
            status = self._background.enqueue(key, prepared, args,
                                              tier_cfg.queue_depth)
            if status == "full":
                self._governor.rearm(key[0])
        pending = _PendingResult(planes, count, interp_query.output)
        pending.execution_tier = "interpreted"
        pending.fingerprint = key[0]
        return pending

    def _compile_miss(self, key, prepared, chunk, args, stats, pool,
                      interp_query=None, donate_columns=False):
        """The memory-miss slow path (single-flight leader only):
        disk-tier load or fresh AOT compile, cache insert + eviction,
        counters/observatory/artifact bookkeeping.  Returns
        (fn, compile_seconds, eager_result_or_None).

        With `interp_query` set (tier decision, ISSUE 18) the persistent
        rungs are still probed — a ready executable beats interpreting —
        but when ALL of them miss this returns (None, 0.0, None) with no
        side effects at all: no miss counters, no span, no storm signal.
        The caller serves the interpreter and the background compiler
        owns the compile."""
        import time as _time

        from ytsaurus_tpu.config import workload_config
        from ytsaurus_tpu.query.engine.aot_cache import (
            get_cluster_store, get_disk_cache)
        cfg = workload_config()
        result = None
        # Cache miss, classified for the observatory BEFORE the
        # entry mutates: never-seen plan shape vs a known shape
        # meeting a new capacity/binding-shape vs an LRU re-miss —
        # or a DISK HIT, when the persistent artifact tier serves a
        # ready executable (the warm-restart arm, ISSUE 10).
        cause = _observatory.classify_miss(key[0], key)
        lowered = None
        fn = None
        disk = get_disk_cache()
        cluster = get_cluster_store()
        if interp_query is not None:
            t0p = _time.perf_counter()
            if disk is not None and (fn := disk.load(key)) is not None:
                cause = "disk_hit"
            elif cluster is not None and \
                    (fn := cluster.fetch(key)) is not None:
                cause = "cluster_hit"
            else:
                return None, 0.0, None
            probe_seconds = _time.perf_counter() - t0p
        # Memory miss: try the disk tier, then the CLUSTER artifact
        # store (fetch-on-miss, ISSUE 17 — a replica joining mid-storm
        # pulls hot executables its peers already published), else
        # build the device program NOW (AOT lower + compile, the XLA
        # analog of the reference's LLVM codegen pass) so compile time
        # is measured apart from execution.  Shapes/dtypes are pinned
        # by the cache key (capacity + binding shapes), which is
        # exactly what AOT requires — and exactly what makes the
        # executables serializable across processes.
        span = child_span("evaluator.compile", fingerprint=key[0],
                          capacity=chunk.capacity)
        with span:
            t0c = _time.perf_counter()
            if fn is not None:
                pass     # the tier probe above hit a persistent rung
            elif disk is not None and \
                    (fn := disk.load(key)) is not None:
                cause = "disk_hit"
            elif cluster is not None and \
                    (fn := cluster.fetch(key)) is not None:
                cause = "cluster_hit"
            else:
                jitted = _jit_run(prepared.run, donate_columns)
                try:
                    lowered = jitted.lower(*args)
                    fn = lowered.compile()
                except Exception:   # noqa: BLE001 — AOT is an
                    # optimization; anything it cannot lower falls back
                    # to the jit wrapper (first call compiles fused).
                    fn = jitted
                    lowered = None
                    result = fn(*args)
            compile_seconds = _time.perf_counter() - t0c
            if interp_query is not None:
                compile_seconds += probe_seconds
            span.add_tag("cause", cause)
        if lowered is not None:
            # Persist the fresh AOT product so the NEXT process
            # (rolling restart) warm-starts this shape from disk, and
            # publish-on-compile to the cluster store so a replica
            # added mid-storm fetches it instead of compiling inline.
            if disk is not None:
                disk.store(key, fn, key[0], compile_seconds)
            if cluster is not None:
                cluster.publish(key, fn, key[0], compile_seconds)
        with self._cache_lock:
            self._cache[key] = fn
            evicted_keys = []
            if cfg.compile_cache_capacity:
                while len(self._cache) > cfg.compile_cache_capacity:
                    evicted_keys.append(
                        self._cache.popitem(last=False)[0])
        for evicted_key in evicted_keys:
            _observatory.observe_eviction(evicted_key)
            _evictions_counter.increment()
        _cache_counters.counters(pool)["misses"].increment()
        _observatory.observe_miss(key[0], key, cause, compile_seconds)
        if cfg.capture_artifacts and lowered is not None:
            try:
                _observatory.capture_artifact(
                    key[0], key, lowered.as_text(),
                    _cost_analysis(fn), compile_seconds)
            except Exception:   # noqa: BLE001 — artifact capture is a
                # debugging aid, never an execution hazard.
                pass
        if stats is not None:
            stats.compile_count += 1
            stats.compile_time += compile_seconds
            if cause == "disk_hit":
                stats.compile_disk_hit += 1
            elif cause == "cluster_hit":
                stats.compile_cluster_hit += 1
            elif cause == "eviction":
                stats.compile_evicted += 1
            elif cause == "new_shape":
                stats.compile_new_shape += 1
            else:
                stats.compile_new_fingerprint += 1
        return fn, compile_seconds, result


def _typed_null(ty):
    """A null-valued expression carrying type `ty`: if(false, zero, null)."""
    return ir.TFunction(
        type=ty, name="if",
        args=(ir.TLiteral(type=EValueType.boolean, value=False),
              ir.TLiteral(type=ty, value=_zero_value(ty)),
              ir.TLiteral(type=EValueType.null, value=None)))


def _make_totals_plan(plan):
    """Derive the grand-total plan: single constant group key, same
    aggregates, project with group-key references nulled out, no having
    (before_having semantics), no order/limit."""
    from dataclasses import replace as dc_replace

    key_types = {item.name: item.expr.type for item in plan.group.group_items}

    def subst(e):
        return ir.map_expr(
            e, lambda node: _typed_null(node.type)
            if isinstance(node, ir.TReference) and node.name in key_types
            else node)

    const_key = ir.NamedExpr(
        name="__totals", expr=ir.TLiteral(type=EValueType.int64, value=0))
    group = ir.GroupClause(group_items=(const_key,),
                           aggregate_items=plan.group.aggregate_items,
                           totals=False)
    if plan.project is not None:
        project = ir.ProjectClause(items=tuple(
            ir.NamedExpr(name=i.name, expr=subst(i.expr))
            for i in plan.project.items))
    else:
        # Default projection: null keys + aggregate values, matching the
        # main query's output schema.
        items = []
        for item in plan.group.group_items:
            items.append(ir.NamedExpr(name=item.name,
                                      expr=_typed_null(item.expr.type)))
        for agg in plan.group.aggregate_items:
            items.append(ir.NamedExpr(
                name=agg.name,
                expr=ir.TReference(type=agg.type, name=agg.name)))
        project = ir.ProjectClause(items=tuple(items))
    return dc_replace(plan, group=group, having=None, order=None,
                      project=project, offset=0, limit=None)


def _zero_value(ty):
    if ty is EValueType.string:
        return b""
    if ty is EValueType.boolean:
        return False
    if ty is EValueType.double:
        return 0.0
    return 0


# -- convenience API -----------------------------------------------------------


_global_evaluator = Evaluator()


def select_rows(query: str,
                tables: Mapping[str, "ColumnarChunk | Sequence"],
                schemas: Optional[Mapping[str, TableSchema]] = None,
                evaluator: Optional[Evaluator] = None,
                params: Optional[Sequence] = None) -> ColumnarChunk:
    """One-shot: parse, plan, and execute a query over in-memory tables.

    `tables` maps table path → ColumnarChunk (or row list, requiring `schemas`
    to carry that table's schema).  `params` binds `?` placeholders (a list
    of floats binds as a vector — the NEAREST query vector).
    """
    evaluator = evaluator or _global_evaluator
    chunks: dict[str, ColumnarChunk] = {}
    schemas = dict(schemas or {})
    for path, data in tables.items():
        if isinstance(data, ColumnarChunk):
            chunks[path] = data
            schemas.setdefault(path, data.schema)
        else:
            if path not in schemas:
                raise YtError(f"Row-list table {path!r} requires a schema")
            chunks[path] = ColumnarChunk.from_rows(schemas[path], data)
    plan = build_query(query, schemas, params=params)
    source_chunk = chunks[plan.source]
    foreign = {p: c for p, c in chunks.items() if p != plan.source}
    return evaluator.run_plan(plan, source_chunk, foreign)
