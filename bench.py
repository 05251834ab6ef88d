"""Benchmarks for the BASELINE.md configs.

Prints ONE JSON line PER CONFIG: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"}.  A config that fails prints no
line and the process exits non-zero.  Default --config=all runs every
BASELINE config, each in its own child process (the parent never touches
JAX: a chip belongs to one process), printing the headline (TPC-H Q1,
config 1) last:

  q1      scan + filter + 8-aggregate GROUP BY (headline; default)
  groupby GROUP BY key over a sorted table (hash-aggregate path, config 2)
  topk    ORDER BY ... LIMIT K (config 3)
  q3      two-table JOIN + GROUP BY + top-K (TPC-H Q3, config 4)
  sort    device sort (single-chip stand-in for the 1B-row Sort, config 5)
  strings GROUP BY over a ~1M-distinct string column (hash-bucket path)
  window  running sum + rank OVER (PARTITION BY ... ORDER BY ...) over
          2M rows (segmented prefix-scan window subsystem)
  serving 64-client concurrent point lookups through the query gateway
          (continuous micro-batching, ISSUE 3) vs the pre-gateway
          sequential path; metric is the batched throughput, the
          speedup + p99s print on stderr
  scan    versioned MVCC snapshot read over a multi-chunk tablet with
          version churn (ISSUE 4): warm snapshot-cache select path is
          the metric; cold vectorized + pre-PR Python reference merge
          timings and speedups print on stderr
  trace_overhead  query flight recorder (ISSUE 5): asserts the untraced
          span-site fast path ≲1µs, reports sampled-mode tracing
          overhead on the select and warm-scan shapes; metric is the
          traced select throughput
  replay  workload recorder + replay harness (ISSUE 8): records a
          parameterized-query mix, exports/reloads it through the
          versioned capture format, then replays it open-loop against
          the live gateway; metric is the achieved replay throughput,
          p50/p99/p999 + steady-state compile-cache hit rate + slowest
          trace ids print on stderr
  serving_steady  compile-once serving (ISSUE 10): replays a skewed-
          literal parameterized mix three ways — pre-PR per-constant
          fingerprints (baseline), auto-parameterized + persistent AOT
          disk cache (asserts steady-state compile-cache hit rate
          >=99%), and a restart-warm-start leg in a SECOND process on
          the same artifact dir (asserts ~0 fresh compiles, disk hits
          only); metric is the parameterized replay throughput
  whole_plan  whole-plan fused SPMD execution (ISSUE 12): q1/groupby-
          class plans on the virtual 8-device CPU mesh, fused
          one-program lowering vs BOTH stitched rungs (shuffle +
          gather), asserting fused >=2x the best stitched rung and
          exactly one host sync per fused query; metric is the fused
          groupby-class throughput
  telemetry_overhead  cluster telemetry plane (ISSUE 6): asserts the
          per-site sensor-recording cost ≲1µs and the per-query
          accounting fold ≲20µs, then runs the serving lookup shape
          with the history sampler OFF vs ON at 100× the configured
          cadence and asserts the sampled throughput stays within 1%;
          metric is the sampled serving throughput
  tiering adaptive tiered execution (ISSUE 18): a burst of distinct
          cold query shapes inline-compiled vs interpreter-first with
          background promotion (cold p99 asserted >=10x lower, steady
          compiled share >=95%) plus a prewarmed-restart leg (0 inline
          compiles); metric is the interpreted cold-burst throughput
  all     run every config, one JSON line each (headline line printed last)

Row counts follow the platform JAX reports (JAX_PLATFORMS=cpu runs take
the smaller CPU sizes).  The iteration loop is time-boxed by --budget
seconds (default 420, env BENCH_BUDGET).

Baseline: the reference's LLVM-JIT evaluator on a modern x86 core sustains
roughly 5e7 rows/s on Q1-shaped scan+filter+group (order-of-magnitude from
vectorized-engine literature; the reference repo publishes no absolute
numbers — see BASELINE.md).  vs_baseline = ours / 5e7 for the query configs.

Usage: python bench.py [--config NAME] [--smoke] [--rows N] [--iters K]
                       [--budget SECONDS]
"""

import argparse
import json
import os
import sys
import time


BASELINE_ROWS_PER_SEC = 5.0e7

_DEADLINE = None   # wall-clock deadline for timed iterations (set in main)


def _iters_left(times, iters):
    """True while another timed iteration fits the budget."""
    if len(times) >= iters:
        return False
    if _DEADLINE is None or not times:
        return len(times) < iters          # always take at least one
    return time.monotonic() + max(times) < _DEADLINE


def _sync(x):
    """Synchronization by a host read of one element, sliced ON DEVICE
    first so only that element crosses to the host."""
    import numpy as np
    leaf = x
    while isinstance(leaf, (list, tuple)):
        leaf = leaf[0]
    if hasattr(leaf, "ravel"):
        leaf = leaf.ravel()[:1]
    np.asarray(leaf)


def _time_plan(query, tables, iters, evaluator=None):
    """Compile + time one plan over prepared chunks; returns best seconds."""
    import jax

    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.query.engine.lowering import prepare

    schemas = {path: chunk.schema for path, chunk in tables.items()}
    plan = build_query(query, schemas)
    chunk = tables[plan.source]
    prepared = prepare(plan, chunk)
    columns = {c.name: (chunk.columns[c.name].data,
                        chunk.columns[c.name].valid)
               for c in plan.schema}
    bindings = tuple(prepared.bindings)
    row_valid = chunk.row_valid
    fn = jax.jit(prepared.run)
    planes, count = fn(columns, row_valid, bindings)   # warm-up / compile
    _sync(planes)
    times = []
    while _iters_left(times, iters):
        t0 = time.perf_counter()
        planes, count = fn(columns, row_valid, bindings)
        _sync(planes)
        times.append(time.perf_counter() - t0)
    return min(times), int(count)


def bench_q1(n_rows, iters):
    from ytsaurus_tpu.models import tpch
    chunk = tpch.generate_lineitem_device(n_rows)
    best, groups = _time_plan(tpch.Q1, {"//tpch/lineitem": chunk}, iters)
    assert 1 <= groups <= 6
    return "tpch_q1_rows_per_sec", n_rows / best, best

def bench_groupby(n_rows, iters):
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.schema import TableSchema
    schema = TableSchema.make([("k", "int64", "ascending"), ("g", "int64"),
                               ("v", "int64")])
    chunk = tpch.device_chunk(schema, tpch.device_planes({
        "k": ("arange",), "g": ("randint", 0, 10_000),
        "v": ("randint", 0, 1000)}, n_rows), n_rows)
    best, _ = _time_plan(
        "g, sum(v) AS s, count(*) AS c FROM [//t] GROUP BY g",
        {"//t": chunk}, iters)
    return "groupby_rows_per_sec", n_rows / best, best

def bench_topk(n_rows, iters):
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.schema import TableSchema
    schema = TableSchema.make([("k", "int64"), ("v", "double")])
    chunk = tpch.device_chunk(schema, tpch.device_planes({
        "k": ("arange",), "v": ("uniform", 0.0, 1.0)}, n_rows), n_rows)
    best, count = _time_plan(
        "k, v FROM [//t] ORDER BY v DESC LIMIT 100", {"//t": chunk}, iters)
    assert count == 100
    return "topk_rows_per_sec", n_rows / best, best

def bench_q3(n_rows, iters):
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    n_orders = max(n_rows // 4, 1)
    lineitem = tpch.generate_lineitem_device(n_rows, n_orders=n_orders)
    orders = tpch.generate_orders_device(n_orders)
    ev = Evaluator()
    from ytsaurus_tpu.query.builder import build_query
    plan = build_query(tpch.Q3, {"//tpch/lineitem": tpch.LINEITEM_SCHEMA,
                                 "//tpch/orders": tpch.ORDERS_SCHEMA})
    foreign = {"//tpch/orders": orders}
    out = ev.run_plan(plan, lineitem, foreign)      # warm-up (incl. join)
    assert out.row_count <= 10
    times = []
    while _iters_left(times, iters):
        t0 = time.perf_counter()
        out = ev.run_plan(plan, lineitem, foreign)
        _sync(out.columns[out.schema.column_names[0]].data)
        times.append(time.perf_counter() - t0)
    best = min(times)
    return "tpch_q3_rows_per_sec", n_rows / best, best

def bench_sort(n_rows, iters):
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.operations.sort_op import sort_chunk
    from ytsaurus_tpu.schema import TableSchema
    schema = TableSchema.make([("k", "int64"), ("p", "double")])
    spill_rows = int(os.environ.get("YT_TPU_SORT_SPILL_ROWS",
                                    128_000_000))
    if n_rows > spill_rows:
        return _bench_sort_spill(n_rows, iters, schema)
    chunk = tpch.device_chunk(schema, tpch.device_planes({
        "k": ("randint", 0, 1 << 60), "p": ("uniform", 0.0, 1.0)},
        n_rows), n_rows)
    out = sort_chunk(chunk, ["k"])                  # warm-up
    _sync(out.columns["k"].data)
    times = []
    while _iters_left(times, iters):
        t0 = time.perf_counter()
        out = sort_chunk(chunk, ["k"])
        _sync(out.columns["k"].data)
        times.append(time.perf_counter() - t0)
    return "sort_rows_per_sec", n_rows / min(times), min(times)


def _bench_sort_spill(n_rows, iters, schema):
    """BASELINE config 5 shape: input larger than HBM — external sort
    (range partition + host spill + per-range device sorts, ops/bigsort).
    Blocks generate lazily so peak device memory stays budget-bounded."""
    import numpy as np

    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.ops.bigsort import SpillStats, external_sort

    block_rows = 16_000_000
    budget = int(os.environ.get("YT_TPU_HBM_BUDGET", 8 << 30))

    def supplier(i, rows):
        def make():
            rng = np.random.default_rng(1000 + i)
            return ColumnarChunk.from_arrays(schema, {
                "k": rng.integers(0, 1 << 60, size=rows,
                                  dtype=np.int64),
                "p": rng.random(rows)})
        return make

    suppliers = []
    left, i = n_rows, 0
    while left > 0:
        rows = min(block_rows, left)
        suppliers.append(supplier(i, rows))
        left -= rows
        i += 1
    times = []
    while _iters_left(times, 1):       # spill passes are minutes: one run
        stats = SpillStats()
        t0 = time.perf_counter()
        total = 0
        prev_last = None
        for out in external_sort(suppliers, ["k"], budget_bytes=budget,
                                 stats=stats):
            # Touch the output (forces the device work) + verify global
            # order across range boundaries.
            n = out.row_count
            first = int(np.asarray(out.columns["k"].data[:1])[0])
            last = int(np.asarray(out.columns["k"].data[n - 1:n])[0])
            if prev_last is not None:
                assert first >= prev_last, "range order violated"
            prev_last = last
            total += n
        times.append(time.perf_counter() - t0)
        assert total == n_rows, (total, n_rows)
        print(f"# spill sort: {stats.ranges} ranges, "
              f"{stats.resplits} resplits, peak range "
              f"{stats.peak_range_rows} rows (budget "
              f"{stats.budget_rows})", file=sys.stderr)
    return "sort_rows_per_sec", n_rows / min(times), min(times)

def bench_strings(n_rows, iters):
    """GROUP BY over a high-cardinality (~n/10 distinct) string column."""
    import numpy as np
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.schema import TableSchema
    n_distinct = max(n_rows // 10, 1)
    schema = TableSchema.make([("k", "int64", "ascending"), ("s", "string"),
                               ("v", "int64")])
    # Codes on device; only the (host-side) vocabulary is materialized.
    vocab = np.empty(n_distinct, dtype=object)
    vocab[:] = [b"u%08d" % c for c in range(n_distinct)]
    chunk = tpch.device_chunk(schema, tpch.device_planes({
        "k": ("arange",), "s": ("randint", 0, n_distinct),
        "v": ("randint", 0, 1000)}, n_rows), n_rows,
        dictionaries={"s": vocab})
    best, groups = _time_plan(
        "s, sum(v) AS t FROM [//t] GROUP BY s", {"//t": chunk}, iters)
    assert groups <= n_distinct
    return "strings_groupby_rows_per_sec", n_rows / best, best


def bench_select(n_rows, iters):
    """Host-coordinated distributed select (coordinate_and_execute over
    8 shards): scan + filter + GROUP BY through the per-shard recovery
    ladder (ISSUE 2).  Also proves the DISABLED failpoint fast path adds
    no measurable overhead — the sites sit on this exact code path."""
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.query.coordinator import coordinate_and_execute
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.schema import TableSchema
    from ytsaurus_tpu.utils import failpoints

    # Fast-path micro-check: a disabled failpoint hit must be ~free
    # (one module-global read), or threading sites through every I/O
    # boundary would tax fault-free production.
    probe = failpoints.register_site("bench.overhead.probe")
    n_probe = 200_000
    t0 = time.perf_counter()
    for _ in range(n_probe):
        probe.hit()
    per_hit = (time.perf_counter() - t0) / n_probe
    print(f"# failpoints disabled fast path: {per_hit * 1e9:.0f} ns/hit",
          file=sys.stderr)
    assert per_hit < 5e-6, \
        f"disabled failpoint hit too slow: {per_hit * 1e9:.0f} ns"

    schema = TableSchema.make([("k", "int64", "ascending"), ("g", "int64"),
                               ("v", "int64")])
    chunk = tpch.device_chunk(schema, tpch.device_planes({
        "k": ("arange",), "g": ("randint", 0, 10_000),
        "v": ("randint", 0, 1000)}, n_rows), n_rows)
    n_shards = 8
    per = max(n_rows // n_shards, 1)
    shards = [chunk.slice_rows(i * per, min((i + 1) * per, n_rows))
              for i in range(n_shards) if i * per < n_rows]
    plan = build_query(
        "g, sum(v) AS s, count(*) AS c FROM [//t] WHERE v < 900 GROUP BY g",
        {"//t": schema})
    ev = Evaluator()
    out = coordinate_and_execute(plan, shards, evaluator=ev)   # warm-up
    _sync(out.columns[out.schema.column_names[0]].data)
    times = []
    while _iters_left(times, iters):
        t0 = time.perf_counter()
        out = coordinate_and_execute(plan, shards, evaluator=ev)
        _sync(out.columns[out.schema.column_names[0]].data)
        times.append(time.perf_counter() - t0)
    best = min(times)
    return "select_rows_per_sec", n_rows / best, best


def bench_window(n_rows, iters):
    """Window subsystem (ISSUE 1): running sum + rank over ~1k
    partitions — one packed u32 sort + segmented prefix scans
    (query/engine/window.py)."""
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.schema import TableSchema
    schema = TableSchema.make([("k", "int64", "ascending"),
                               ("g", "int64"), ("v", "int64")])
    chunk = tpch.device_chunk(schema, tpch.device_planes({
        "k": ("arange",), "g": ("randint", 0, 1000),
        "v": ("randint", 0, 1000)}, n_rows), n_rows)
    best, count = _time_plan(
        "k, sum(v) OVER (PARTITION BY g ORDER BY k) AS s, "
        "rank() OVER (PARTITION BY g ORDER BY k) AS r FROM [//t]",
        {"//t": chunk}, iters)
    assert count == n_rows
    return "window_rows_per_sec", n_rows / best, best


def bench_serving(n_rows, iters):
    """Query serving plane (ISSUE 3): 64 concurrent clients doing
    point lookups (8-key multi-gets) against one flushed 4-tablet
    dynamic table, batched (gateway micro-batching + vectorized batch
    probe + per-tablet fan-out) vs unbatched (the pre-gateway
    sequential path: one full-plane chunk mask PER KEY, tablets
    visited sequentially).  The table is larger than the tablet row
    caches, so per-key chunk-probe cost — the cost batching
    amortizes — dominates, as it does at serving scale.  The emitted
    metric is the BATCHED key throughput; the speedup and p99s go to
    stderr.  n_rows sizes the table."""
    import random
    import tempfile
    import threading

    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.schema import TableSchema

    n_clients = 64
    per_client = 8
    keys_per_op = 8
    client = connect(tempfile.mkdtemp(prefix="bench-serving-"))
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("v", "int64")], unique_keys=True)
    pivots = [[n_rows // 4], [n_rows // 2], [3 * n_rows // 4]]
    client.create("table", "//bench/serve",
                  attributes={"schema": schema, "dynamic": True,
                              "pivot_keys": pivots}, recursive=True)
    client.mount_table("//bench/serve")
    for lo in range(0, n_rows, 50_000):
        hi = min(lo + 50_000, n_rows)
        client.insert_rows("//bench/serve",
                           [{"k": i, "v": i * 3} for i in range(lo, hi)])
    # Flush to chunks: the steady serving state (memtable-only tables
    # are the post-restart exception, not the rule).
    client.freeze_table("//bench/serve")

    def run_mode(lookup_fn):
        latencies = []
        lock = threading.Lock()
        barrier = threading.Barrier(n_clients + 1)

        def worker(seed):
            rng = random.Random(seed)
            mine = []
            barrier.wait()
            for _ in range(per_client):
                keys = [(rng.randrange(n_rows),)
                        for _ in range(keys_per_op)]
                t0 = time.perf_counter()
                rows = lookup_fn("//bench/serve", keys)
                mine.append(time.perf_counter() - t0)
                assert rows[0]["v"] == keys[0][0] * 3
            with lock:
                latencies.extend(mine)

        threads = [threading.Thread(target=worker, args=(s,), daemon=True)
                   for s in range(n_clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        latencies.sort()
        p99 = latencies[int(len(latencies) * 0.99) - 1]
        total_keys = n_clients * per_client * keys_per_op
        return total_keys / elapsed, p99, elapsed

    # Warm both paths (tablet host planes) off the clock.
    client._lookup_rows_direct("//bench/serve", [(0,), (n_rows - 1,)])
    client.lookup_rows("//bench/serve", [(1,)])
    seq_tput, seq_p99, _ = run_mode(client._lookup_rows_direct)
    best_tput, best_p99, best_elapsed = 0.0, 0.0, 0.0
    times = []
    while _iters_left(times, iters):
        t0 = time.perf_counter()
        tput, p99, elapsed = run_mode(client.lookup_rows)
        times.append(time.perf_counter() - t0)
        if tput > best_tput:
            best_tput, best_p99, best_elapsed = tput, p99, elapsed
    snap = client.cluster.gateway.snapshot()["lookup"]
    print(f"# serving: batched {best_tput:.0f} keys/s "
          f"p99={best_p99*1e3:.2f}ms vs unbatched {seq_tput:.0f} keys/s "
          f"p99={seq_p99*1e3:.2f}ms "
          f"(speedup {best_tput / max(seq_tput, 1e-9):.2f}x, "
          f"{snap['requests']:.0f} requests in {snap['batches']:.0f} "
          "batches)", file=sys.stderr)
    return "serving_lookup_rows_per_sec", best_tput, best_elapsed


def bench_trace_overhead(n_rows, iters):
    """Query flight recorder (ISSUE 5): the UNTRACED span-site fast path
    must stay ≲1µs/site (one contextvar read + a singleton return —
    mirror of the failpoints fast-path assert: the query/operation planes
    thread ~20 sites through their hot paths, and fault-free untraced
    production must not pay for them), and sampled tracing must tax the
    select/scan pipelines only marginally.  The emitted metric is the
    TRACED select throughput; the per-site costs and the traced-vs-
    untraced deltas for the select and scan shapes go to stderr."""
    from ytsaurus_tpu import config as _config
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.query.coordinator import coordinate_and_execute
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.schema import TableSchema
    from ytsaurus_tpu.utils import tracing

    def per_site(site):
        """min-of-rounds mean: stable against scheduler noise."""
        n_round, best = 40_000, float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n_round):
                with site("bench.trace.site"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n_round)
        return best

    # (a) interior site with NO ambient trace — the path every span site
    # in an untraced query takes.
    null_cost = per_site(tracing.child_span)
    # (b) entry-point site with tracing DISABLED outright.
    _config.set_tracing_config(_config.TracingConfig(enabled=False))
    try:
        disabled_cost = per_site(tracing.start_query_span)
    finally:
        _config.set_tracing_config(None)
    # (c) reference: a live recorded span (allocation + collector add).
    def _recorded(name):
        return tracing.TraceContext(name)
    recorded_cost = per_site(_recorded)
    print(f"# trace sites: untraced child_span {null_cost * 1e9:.0f} "
          f"ns/site, disabled entry {disabled_cost * 1e9:.0f} ns/site, "
          f"recorded span {recorded_cost * 1e9:.0f} ns/site",
          file=sys.stderr)
    assert null_cost < 1.5e-6, \
        f"untraced span site too slow: {null_cost * 1e9:.0f} ns"
    assert disabled_cost < 1.5e-6, \
        f"disabled entry span site too slow: {disabled_cost * 1e9:.0f} ns"

    # Sampled-mode overhead, select shape: the bench_select pipeline
    # (8-shard coordinate_and_execute) untraced vs under a sampled root.
    schema = TableSchema.make([("k", "int64", "ascending"), ("g", "int64"),
                               ("v", "int64")])
    chunk = tpch.device_chunk(schema, tpch.device_planes({
        "k": ("arange",), "g": ("randint", 0, 10_000),
        "v": ("randint", 0, 1000)}, n_rows), n_rows)
    n_shards = 8
    per = max(n_rows // n_shards, 1)
    shards = [chunk.slice_rows(i * per, min((i + 1) * per, n_rows))
              for i in range(n_shards) if i * per < n_rows]
    plan = build_query(
        "g, sum(v) AS s, count(*) AS c FROM [//t] WHERE v < 900 GROUP BY g",
        {"//t": schema})
    ev = Evaluator()

    def timed_select(traced):
        out = coordinate_and_execute(plan, shards, evaluator=ev)  # warm
        _sync(out.columns[out.schema.column_names[0]].data)
        times = []
        while _iters_left(times, iters):
            t0 = time.perf_counter()
            if traced:
                with tracing.start_query_span("bench.trace.select"):
                    out = coordinate_and_execute(plan, shards,
                                                 evaluator=ev)
            else:
                out = coordinate_and_execute(plan, shards, evaluator=ev)
            _sync(out.columns[out.schema.column_names[0]].data)
            times.append(time.perf_counter() - t0)
        return min(times)

    plain = timed_select(traced=False)
    traced = timed_select(traced=True)

    # Sampled-mode overhead, scan shape: warm snapshot-cache tablet reads.
    import tempfile

    from ytsaurus_tpu.chunks.store import FsChunkStore
    from ytsaurus_tpu.tablet.tablet import Tablet
    tablet_schema = TableSchema.make(
        [("k", "int64", "ascending"), ("g", "int64"), ("v", "int64")],
        unique_keys=True)
    tablet = Tablet(tablet_schema,
                    FsChunkStore(tempfile.mkdtemp(prefix="bench-trace-")))
    for i in range(2048):
        tablet.write_row({"k": i, "g": i % 7, "v": i}, timestamp=100)
    tablet.read_snapshot()                        # prime the cache

    def timed_scan(do_trace):
        times = []
        while _iters_left(times, max(iters, 3)):
            t0 = time.perf_counter()
            for _ in range(100):
                if do_trace:
                    with tracing.start_query_span("bench.trace.scan"):
                        tablet.read_snapshot()
                else:
                    tablet.read_snapshot()
            times.append((time.perf_counter() - t0) / 100)
        return min(times)

    scan_plain = timed_scan(False)
    scan_traced = timed_scan(True)
    print(f"# sampled tracing overhead: select {plain * 1e3:.2f}ms -> "
          f"{traced * 1e3:.2f}ms "
          f"(+{(traced / plain - 1) * 100:.1f}%), warm scan "
          f"{scan_plain * 1e6:.0f}µs -> {scan_traced * 1e6:.0f}µs "
          f"(+{(scan_traced / scan_plain - 1) * 100:.1f}%)",
          file=sys.stderr)
    return "trace_overhead_rows_per_sec", n_rows / traced, traced


def bench_telemetry_overhead(n_rows, iters):
    """Cluster telemetry plane (ISSUE 6): the per-site recording cost
    (one counter increment / gauge set / histogram record — the unit
    every hot-path sensor pays) must stay ≲1µs, the per-query
    accounting fold (query/accounting.ResourceAccountant.fold: ~12
    counter adds under one lock) ≲20µs, and the sampler + accounting
    fold together must add ≤1% to the serving bench throughput.  The
    ≤1% claim is asserted as a deterministic decomposition — the
    sampler's whole cost is its duty cycle (sample_once walk time over
    the LIVE post-traffic registry / configured cadence) and the fold's
    is fold cost × the fold rate OBSERVED while the serving shape runs
    — because a direct A/B of a 16-thread throughput number on a noisy
    shared host cannot resolve 1% (round-to-round swings here are
    ±20%+); the A/B delta at 100× the configured cadence is still
    measured and printed for the record.  The emitted metric is the
    sampled serving key throughput."""
    import random
    import tempfile
    import threading

    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.query.accounting import ResourceAccountant
    from ytsaurus_tpu.schema import TableSchema
    from ytsaurus_tpu.utils.profiling import (
        MetricsHistory,
        Profiler,
        ProfilerRegistry,
        TelemetrySampler,
        get_registry,
    )
    from ytsaurus_tpu.utils.slo import SloTracker

    def per_site(fn, n_round=40_000, rounds=5):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(n_round):
                fn()
            best = min(best, (time.perf_counter() - t0) / n_round)
        return best

    reg = ProfilerRegistry()
    prof = Profiler("/bench/telemetry", registry=reg)
    counter, gauge = prof.counter("c"), prof.gauge("g")
    hist = prof.histogram("h")
    counter_cost = per_site(lambda: counter.increment())
    gauge_cost = per_site(lambda: gauge.set(1.25))
    hist_cost = per_site(lambda: hist.record(0.003))
    acct = ResourceAccountant(registry=reg)
    fold_cost = per_site(
        lambda: acct.fold("bench", "root", queries=1, rows_read=512,
                          bytes_read=16_384, compile_seconds=0.001,
                          execute_seconds=0.004, wall_seconds=0.005,
                          cache_hits=1),
        n_round=10_000)
    print(f"# telemetry sites: counter {counter_cost * 1e9:.0f} ns, "
          f"gauge {gauge_cost * 1e9:.0f} ns, histogram "
          f"{hist_cost * 1e9:.0f} ns, accounting fold "
          f"{fold_cost * 1e9:.0f} ns", file=sys.stderr)
    assert counter_cost < 1.5e-6, \
        f"counter record too slow: {counter_cost * 1e9:.0f} ns"
    assert gauge_cost < 1.5e-6, \
        f"gauge record too slow: {gauge_cost * 1e9:.0f} ns"
    assert hist_cost < 1.5e-6, \
        f"histogram record too slow: {hist_cost * 1e9:.0f} ns"
    assert fold_cost < 20e-6, \
        f"accounting fold too slow: {fold_cost * 1e9:.0f} ns"

    # Serving shape (scaled-down bench_serving): concurrent batched
    # multi-gets through the gateway, sampler OFF vs ON.
    n_clients, per_client, keys_per_op = 16, 64, 8
    client = connect(tempfile.mkdtemp(prefix="bench-telemetry-"))
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("v", "int64")], unique_keys=True)
    client.create("table", "//bench/telemetry",
                  attributes={"schema": schema, "dynamic": True,
                              "pivot_keys": [[n_rows // 2]]},
                  recursive=True)
    client.mount_table("//bench/telemetry")
    for lo in range(0, n_rows, 50_000):
        hi = min(lo + 50_000, n_rows)
        client.insert_rows("//bench/telemetry",
                           [{"k": i, "v": i * 3} for i in range(lo, hi)])
    client.freeze_table("//bench/telemetry")
    client.lookup_rows("//bench/telemetry", [(1,)])        # warm

    def run_round():
        barrier = threading.Barrier(n_clients + 1)

        def worker(seed):
            rng = random.Random(seed)
            barrier.wait()
            for _ in range(per_client):
                keys = [(rng.randrange(n_rows),)
                        for _ in range(keys_per_op)]
                rows = client.lookup_rows("//bench/telemetry", keys)
                assert rows[0]["v"] == keys[0][0] * 3
        threads = [threading.Thread(target=worker, args=(s,),
                                    daemon=True)
                   for s in range(n_clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        return n_clients * per_client * keys_per_op / elapsed, elapsed

    # The sampler walks the LIVE global registry (every sensor the
    # serving path above has created — the realistic per-tick cost),
    # with SLO evaluation hooked exactly as start_telemetry wires it.
    from ytsaurus_tpu.config import TelemetryConfig, telemetry_config
    from ytsaurus_tpu.query.accounting import get_accountant
    history = MetricsHistory(registry=get_registry())
    tracker = SloTracker(TelemetryConfig(), history=history)

    # A/B rounds (informational) + the observed accounting-fold rate;
    # one untimed round first warms every probe shape off the clock.
    run_round()
    rounds = min(max(iters or 0, 3), 7)
    best_off, best_on, best_on_elapsed = 0.0, 0.0, 0.0
    fold_rate = 0.0
    for _ in range(rounds):
        tput, _elapsed = run_round()
        best_off = max(best_off, tput)
        sampler = TelemetrySampler(history, period=0.1,
                                   hooks=[tracker.evaluate])
        sampler.start()
        folds0 = get_accountant().totals()["lookups"]
        try:
            tput, elapsed = run_round()
        finally:
            sampler.stop()
        fold_rate = max(fold_rate,
                        (get_accountant().totals()["lookups"] - folds0)
                        / elapsed)
        if tput > best_on:
            best_on, best_on_elapsed = tput, elapsed
    # Per-tick walk cost AFTER traffic: the registry now holds the full
    # serving sensor population and the rings are warm.
    walk_cost = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        history.sample_once()
        tracker.evaluate()
        walk_cost = min(walk_cost, time.perf_counter() - t0)

    period = telemetry_config().sample_period or 10.0
    sampler_share = walk_cost / period
    fold_share = fold_cost * fold_rate
    overhead = 1.0 - best_on / best_off if best_off else 0.0
    print(f"# sample_once+slo over the live registry: "
          f"{walk_cost * 1e6:.0f} µs/tick -> duty "
          f"{sampler_share * 100:.4f}% at the configured "
          f"{period:.0f}s cadence; accounting folds "
          f"{fold_rate:.0f}/s x {fold_cost * 1e9:.0f} ns -> "
          f"{fold_share * 100:.4f}% of one core", file=sys.stderr)
    print(f"# serving lookups: sampler off {best_off:.0f} keys/s, "
          f"on(100ms cadence) {best_on:.0f} keys/s "
          f"(A/B delta {overhead * 100:+.2f}%, informational: host "
          f"noise exceeds 1%)", file=sys.stderr)
    assert sampler_share + fold_share < 0.01, \
        f"telemetry costs {(sampler_share + fold_share) * 100:.3f}% " \
        f"> 1% (sampler duty {sampler_share * 100:.4f}%, accounting " \
        f"fold {fold_share * 100:.4f}%)"
    return "telemetry_overhead_rows_per_sec", best_on, best_on_elapsed


def bench_replay(n_rows, iters):
    """Workload recorder + replay harness (ISSUE 8): record a
    parameterized-query mix (3 shapes x skewed literal draws — the
    repeated-shape/varied-literal traffic ROADMAP 1 must compile once)
    against a flushed dynamic table, export the capture through the
    versioned workload-log schema, re-load it, and REPLAY it open-loop
    against the live gateway.  Reports p50/p99/p999, throttle/deadline
    counts, and the steady-state compile-cache hit rate (second half of
    the mix) — the measurement substrate the ROADMAP-1 ">=99% hit rate"
    acceptance will run on.  The emitted metric is the achieved replay
    query throughput; the latency/hit-rate detail and the slowest
    queries' trace ids go to stderr.  n_rows sizes the table."""
    import os as _os
    import random
    import tempfile

    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.query import workload as wl
    from ytsaurus_tpu.schema import TableSchema

    root = tempfile.mkdtemp(prefix="bench-replay-")
    client = connect(root)
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("g", "int64"), ("v", "int64")],
        unique_keys=True)
    client.create("table", "//bench/replay",
                  attributes={"schema": schema, "dynamic": True,
                              "pivot_keys": [[n_rows // 2]]},
                  recursive=True)
    client.mount_table("//bench/replay")
    for lo in range(0, n_rows, 50_000):
        hi = min(lo + 50_000, n_rows)
        client.insert_rows("//bench/replay",
                           [{"k": i, "g": i % 97, "v": i * 3}
                            for i in range(lo, hi)])
    client.freeze_table("//bench/replay")

    # Record phase: every select folds into the process workload log
    # (fresh — configure(None) rebinds it) via the normal client path.
    wl.configure(None)
    shapes = [
        "k, v FROM [//bench/replay] WHERE k = {}",
        "g, sum(v) AS s FROM [//bench/replay] WHERE v < {} GROUP BY g",
        "k, v FROM [//bench/replay] WHERE k > {} ORDER BY k LIMIT 10",
    ]
    rng = random.Random(7)
    distinct = [rng.randrange(n_rows) for _ in range(16)]
    n_queries = 240
    for i in range(n_queries):
        client.select_rows(shapes[i % len(shapes)].format(
            distinct[rng.randrange(4) if rng.random() < 0.5
                     else rng.randrange(len(distinct))]))
    capture_path = _os.path.join(root, "capture.json")
    written = wl.get_workload_log().export_capture(capture_path)
    records = wl.load_capture(capture_path)   # versioned-schema check
    assert written == len(records) == n_queries, (written, len(records))

    best = None
    times = []
    while _iters_left(times, iters):
        t0 = time.perf_counter()
        report = wl.replay(client, records, rate=400.0, max_workers=8)
        times.append(time.perf_counter() - t0)
        if best is None or report["achieved_rate"] > \
                best["achieved_rate"]:
            best = report
    lat, cache = best["latency"], best["compile_cache"]
    slow = best["slowest"][0] if best["slowest"] else {}
    print(f"# replay: {best['queries']} queries in "
          f"{best['elapsed_seconds']:.2f}s "
          f"({best['achieved_rate']:.0f}/s of {best['offered_rate']:.0f}/s "
          f"offered); p50={lat['p50_ms']:.2f}ms p99={lat['p99_ms']:.2f}ms "
          f"p999={lat['p999_ms']:.2f}ms; "
          f"{best['throttled']} throttled, {best['deadline']} deadline, "
          f"{best['error']} error; compile hit rate "
          f"{(cache['hit_rate'] or 0) * 100:.1f}% "
          f"(steady {(cache['steady_hit_rate'] or 0) * 100:.1f}%); "
          f"slowest {slow.get('wall_ms')}ms trace={slow.get('trace_id')}",
          file=sys.stderr)
    assert best["ok"] == best["queries"], best
    assert cache["steady_hit_rate"] is not None
    return ("replay_queries_per_sec", best["achieved_rate"],
            best["elapsed_seconds"])


def bench_serving_steady(n_rows, iters):
    """Compile-once serving (ISSUE 10): three legs over one fresh-
    constant parameterized mix (3 shapes x skewed draws over the FULL
    key domain, so constants essentially never repeat — the
    million-users `WHERE user_id = ?` traffic ROADMAP 1 names, which
    the pre-PR per-constant fingerprints recompile on every query).

      baseline   auto-parameterization OFF (the pre-PR discipline) on
                 a 60-query slice — recorded to show what
                 the fix buys (expected: every fresh constant is a
                 fresh fingerprint, hit rate collapses);
      steady     parameterization ON + persistent AOT disk cache, a
                 60-query warmup then the full measured replay —
                 acceptance: steady-state compile-cache hit rate >=99%
                 and CompileObservatory shape-spectrum cardinality
                 bounded (<= pow2 bucket count) despite ~240 distinct
                 constants;
      restart    a SECOND PROCESS builds the same table, points at the
                 same artifact directory, replays the same capture —
                 acceptance: ~0 fresh compiles (disk hits only), the
                 rolling-restart warm start.

    Metric is the parameterized leg's achieved replay throughput."""
    import os as _os
    import random
    import subprocess as _subprocess
    import tempfile

    from ytsaurus_tpu import config as yt_config
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.query import workload as wl
    from ytsaurus_tpu.schema import TableSchema

    root = tempfile.mkdtemp(prefix="bench-serving-steady-")
    aot_dir = _os.path.join(root, "aot")

    def build_client(base):
        client = connect(base)
        schema = TableSchema.make(
            [("k", "int64", "ascending"), ("g", "int64"),
             ("v", "int64")], unique_keys=True)
        client.create("table", "//bench/steady",
                      attributes={"schema": schema, "dynamic": True,
                                  "pivot_keys": [[n_rows // 2]]},
                      recursive=True)
        client.mount_table("//bench/steady")
        for lo in range(0, n_rows, 50_000):
            hi = min(lo + 50_000, n_rows)
            client.insert_rows("//bench/steady",
                               [{"k": i, "g": i % 97, "v": i * 3}
                                for i in range(lo, hi)])
        client.freeze_table("//bench/steady")
        return client

    client = build_client(root)
    shapes = [
        "k, v FROM [//bench/steady] WHERE k = {}",
        "g, sum(v) AS s FROM [//bench/steady] WHERE v < {} GROUP BY g",
        "k, v FROM [//bench/steady] WHERE k > {} "
        "ORDER BY k LIMIT 10",
    ]
    # Fresh-constant mix: drawn over the whole key domain (Zipf-ish
    # skew via synthesize_mix), so with n_rows >> count virtually every
    # query carries a constant the fleet has never seen — the traffic
    # shape that makes per-constant fingerprints recompile forever.
    records = wl.synthesize_mix(shapes, count=240, distinct=n_rows,
                                seed=11, interval=0.0)
    capture_path = _os.path.join(root, "capture.json")
    wl.write_capture(capture_path, records)
    records = wl.load_capture(capture_path)

    # Leg 0 — pre-PR baseline: per-constant fingerprints (60-query
    # slice; every fresh constant compiles, so keep the burn bounded).
    yt_config.set_compile_config(
        yt_config.CompileConfig(parameterize=False))
    base_report = wl.replay(client, records[:60], rate=400.0,
                            max_workers=8)
    base_cache = base_report["compile_cache"]

    # Leg 1 — parameterized + persistent artifact tier (the metric).
    # One warmup slice compiles the bounded program set (shape x pow2
    # buckets); the measured replay then serves ~240 distinct constants
    # from it.
    yt_config.set_compile_config(yt_config.CompileConfig(
        parameterize=True, disk_cache_dir=aot_dir))
    from ytsaurus_tpu.query.engine.evaluator import (
        get_compile_observatory,
    )
    obs = get_compile_observatory()
    obs.reset()
    wl.replay(client, records[:60], rate=400.0, max_workers=8)
    best = None
    times = []
    while _iters_left(times, iters):
        t0 = time.perf_counter()
        report = wl.replay(client, records, rate=400.0, max_workers=8)
        times.append(time.perf_counter() - t0)
        if best is None or report["achieved_rate"] > \
                best["achieved_rate"]:
            best = report
    cache = best["compile_cache"]
    steady_rate = cache["steady_hit_rate"] or 0.0
    assert best["ok"] == best["queries"], best
    assert steady_rate >= 0.99, \
        f"steady-state hit rate {steady_rate:.4f} < 0.99"
    # Shape-spectrum acceptance: per fingerprint, the distinct
    # (capacity, binding-shape) programs stay pow2-bounded — 240
    # distinct constants must NOT widen the spectrum.
    spectrum = {r["fingerprint"]: r["shape_count"] for r in obs.top(0)}
    assert spectrum and max(spectrum.values()) <= 8, spectrum

    # Leg 2 — restart warm start: a fresh PROCESS, same artifacts.
    child_src = f"""
import json, sys
from ytsaurus_tpu import config as yt_config
yt_config.set_compile_config(yt_config.CompileConfig(
    parameterize=True, disk_cache_dir={aot_dir!r}))
sys.argv = ["child"]
import bench
client = bench.bench_serving_steady_child({root!r}, {n_rows})
"""
    env = dict(_os.environ, JAX_PLATFORMS=_os.environ.get(
        "JAX_PLATFORMS", "cpu"), BENCH_CHILD="1")
    proc = _subprocess.run(
        [sys.executable, "-c", child_src],
        cwd=_os.path.dirname(_os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    child = json.loads(
        [ln for ln in proc.stdout.splitlines()
         if ln.startswith("{")][-1])
    print(f"# serving_steady: baseline steady hit rate "
          f"{(base_cache['steady_hit_rate'] or 0) * 100:.1f}% "
          f"({base_cache['misses']} compiles) -> parameterized "
          f"{steady_rate * 100:.1f}% ({cache['misses']} misses, "
          f"{cache['fresh_compiles']} fresh); restart leg: "
          f"{child['disk_hits']} disk hits, "
          f"{child['fresh_compiles']} fresh compiles, hit rate "
          f"{(child['hit_rate'] or 0) * 100:.1f}%; "
          f"p99 {best['latency']['p99_ms']:.2f}ms",
          file=sys.stderr)
    assert child["fresh_compiles"] <= 1, child
    assert child["disk_hits"] >= 1, child
    return ("serving_steady_queries_per_sec", best["achieved_rate"],
            best["elapsed_seconds"])


def bench_serving_steady_child(parent_root, n_rows):
    """Restart-warm-start leg of bench_serving_steady, run in a FRESH
    process: rebuild the same table from the same row recipe, replay
    the same capture against the same AOT artifact directory, report
    the compile-cache split as one JSON line."""
    import os as _os
    import tempfile

    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.query import workload as wl
    from ytsaurus_tpu.schema import TableSchema

    base = tempfile.mkdtemp(prefix="bench-steady-child-")
    client = connect(base)
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("g", "int64"), ("v", "int64")],
        unique_keys=True)
    client.create("table", "//bench/steady",
                  attributes={"schema": schema, "dynamic": True,
                              "pivot_keys": [[n_rows // 2]]},
                  recursive=True)
    client.mount_table("//bench/steady")
    for lo in range(0, n_rows, 50_000):
        hi = min(lo + 50_000, n_rows)
        client.insert_rows("//bench/steady",
                           [{"k": i, "g": i % 97, "v": i * 3}
                            for i in range(lo, hi)])
    client.freeze_table("//bench/steady")
    records = wl.load_capture(_os.path.join(parent_root,
                                            "capture.json"))
    report = wl.replay(client, records, rate=400.0, max_workers=8)
    cache = report["compile_cache"]
    print(json.dumps({
        "disk_hits": cache["disk_hits"],
        "fresh_compiles": cache["fresh_compiles"],
        "hit_rate": cache["hit_rate"],
        "ok": report["ok"], "queries": report["queries"],
    }), flush=True)
    return client


# Declared per-pool serving SLOs for bench_slo — what the report grades
# p50/p99 against (loose enough for shared CI hosts; the hard
# assertions are the RELATIVE isolation/degradation properties).
_SLO_TARGETS = {
    "prod": {"p50_ms": 100.0, "p99_ms": 500.0},
    "batch": {"p50_ms": 200.0, "p99_ms": 1000.0},
}


def bench_slo(n_rows, iters):
    """Overload-resilient multi-replica serving macro-bench (ISSUE 17):
    the PR 7 open-loop replay mix driven through >= 2 serving replicas
    (each its own cluster + gateway + real HTTP /serving endpoint) via
    the load-aware ReplicaRouter, reporting p50/p99/p999 per pool
    against the declared SLOs.  Five legs:

      baseline   prod + batch mixed at moderate rate; per-pool
                 percentiles recorded (the metric: achieved qps);
      storm      the batch tenant goes greedy (open-loop flood) while
                 prod holds its baseline rate — acceptance: batch p99
                 moves >= 5x its own baseline while prod p99 stays
                 within 1.3x (fair-share isolation), and the brown-out
                 ladder ENGAGES under the storm and DISENGAGES after
                 it drains (rung transitions on /serving);
      join-hot   a THIRD replica built mid-bench joins the router
                 while the mix runs — acceptance: it serves load with
                 ZERO fresh compiles (every program fetched from the
                 cluster AOT artifact store its peers published to);
      control    a fixed chaos-mix replayed fault-free, per-query
                 result digests recorded;
      chaos      the same mix under injected faults (replica death
                 mid-run, routing-scrape failures, artifact-fetch
                 failures) — acceptance: zero lost/duplicated
                 responses, every result digest bit-identical to the
                 fault-free control run."""
    import hashlib
    import os as _os
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from ytsaurus_tpu import config as yt_config
    from ytsaurus_tpu.chunks.store import FsChunkStore
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.config import ServingConfig
    from ytsaurus_tpu.errors import EErrorCode, YtError
    from ytsaurus_tpu.query import workload as wl
    from ytsaurus_tpu.query.engine import aot_cache
    from ytsaurus_tpu.query.routing import ReplicaRouter, RoutedYtClient
    from ytsaurus_tpu.schema import TableSchema
    from ytsaurus_tpu.server.monitoring import MonitoringServer
    from ytsaurus_tpu.utils import failpoints

    root = tempfile.mkdtemp(prefix="bench-slo-")
    # The compile ladder under test: memory -> CLUSTER artifact store
    # (shared blob store, what lets a replica join hot).  The process-
    # global DISK tier stays off — it would hide cluster fetches.
    yt_config.set_compile_config(yt_config.CompileConfig(
        parameterize=True))
    artifact_store = aot_cache.ClusterArtifactStore(
        FsChunkStore(_os.path.join(root, "artifacts")))
    aot_cache.set_cluster_store(artifact_store)

    def serving_config():
        # Tight slots so admission (not raw capacity) shapes latency,
        # and a HARD cap on batch (pool_limits) so the greedy tenant's
        # executing footprint — the thing that contends for CPU with
        # prod — can never exceed 1 slot per replica no matter how
        # idle the rest of the box looks (work-conserving fair share
        # alone would hand it the free slots, and on a shared-CPU host
        # that IS the neighbor's p99).  Deep queue so the storm
        # measures queueing, not rejections; rung-1 threshold above
        # baseline pressure but far below the storm's; rung 2 out of
        # reach so shedding doesn't mask the p99 movement.
        return ServingConfig(
            slots=2, max_queue=10_000, default_pool="prod",
            pools={"prod": 3.0, "batch": 1.0},
            pool_limits={"batch": 1},
            brownout_rung1_seconds=0.4, brownout_rung2_seconds=120.0,
            brownout_min_dwell_seconds=0.5,
            default_staleness_seconds=30.0)

    class _Handle:
        """One replica as the router sees it: a select_rows endpoint
        with a kill switch (simulated replica death) and per-replica
        compile accounting from each query's EXPLAIN ANALYZE stats."""

        def __init__(self, name, client):
            self.name = name
            self.client = client
            self.dead = False
            self.lock = threading.Lock()
            self.served = 0
            self.compile_count = 0
            self.cluster_hits = 0

        def select_rows(self, query, pool=None, timeout=None):
            if self.dead:
                raise YtError(f"replica {self.name} is down",
                              code=EErrorCode.TransportError)
            profile = self.client.select_rows(
                query, pool=pool, timeout=timeout, explain_analyze=True)
            stats = profile.statistics or {}
            with self.lock:
                self.served += 1
                self.compile_count += int(stats.get("compile_count", 0))
                self.cluster_hits += \
                    int(stats.get("compile_cluster_hit", 0))
            return profile.rows

    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("g", "int64"), ("v", "int64")],
        unique_keys=True)

    def make_replica(name):
        client = connect(_os.path.join(root, name))
        client.cluster.serving_config = serving_config()
        client.create("table", "//slo/t",
                      attributes={"schema": schema, "dynamic": True,
                                  "pivot_keys": [[n_rows // 2]]},
                      recursive=True)
        client.mount_table("//slo/t")
        for lo in range(0, n_rows, 50_000):
            hi = min(lo + 50_000, n_rows)
            client.insert_rows("//slo/t",
                               [{"k": i, "g": i % 53, "v": i * 3}
                                for i in range(lo, hi)])
        client.freeze_table("//slo/t")
        monitoring = MonitoringServer()
        monitoring.serving_gateways = [client.cluster.gateway]
        monitoring.start()
        return {"name": name, "client": client,
                "gateway": client.cluster.gateway,
                "monitoring": monitoring,
                "handle": _Handle(name, client)}

    replicas = [make_replica("replica-0"), make_replica("replica-1")]
    router = ReplicaRouter(
        [(r["name"], r["name"], r["monitoring"].address)
         for r in replicas],
        scrape_period=0.2, penalty_seconds=1.0)
    routed = RoutedYtClient(
        router, {r["name"]: r["handle"] for r in replicas})
    router.start()

    shapes = [
        "k, v FROM [//slo/t] WHERE k = {}",
        "g, sum(v) AS s FROM [//slo/t] WHERE v < {} GROUP BY g",
        "k, v FROM [//slo/t] WHERE k > {} ORDER BY k LIMIT 10",
    ]

    def mix(count, pool, seed, rate, start=0.0):
        records = wl.synthesize_mix(shapes, count=count, distinct=64,
                                    seed=seed, pool=pool)
        for i, rec in enumerate(records):
            rec.started_at = start + i / rate
        return records

    def drive(records, timeout=120.0, max_workers=None):
        """Open-loop replay through the routed client: dispatch on each
        record's schedule, never waiting for completions; one result
        slot per record (lost/duplicated responses are structurally
        visible).  The worker pool is sized to the record count so a
        greedy pool's backlog can never starve another pool's DISPATCH
        — starving its admission is the system under test's job."""
        records = sorted(records, key=lambda r: r.started_at)
        results = [None] * len(records)
        if max_workers is None:
            max_workers = len(records) + 4

        def run_one(i, rec):
            t0 = time.perf_counter()
            try:
                rows = routed.select_rows(
                    wl.substitute_literals(rec.query, rec.literals),
                    pool=rec.pool, timeout=timeout)
                outcome, digest = "ok", hashlib.sha1(
                    json.dumps(rows, sort_keys=True,
                               default=str).encode()).hexdigest()
            except YtError as err:
                outcome, digest = wl.outcome_of(err), None
            results[i] = {"pool": rec.pool, "outcome": outcome,
                          "digest": digest,
                          "latency": time.perf_counter() - t0}

        t_start = time.perf_counter()
        with ThreadPoolExecutor(max_workers=max_workers,
                                thread_name_prefix="slo") as pool:
            for i, rec in enumerate(records):
                delay = t_start + rec.started_at - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                pool.submit(run_one, i, rec)
        elapsed = time.perf_counter() - t_start
        return results, elapsed

    def percentiles(results, pool):
        lat = sorted(r["latency"] for r in results
                     if r and r["pool"] == pool and r["outcome"] == "ok")
        if not lat:
            return {"p50_ms": 0.0, "p99_ms": 0.0, "p999_ms": 0.0,
                    "ok": 0}
        def pct(q):
            return round(
                lat[min(int(q * len(lat)), len(lat) - 1)] * 1e3, 3)
        return {"p50_ms": pct(0.50), "p99_ms": pct(0.99),
                "p999_ms": pct(0.999), "ok": len(lat)}

    def brownout_view():
        return {r["name"]:
                r["gateway"].snapshot()["admission"]["brownout"]
                for r in replicas}

    # -- warmup: every shape compiles once per replica (replica-0 first
    # so its publishes seed the artifact store; replica-1's misses then
    # exercise fetch-on-miss before any measured leg).
    warm = wl.synthesize_mix(shapes, count=12, distinct=64, seed=7)
    for r in replicas:
        for rec in warm:
            r["handle"].select_rows(
                wl.substitute_literals(rec.query, rec.literals),
                pool="prod", timeout=30.0)

    # -- calibration: rates scale to THIS host's measured service time
    # (CI boxes span an order of magnitude).  The key design point on
    # a shared-CPU host: the baseline keeps batch's fair-share slots
    # BUSY, so the storm changes only batch's queue depth — its
    # executing footprint (the thing that could slow prod down) is
    # identical in both phases.  That is precisely the isolation
    # fair-share admission promises.
    t_cal = time.perf_counter()
    cal = wl.synthesize_mix(shapes, count=16, distinct=64, seed=9)
    for rec in cal:
        replicas[0]["handle"].select_rows(
            wl.substitute_literals(rec.query, rec.literals),
            pool="prod", timeout=30.0)
    service = (time.perf_counter() - t_cal) / len(cal)
    cap = 1.0 / service            # sequential host capacity, qps
    # Prod's worst-case share under a batch storm is ~cap/2 (batch is
    # hard-capped at 1 of 2 slots per replica); offering prod at
    # cap/4 leaves a 2x margin over calibration noise, so prod never
    # queues structurally in EITHER leg and its p99 measures pure
    # contention — which the design makes identical across legs.
    prod_n = 120
    prod_rate = cap * 0.25
    prod_span = prod_n / prod_rate      # seconds the prod probe runs
    # Batch's real drain rate is NOT derivable from sequential service
    # time (slot caps, cross-replica contention, and scheduler overhead
    # all cut into it) — measure it: burst a cohort through the routed
    # path with prod idle and time the drain.  Everything downstream is
    # sized from this number, so the leg shapes are host-independent.
    burst = mix(max(int(cap * 1.5), 30), "batch", seed=10,
                rate=cap * 50.0)
    burst_results, burst_elapsed = drive(burst)
    batch_drain = len(burst_results) / burst_elapsed    # qps, measured
    # Offered slightly above the measured drain rate FOR PROD'S WHOLE
    # SPAN, so batch's capped executing footprint is saturated in the
    # baseline exactly as it will be under the storm — the storm then
    # moves only batch's own queue, which is the isolation being
    # proven.  (A batch cohort that drains before prod finishes would
    # leave the baseline's tail uncontended and inflate the measured
    # prod move; a grossly over-offered one would pre-build a storm-
    # sized queue and deflate the batch move.)  The burst above ran
    # with prod IDLE; during the legs prod occupies ~prod_rate*service
    # = 0.25 of the core, so batch's effective drain is ~0.75x the
    # measured one — offer against THAT.
    base_batch_rate = batch_drain * 0.75 * 1.10
    base_batch_n = max(int(base_batch_rate * (prod_span + 2.0)), 40)
    storm_rate = cap * 6.0              # the greedy tenant's flood
    # Enough storm queries that the backlog outlives prod's span at
    # the measured drain rate: every prod sample sees the storm, and
    # batch's own queue wait lands near 2x prod_span vs the baseline's
    # ~0.1x — a p99 move of well over 5x by construction, with enough
    # slack that drain-rate measurement noise (which leaks into the
    # baseline's queue growth) can't drag the ratio under the bar.
    storm_batch_n = max(int(batch_drain * prod_span * 2.0), 150)
    batch_cap = cap * 0.25              # nominal share, for reporting

    def settle():
        """Wait for every replica's brown-out ladder to walk back to
        rung 0 (the snapshot read itself drives de-escalation)."""
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            rungs = {name: v["rung"]
                     for name, v in brownout_view().items()}
            if all(r == 0 for r in rungs.values()):
                return rungs
            time.sleep(0.3)
        return rungs

    # -- leg 1: baseline (the metric) ------------------------------------------
    base = None
    times = []
    while _iters_left(times, iters):
        records = mix(prod_n, "prod", seed=21, rate=prod_rate) + \
            mix(base_batch_n, "batch", seed=22,
                rate=base_batch_rate)
        results, elapsed = drive(records)
        times.append(elapsed)
        report = {"results": results, "elapsed": elapsed,
                  "prod": percentiles(results, "prod"),
                  "batch": percentiles(results, "batch")}
        if base is None or elapsed < base["elapsed"]:
            base = report
    lost = [r for r in base["results"] if r is None or
            r["outcome"] != "ok"]
    assert not lost, f"baseline lost/failed {len(lost)} responses"
    baseline_rate = len(base["results"]) / base["elapsed"]

    # -- leg 2: greedy-tenant storm + brown-out ladder -------------------------
    settle()
    engaged_before = sum(v["engaged"] for v in brownout_view().values())
    storm_records = mix(prod_n, "prod", seed=21, rate=prod_rate) + \
        mix(storm_batch_n, "batch", seed=31, rate=storm_rate)
    storm_results, _ = drive(storm_records, timeout=300.0)
    storm_prod = percentiles(storm_results, "prod")
    storm_batch = percentiles(storm_results, "batch")
    print(f"# slo storm: prod {base['prod']} -> {storm_prod} | "
          f"batch {base['batch']} -> {storm_batch}", file=sys.stderr)
    prod_failed = [r for r in storm_results
                   if r and r["pool"] == "prod" and r["outcome"] != "ok"]
    assert not prod_failed, \
        f"prod lost {len(prod_failed)} responses during the storm"
    batch_move = storm_batch["p99_ms"] / max(base["batch"]["p99_ms"],
                                             1e-3)
    prod_move = storm_prod["p99_ms"] / max(base["prod"]["p99_ms"], 1e-3)
    assert batch_move >= 5.0, \
        f"greedy batch p99 moved only {batch_move:.2f}x " \
        f"({base['batch']['p99_ms']} -> {storm_batch['p99_ms']}ms)"
    assert prod_move <= 1.3, \
        f"neighbor prod p99 moved {prod_move:.2f}x " \
        f"({base['prod']['p99_ms']} -> {storm_prod['p99_ms']}ms)"
    after = brownout_view()
    engaged_after = sum(v["engaged"] for v in after.values())
    assert engaged_after > engaged_before, \
        f"brown-out never engaged under the storm: {after}"
    # Disengage on recovery: the storm has drained (drive returned),
    # so after the dwell every replica's ladder must walk back to 0.
    rungs = settle()
    assert all(r == 0 for r in rungs.values()), \
        f"brown-out failed to disengage after recovery: {rungs}"

    # -- leg 3: replica joins hot mid-bench ------------------------------------
    joiner = make_replica("replica-2")
    join_records = mix(120, "prod", seed=41, rate=prod_rate) + \
        mix(50, "batch", seed=42, rate=batch_cap * 0.6)
    join_out = {}

    def run_join_mix():
        join_out["results"], _ = drive(join_records)

    mixer = threading.Thread(target=run_join_mix, daemon=True)
    mixer.start()
    time.sleep(0.8)                        # the mix is mid-flight
    routed.add_replica((joiner["name"], joiner["name"],
                        joiner["monitoring"].address),
                       joiner["handle"])
    replicas.append(joiner)
    mixer.join(timeout=120)
    assert not mixer.is_alive(), "join-hot mix did not complete"
    handle = joiner["handle"]
    assert handle.served > 0, "joining replica was never routed to"
    assert handle.compile_count > 0, \
        "joining replica never loaded a program (mix too small?)"
    fresh = handle.compile_count - handle.cluster_hits
    assert fresh == 0, \
        f"joining replica fresh-compiled {fresh} programs " \
        f"(cluster store should have served them all)"
    join_lost = [r for r in join_out["results"]
                 if r is None or r["outcome"] != "ok"]
    assert not join_lost, \
        f"join-hot leg lost {len(join_lost)} responses"

    # -- legs 4+5: chaos vs fault-free control ---------------------------------
    def chaos_mix():
        return mix(80, "prod", seed=51, rate=prod_rate) + \
            mix(40, "batch", seed=52, rate=batch_cap * 0.5)

    control_results, _ = drive(chaos_mix())
    control = [r["digest"] for r in control_results]
    assert all(r is not None and r["outcome"] == "ok"
               for r in control_results), "control run lost responses"

    failovers_before = router.failovers_n
    by_name = {r["name"]: r for r in replicas}
    victim_cell = []

    def kill_victim():
        time.sleep(1.0)                    # mid-run, not at the edges
        # Kill the replica the router currently FAVORS for prod: pool-
        # aware scoring sends light traffic almost deterministically to
        # the best-scored replica, so killing any OTHER one could sail
        # through the whole leg unpicked and never exercise failover.
        # Favored + dead + monitoring still up reporting an EMPTY queue
        # = traffic keeps landing on the corpse — the failover +
        # quarantine path, not just routing around a pre-flagged peer.
        victim = by_name[router.pick(pool="prod").name]
        victim_cell.append(victim)
        victim["handle"].dead = True       # calls now fail hard...
        # The window spans many scrape periods because the chaos
        # failpoint (`serving.route_scrape=error:p=0.3`) intermittently
        # penalizes the victim into un-pickability; a short window can
        # flakily miss every pick.  Then the endpoint dies too.
        time.sleep(2.0)
        victim["monitoring"].stop()
    killer = threading.Thread(target=kill_victim, daemon=True)
    killer.start()
    with failpoints.active(
            "serving.route_scrape=error:p=0.3;aot.fetch=error:p=0.5",
            seed=17):
        chaos_results, _ = drive(chaos_mix(), timeout=60.0)
    killer.join(timeout=10)
    chaos_lost = [i for i, r in enumerate(chaos_results)
                  if r is None or r["outcome"] != "ok"]
    assert not chaos_lost, \
        f"chaos leg lost {len(chaos_lost)} responses: {chaos_lost[:5]}"
    mismatched = [i for i, r in enumerate(chaos_results)
                  if r["digest"] != control[i]]
    assert not mismatched, \
        f"chaos results diverge from fault-free control at " \
        f"{mismatched[:5]}"
    assert router.failovers_n > failovers_before, \
        "replica death never triggered a failover"

    routing = router.snapshot()
    router.stop()
    victim = victim_cell[0] if victim_cell else None
    for r in replicas:
        if r is not victim:
            r["monitoring"].stop()
    aot_cache.set_cluster_store(None)
    yt_config.set_compile_config(None)

    def grade(pool):
        slo = _SLO_TARGETS[pool]
        got = base[pool]
        return {**got, "slo": slo,
                "met": got["p50_ms"] <= slo["p50_ms"] and
                       got["p99_ms"] <= slo["p99_ms"]}

    print(json.dumps({
        "baseline": {"prod": grade("prod"), "batch": grade("batch"),
                     "achieved_qps": round(baseline_rate, 1)},
        "storm": {"prod": storm_prod, "batch": storm_batch,
                  "batch_p99_move": round(batch_move, 2),
                  "prod_p99_move": round(prod_move, 2),
                  "brownout": after},
        "join_hot": {"served": handle.served,
                     "cluster_hits": handle.cluster_hits,
                     "fresh_compiles": fresh},
        "chaos": {"queries": len(chaos_results), "lost": 0,
                  "mismatched": 0,
                  "failovers": router.failovers_n - failovers_before},
        "artifact_store": artifact_store.snapshot(),
        "routing": {k: v for k, v in routing.items()
                    if k != "replicas"},
    }, indent=2), file=sys.stderr, flush=True)
    return ("slo_baseline_queries_per_sec", baseline_rate,
            base["elapsed"])


def bench_whole_plan(n_rows, iters):
    """Whole-plan fused SPMD execution (ISSUE 12): q1/groupby-class
    plans on the virtual 8-device CPU mesh, three legs per plan —

      stitched-shuffle  CompileConfig.whole_plan OFF, prefer_shuffle
                        (the pre-PR default ladder rung: count program
                        + quota host-sync + exchange program)
      stitched-gather   whole_plan OFF, gather-merge rung
      fused             whole_plan ON: ONE jit(shard_map) program, one
                        final stacked host transfer

    The mesh legs run in a CHILD process (the bench parent is a
    single-device backend; the child forces 8 virtual CPU devices).
    Acceptance: fused ≥2× the BEST stitched rung for both plan classes
    and exactly 1 host sync per fused query (the stitched rungs pay 2).
    Metric is the fused groupby-class throughput."""
    import subprocess as _subprocess

    child_src = f"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, time
import numpy as np
from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.chunks.columnar import ColumnarChunk
from ytsaurus_tpu.parallel.mesh import make_mesh
from ytsaurus_tpu.parallel.distributed import (
    DistributedEvaluator, coordinate_distributed, host_sync_count)
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.statistics import QueryStatistics
from ytsaurus_tpu.schema import TableSchema

N = {n_rows}
ITERS = {max(int(iters), 3)}
mesh = make_mesh(8)
rng = np.random.default_rng(1)
per = N // 8

gb_schema = TableSchema.make([("k", "int64", "ascending"),
                              ("g", "int64"), ("v", "int64")])
# Group domain scales with N (~100 rows per group) so smoke-sized runs
# keep the same rows:groups ratio as the default config.
n_groups = max(64, N // 100)
gb_chunks = [ColumnarChunk.from_arrays(gb_schema, {{
    "k": np.arange(per) + s * per,
    "g": rng.integers(0, n_groups, per),
    "v": rng.integers(0, 1000, per)}}) for s in range(8)]
gb_plan = build_query(
    "g, sum(v) AS s, count(*) AS c FROM [//t] GROUP BY g",
    {{"//t": gb_schema}})

q1_schema = TableSchema.make([("rf", "int64"), ("ls", "int64"),
                              ("qty", "double"), ("price", "double")])
q1_chunks = [ColumnarChunk.from_arrays(q1_schema, {{
    "rf": rng.integers(0, 3, per), "ls": rng.integers(0, 2, per),
    "qty": rng.uniform(1, 50, per),
    "price": rng.uniform(1, 1e5, per)}}) for s in range(8)]
q1_plan = build_query(
    "rf, ls, sum(qty) AS sq, sum(price) AS sp, avg(qty) AS aq, "
    "avg(price) AS ap, count(*) AS c FROM [//t] GROUP BY rf, ls",
    {{"//t": q1_schema}})


def leg(plan, chunks, whole, prefer_shuffle=True):
    yt_config.set_compile_config(
        yt_config.CompileConfig(whole_plan=whole))
    de = DistributedEvaluator(mesh)
    stats = QueryStatistics()
    out = coordinate_distributed(plan, mesh, chunks, evaluator=de,
                                 prefer_shuffle=prefer_shuffle,
                                 stats=stats)                  # warm-up
    times = []
    s0 = host_sync_count()
    for _ in range(ITERS):
        t0 = time.perf_counter()
        out = coordinate_distributed(plan, mesh, chunks, evaluator=de,
                                     prefer_shuffle=prefer_shuffle)
        np.asarray(next(iter(out.columns.values())).data[:1])
        times.append(time.perf_counter() - t0)
    return {{"best_s": min(times),
             "syncs_per_query": (host_sync_count() - s0) / ITERS,
             "whole_plan": stats.whole_plan, "rows": out.row_count}}


report = {{}}
for name, plan, chunks in (("groupby", gb_plan, gb_chunks),
                           ("q1", q1_plan, q1_chunks)):
    report[name] = {{
        "stitched_shuffle": leg(plan, chunks, False, True),
        "stitched_gather": leg(plan, chunks, False, False),
        "fused": leg(plan, chunks, True),
    }}
print("REPORT " + json.dumps(report), flush=True)
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = _subprocess.run(
        [sys.executable, "-c", child_src],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=3000, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    report = json.loads(
        [ln for ln in proc.stdout.splitlines()
         if ln.startswith("REPORT ")][-1][len("REPORT "):])
    for name, legs in report.items():
        fused = legs["fused"]
        best_stitched = min(legs["stitched_shuffle"]["best_s"],
                            legs["stitched_gather"]["best_s"])
        speedup = best_stitched / fused["best_s"]
        print(f"# whole_plan {name}: stitched-shuffle "
              f"{legs['stitched_shuffle']['best_s']*1e3:.0f}ms "
              f"({legs['stitched_shuffle']['syncs_per_query']:.0f} "
              f"syncs/query), stitched-gather "
              f"{legs['stitched_gather']['best_s']*1e3:.0f}ms, fused "
              f"{fused['best_s']*1e3:.0f}ms "
              f"({fused['syncs_per_query']:.0f} sync/query, "
              f"{n_rows / fused['best_s']:.0f} rows/s) -> "
              f"{speedup:.2f}x vs best stitched rung", file=sys.stderr)
        assert fused["whole_plan"] == 1, name
        assert fused["syncs_per_query"] == 1.0, \
            f"{name}: fused path must host-sync exactly once per query"
        assert legs["stitched_shuffle"]["syncs_per_query"] >= 2.0, name
        assert speedup >= 2.0, \
            (f"{name}: fused {fused['best_s']:.3f}s not >=2x best "
             f"stitched {best_stitched:.3f}s")
    best = report["groupby"]["fused"]["best_s"]
    return "whole_plan_rows_per_sec", n_rows / best, best


def bench_mesh_overhead(n_rows, iters):
    """Mesh telemetry overhead (ISSUE 20): the fused whole-plan rung
    with the in-program telemetry block disarmed vs armed, for the
    round-8 groupby and q1 plan shapes on the virtual 8-device mesh.

    The armed program appends its telemetry lanes (per-shard rows,
    transfer matrices, quota demand) onto the SAME stacked final
    transfer, so arming must cost neither a host sync nor measurable
    wall time.  The ≤1% claim is asserted as a deterministic
    decomposition (the bench_telemetry_overhead discipline — a direct
    A/B on a noisy shared host cannot resolve 1%): exactly 1 host sync
    per query on BOTH legs (the telemetry's whole device cost rides a
    transfer the query already pays), and the per-query host
    decode+publish cost — measured as a per-site microbench — must be
    ≤1% of the disarmed query wall.  The armed/disarmed A/B delta is
    still measured and printed for the record, with a loose 1.5×
    outlier guard against a genuinely broken armed program.  Metric is
    the armed groupby-class throughput."""
    import subprocess as _subprocess

    child_src = f"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, time
import numpy as np
from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.chunks.columnar import ColumnarChunk
from ytsaurus_tpu.parallel.mesh import make_mesh
from ytsaurus_tpu.parallel.distributed import (
    DistributedEvaluator, coordinate_distributed, host_sync_count)
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.statistics import QueryStatistics
from ytsaurus_tpu.schema import TableSchema

N = {n_rows}
ITERS = {max(int(iters), 3)}
mesh = make_mesh(8)
rng = np.random.default_rng(1)
per = N // 8

gb_schema = TableSchema.make([("k", "int64", "ascending"),
                              ("g", "int64"), ("v", "int64")])
n_groups = max(64, N // 100)
gb_chunks = [ColumnarChunk.from_arrays(gb_schema, {{
    "k": np.arange(per) + s * per,
    "g": rng.integers(0, n_groups, per),
    "v": rng.integers(0, 1000, per)}}) for s in range(8)]
gb_plan = build_query(
    "g, sum(v) AS s, count(*) AS c FROM [//t] GROUP BY g",
    {{"//t": gb_schema}})

q1_schema = TableSchema.make([("rf", "int64"), ("ls", "int64"),
                              ("qty", "double"), ("price", "double")])
q1_chunks = [ColumnarChunk.from_arrays(q1_schema, {{
    "rf": rng.integers(0, 3, per), "ls": rng.integers(0, 2, per),
    "qty": rng.uniform(1, 50, per),
    "price": rng.uniform(1, 1e5, per)}}) for s in range(8)]
q1_plan = build_query(
    "rf, ls, sum(qty) AS sq, sum(price) AS sp, avg(qty) AS aq, "
    "avg(price) AS ap, count(*) AS c FROM [//t] GROUP BY rf, ls",
    {{"//t": q1_schema}})

yt_config.set_compile_config(yt_config.CompileConfig(whole_plan=True))


def leg(plan, chunks, armed):
    yt_config.set_telemetry_config(
        yt_config.TelemetryConfig(mesh_telemetry=armed))
    de = DistributedEvaluator(mesh)
    stats = QueryStatistics()
    out = coordinate_distributed(plan, mesh, chunks, evaluator=de,
                                 stats=stats)                  # warm-up
    times = []
    s0 = host_sync_count()
    for _ in range(ITERS):
        t0 = time.perf_counter()
        out = coordinate_distributed(plan, mesh, chunks, evaluator=de)
        np.asarray(next(iter(out.columns.values())).data[:1])
        times.append(time.perf_counter() - t0)
    return {{"best_s": min(times),
             "syncs_per_query": (host_sync_count() - s0) / ITERS,
             "whole_plan": stats.whole_plan, "rows": out.row_count,
             "mesh_blocks": len(stats.mesh_blocks),
             "skew": stats.mesh_skew_max}}


report = {{}}
for name, plan, chunks in (("groupby", gb_plan, gb_chunks),
                           ("q1", q1_plan, q1_chunks)):
    report[name] = {{"off": leg(plan, chunks, False),
                     "on": leg(plan, chunks, True)}}

# Per-site microbench of the armed path's ENTIRE host-side addition:
# decode the appended lanes of a representative exchange-shape vector
# (n=8: version + 2x8 row lanes + the 64-cell transfer matrix) and fan
# the block out to stats + observatory + sensors.
from ytsaurus_tpu.parallel import whole_plan as wp
yt_config.set_telemetry_config(yt_config.TelemetryConfig())
vals = np.zeros(3 + 1 + 16 + 64, dtype=np.int64)
vals[3] = wp.MESH_TELEMETRY_VERSION
vals[4:12] = 1000
vals[12:20] = 900
vals[20:] = 100
decode_stats = QueryStatistics()

def decode_once():
    in_rows, out_rows, off = wp._mesh_slices(vals, 3, 8)
    entry = wp._mesh_exchange_entry("shuffle/bench", vals[off: off + 64],
                                    500, 512, 33)
    block = wp._mesh_block(8, in_rows, out_rows, [entry])
    wp._publish_mesh(decode_stats, "bench-fp", None, block)

decode_cost = float("inf")
for _ in range(5):
    t0 = time.perf_counter()
    for _ in range(2000):
        decode_once()
    decode_cost = min(decode_cost, (time.perf_counter() - t0) / 2000)
    decode_stats.mesh_blocks.clear()
report["decode_cost_s"] = decode_cost
print("REPORT " + json.dumps(report), flush=True)
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = _subprocess.run(
        [sys.executable, "-c", child_src],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=3000, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    report = json.loads(
        [ln for ln in proc.stdout.splitlines()
         if ln.startswith("REPORT ")][-1][len("REPORT "):])
    decode_cost = report.pop("decode_cost_s")
    print(f"# mesh_overhead decode+publish per query: "
          f"{decode_cost * 1e6:.1f} µs", file=sys.stderr)
    for name, legs in report.items():
        off, on = legs["off"], legs["on"]
        delta = on["best_s"] / off["best_s"] - 1.0
        print(f"# mesh_overhead {name}: disarmed "
              f"{off['best_s']*1e3:.1f}ms, armed {on['best_s']*1e3:.1f}ms "
              f"({delta*100:+.2f}% A/B, for the record), "
              f"{on['syncs_per_query']:.0f} sync/query armed, "
              f"{on['mesh_blocks']} blocks (skew {on['skew']:.3f})",
              file=sys.stderr)
        assert off["whole_plan"] == 1 and on["whole_plan"] == 1, name
        assert off["rows"] == on["rows"], name
        assert off["syncs_per_query"] == 1.0, \
            f"{name}: disarmed fused path must host-sync exactly once"
        assert on["syncs_per_query"] == 1.0, \
            f"{name}: ARMED fused path must still host-sync exactly " \
            f"once — telemetry rides the existing stacked transfer"
        assert on["mesh_blocks"] >= 1 and on["skew"] >= 1.0, \
            f"{name}: armed leg decoded no telemetry block"
        # The ≤1% budget, decomposed: the armed path's host-side
        # addition per query vs the disarmed query wall.
        assert decode_cost <= off["best_s"] * 0.01, \
            (f"{name}: telemetry decode+publish {decode_cost*1e6:.0f}µs "
             f"exceeds 1% of the disarmed query "
             f"({off['best_s']*1e3:.1f}ms)")
        assert on["best_s"] <= off["best_s"] * 1.5 + 0.1, \
            (f"{name}: armed leg {on['best_s']:.4f}s grossly over "
             f"disarmed {off['best_s']:.4f}s — the armed program is "
             f"broken, not noisy")
    best = report["groupby"]["on"]["best_s"]
    return "mesh_overhead_rows_per_sec", n_rows / best, best


def bench_multiway_join(n_rows, iters):
    """Fused multiway join + cost-based planner (ISSUE 14): TPC-H
    Q5/Q7-class 3-way join plans on the virtual 8-device CPU mesh,
    two legs per plan —

      cascade  CompileConfig.whole_plan OFF, the stitched binary
               cascade (`_run_partitioned`: per join a count program +
               quota host sync, a route+probe program + totals host
               sync, an expand program; then the stitched finish)
      fused    whole_plan ON: planner-ordered broadcast/partition joins
               inside ONE jit(shard_map) program — one host sync, the
               exchange/expansion quotas memoized

    Acceptance: fused ≥2× the cascade on both plans, exactly 1 host
    sync per fused query.  Metric is the fused Q5-class throughput
    (fact rows/s)."""
    import subprocess as _subprocess

    child_src = f"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, time
import numpy as np
from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.chunks.columnar import ColumnarChunk
from ytsaurus_tpu.parallel.mesh import make_mesh
from ytsaurus_tpu.parallel.distributed import (
    DistributedEvaluator, coordinate_distributed, host_sync_count)
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.statistics import QueryStatistics
from ytsaurus_tpu.schema import TableSchema

N = {n_rows}
ITERS = {max(int(iters), 3)}
mesh = make_mesh(8)
rng = np.random.default_rng(14)
per = N // 8

# TPC-H-class star: lineitem fact, orders (fact-adjacent, too big to
# broadcast -> partition exchange), customer + nation (broadcast dims).
n_orders = max(N // 4, 70_000)      # above broadcast_join_rows
n_cust = 10_000
nations = [f"nation{{i:02d}}" for i in range(25)]
li_schema = TableSchema.make([("l_ok", "int64"), ("l_sk", "int64"),
                              ("price", "double")])
o_schema = TableSchema.make([("o_ok", "int64"), ("o_ck", "int64")])
c_schema = TableSchema.make([("c_ck", "int64"), ("c_nk", "int64")])
n_schema = TableSchema.make([("n_nk", "int64"), ("n_name", "string")])
s_schema = TableSchema.make([("s_sk", "int64"), ("s_nk", "int64")])

li_chunks = [ColumnarChunk.from_arrays(li_schema, {{
    "l_ok": rng.integers(0, n_orders, per),
    "l_sk": rng.integers(0, 1000, per),
    "price": rng.uniform(1, 1e4, per)}}) for s in range(8)]
orders = ColumnarChunk.from_arrays(o_schema, {{
    "o_ok": np.arange(n_orders),
    "o_ck": rng.integers(0, n_cust, n_orders)}})
customer = ColumnarChunk.from_arrays(c_schema, {{
    "c_ck": np.arange(n_cust), "c_nk": rng.integers(0, 25, n_cust)}})
nation = ColumnarChunk.from_rows(
    n_schema, [(i, nations[i]) for i in range(25)])
supplier = ColumnarChunk.from_arrays(s_schema, {{
    "s_sk": np.arange(1000), "s_nk": rng.integers(0, 25, 1000)}})
schemas = {{"//li": li_schema, "//o": o_schema, "//c": c_schema,
           "//n": n_schema, "//s": s_schema}}
foreign = {{"//o": orders, "//c": customer, "//n": nation,
           "//s": supplier}}

# Q5 class: 4-way chain through orders -> customer -> nation.
q5 = build_query(
    "n_name, sum(price) AS rev, count(*) AS c FROM [//li] "
    "JOIN [//o] ON l_ok = o_ok JOIN [//c] ON o_ck = c_ck "
    "JOIN [//n] ON c_nk = n_nk GROUP BY n_name "
    "ORDER BY n_name LIMIT 32", schemas)
# Q7 class: supplier-side 3-way.
q7 = build_query(
    "n_name, sum(price) AS rev FROM [//li] "
    "JOIN [//s] ON l_sk = s_sk JOIN [//n] ON s_nk = n_nk "
    "GROUP BY n_name ORDER BY n_name LIMIT 32", schemas)


from ytsaurus_tpu.parallel.distributed import ShardedTable
table = ShardedTable.from_chunks(mesh, li_chunks)


def leg(plan, mode):
    # cascade   the stitched binary cascade (_run_partitioned: count/
    #           probe/expand programs + 2 host syncs PER join) — the
    #           pre-ISSUE-14 multiway shape the acceptance compares to
    # stitched  whole_plan OFF through the ladder (broadcast-gather
    #           rung when every dim proves unique keys)
    # fused     whole_plan ON: one program, one sync
    yt_config.set_compile_config(
        yt_config.CompileConfig(whole_plan=(mode == "fused")))
    de = DistributedEvaluator(mesh)
    stats = QueryStatistics()

    def run_once(stats=None):
        if mode == "cascade":
            return de.run(plan, table, foreign, shuffle=True)
        return coordinate_distributed(plan, mesh, li_chunks, foreign,
                                      evaluator=de, stats=stats)

    out = run_once(stats)                                    # warm-up
    times = []
    s0 = host_sync_count()
    for _ in range(ITERS):
        t0 = time.perf_counter()
        out = run_once()
        np.asarray(next(iter(out.columns.values())).data[:1])
        times.append(time.perf_counter() - t0)
    return {{"best_s": min(times),
             "syncs_per_query": (host_sync_count() - s0) / ITERS,
             "whole_plan": stats.whole_plan, "rows": out.row_count,
             "join_plan": stats.join_plan}}


report = {{}}
for name, plan in (("q5", q5), ("q7", q7)):
    report[name] = {{"cascade": leg(plan, "cascade"),
                     "stitched": leg(plan, "stitched"),
                     "fused": leg(plan, "fused")}}
print("REPORT " + json.dumps(report), flush=True)
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = _subprocess.run(
        [sys.executable, "-c", child_src],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=3000, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    report = json.loads(
        [ln for ln in proc.stdout.splitlines()
         if ln.startswith("REPORT ")][-1][len("REPORT "):])
    for name, legs in report.items():
        fused = legs["fused"]
        cascade = legs["cascade"]
        stitched = legs["stitched"]
        speedup = cascade["best_s"] / fused["best_s"]
        strategies = [e["strategy"] for e in fused["join_plan"] if e]
        print(f"# multiway_join {name}: cascade "
              f"{cascade['best_s']*1e3:.0f}ms "
              f"({cascade['syncs_per_query']:.0f} syncs/query), "
              f"stitched-gather {stitched['best_s']*1e3:.0f}ms "
              f"({stitched['syncs_per_query']:.0f}), fused "
              f"{fused['best_s']*1e3:.0f}ms "
              f"({fused['syncs_per_query']:.0f} sync/query, "
              f"strategies {strategies}, "
              f"{n_rows / fused['best_s']:.0f} rows/s) -> "
              f"{speedup:.2f}x vs stitched cascade", file=sys.stderr)
        assert fused["whole_plan"] == 1, name
        assert fused["syncs_per_query"] == 1.0, \
            f"{name}: fused multiway join must host-sync exactly once"
        assert cascade["syncs_per_query"] >= 3.0, name
        assert fused["rows"] == cascade["rows"] == stitched["rows"], name
        assert speedup >= 2.0, \
            (f"{name}: fused {fused['best_s']:.3f}s not >=2x cascade "
             f"{cascade['best_s']:.3f}s")
    best = report["q5"]["fused"]["best_s"]
    return "multiway_join_rows_per_sec", n_rows / best, best


def bench_scan(n_rows, iters):
    """Versioned MVCC read path (ISSUE 4): snapshot reads over a tablet
    with three flushed version generations (overwrites, deletes, partial
    writes) plus live store churn.  The emitted metric is the WARM
    snapshot-cache path (repeated selects at the current timestamp);
    the cold vectorized merge and the retained pre-PR Python reference
    merge print on stderr with speedups.  n_rows sizes the key space;
    total versions ≈ 1.55×."""
    import tempfile

    import numpy as np

    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.chunks.store import FsChunkStore
    from ytsaurus_tpu.schema import TableSchema
    from ytsaurus_tpu.tablet.tablet import Tablet, versioned_schema

    schema = TableSchema.make([("k", "int64", "ascending"),
                               ("g", "int64"), ("v", "int64")],
                              unique_keys=True)
    tablet = Tablet(schema, FsChunkStore(
        tempfile.mkdtemp(prefix="bench-scan-")))
    vschema = versioned_schema(schema)
    rng = np.random.default_rng(7)

    def publish(arrays, valids):
        chunk = ColumnarChunk.from_arrays(vschema, arrays, valids=valids)
        tablet.chunk_ids.append(tablet.chunk_store.write_chunk(chunk))

    n = n_rows
    keys0 = np.arange(n, dtype=np.int64)
    ones = np.ones(n, dtype=bool)
    publish({"k": keys0, "$timestamp": np.full(n, 100, np.int64),
             "$tombstone": np.zeros(n, dtype=bool),
             "g": keys0 % 1000, "$w:g": ones,
             "v": keys0 * 3, "$w:v": ones},
            valids={})
    # Generation 2: a third of the keys overwritten, a fifth of THOSE
    # deleted (tombstones bound the merge for their keys).
    m1 = max(n // 3, 1)
    k1 = np.sort(rng.choice(n, size=m1, replace=False)).astype(np.int64)
    tomb = np.zeros(m1, dtype=bool)
    tomb[:: 5] = True
    publish({"k": k1, "$timestamp": np.full(m1, 200, np.int64),
             "$tombstone": tomb,
             "g": k1 % 500, "$w:g": ~tomb,
             "v": k1 * 7, "$w:v": ~tomb},
            valids={"g": ~tomb, "v": ~tomb})
    # Generation 3: partial writes — only `v` stated, `g` merges from
    # older generations per column.
    m2 = max(n // 5, 1)
    k2 = np.sort(rng.choice(n, size=m2, replace=False)).astype(np.int64)
    publish({"k": k2, "$timestamp": np.full(m2, 300, np.int64),
             "$tombstone": np.zeros(m2, dtype=bool),
             "g": np.zeros(m2, np.int64),
             "$w:g": np.zeros(m2, dtype=bool),
             "v": k2 * 11, "$w:v": np.ones(m2, dtype=bool)},
            valids={"g": np.zeros(m2, dtype=bool)})
    # Live store churn on top of the sealed chunks.
    for i in range(1024):
        tablet.write_row({"k": int(n + i), "g": i, "v": i}, timestamp=400)

    t0 = time.perf_counter()
    ref = tablet.read_snapshot_reference()
    ref_time = time.perf_counter() - t0
    versions = n + m1 + m2 + 1024

    def timed_read(invalidate):
        times = []
        while _iters_left(times, iters):
            if invalidate:
                tablet._snapshot_cache = None
            t0 = time.perf_counter()
            out = tablet.read_snapshot()
            _sync(out.columns["k"].data)
            times.append(time.perf_counter() - t0)
        return min(times), out

    cold_time, out = timed_read(invalidate=True)
    assert out.row_count == ref.row_count, (out.row_count, ref.row_count)
    tablet.read_snapshot()                        # prime the cache
    warm_time, _ = timed_read(invalidate=False)
    ref_rps = versions / ref_time
    print(f"# scan: warm cache {versions / warm_time:.0f} rows/s "
          f"({warm_time * 1e3:.2f}ms), cold vectorized "
          f"{versions / cold_time:.0f} rows/s ({cold_time * 1e3:.1f}ms), "
          f"reference {ref_rps:.0f} rows/s ({ref_time * 1e3:.0f}ms); "
          f"warm {ref_time / warm_time:.0f}x, cold "
          f"{ref_time / cold_time:.1f}x vs pre-PR merge "
          f"({versions} versions, {out.row_count} visible)",
          file=sys.stderr)
    return "scan_rows_per_sec", versions / warm_time, warm_time


# config -> (fn, default rows on an accelerator, default rows on CPU)
def bench_matview(n_rows, iters):
    """Continuous queries (ISSUE 13): sustained ordered-table ingest
    with an incrementally maintained GROUP BY view (sum/count/avg by a
    97-ary key), exactly-once refresh per micro-batch.

      ingest     the metric: source rows/s through push + incremental
                 refresh (delta-merge into the sorted target), with
                 end-to-end freshness lag (push → committed visibility)
                 reported p50/p99 over the waves;
      steady     fresh-compile count across the measured waves must be
                 ZERO after warmup — one parameterized plan per view,
                 fixed pow2 batch capacity (the ISSUE 13 acceptance);
      restart    (a) in-process daemon-restart analog: a FRESH
                 evaluator + refresher resumes from committed offsets
                 with 0 fresh compiles (AOT disk tier), (b) a fresh
                 CHILD PROCESS builds the same view against the same
                 artifact dir and also refreshes with 0 fresh compiles.

    Correctness is asserted against the full-recompute oracle at the
    end of every leg."""
    import os as _os
    import subprocess as _subprocess
    import tempfile

    from ytsaurus_tpu import config as yt_config
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.query.engine.evaluator import (
        Evaluator,
        get_compile_observatory,
    )
    from ytsaurus_tpu.query.views import ViewRefresher, load_view
    from ytsaurus_tpu.schema import TableSchema

    root = tempfile.mkdtemp(prefix="bench-matview-")
    aot_dir = _os.path.join(root, "aot")
    yt_config.set_compile_config(yt_config.CompileConfig(
        parameterize=True, disk_cache_dir=aot_dir))
    batch_rows = 16_384
    wave_rows = max(min(n_rows // 8, 4 * batch_rows), batch_rows)

    def make_rows(lo, n):
        return [{"k": lo + i, "g": (lo + i) % 97,
                 "v": float((lo + i) % 1013)} for i in range(n)]

    client = connect(root)
    schema = TableSchema.make([("k", "int64"), ("g", "int64"),
                               ("v", "double")])
    client.create("table", "//bench/stream", recursive=True,
                  attributes={"schema": schema, "dynamic": True})
    client.mount_table("//bench/stream")
    query = ("g, sum(v) AS s, count(*) AS c, avg(v) AS a "
             "FROM [//bench/stream] GROUP BY g")
    client.create_materialized_view("agg", query,
                                    batch_rows=batch_rows)
    refresher = ViewRefresher(client, load_view(client, "agg"))
    obs = get_compile_observatory()

    # Warmup: full and partial batches cover the (fixed) batch capacity
    # and the merge-combine shapes; everything compiles here (and lands
    # in the AOT disk tier for the restart legs).
    client.push_queue("//bench/stream", make_rows(0, batch_rows))
    refresher.refresh()
    client.push_queue("//bench/stream",
                      make_rows(batch_rows, batch_rows // 3))
    refresher.refresh()
    pushed = batch_rows + batch_rows // 3

    def canon(rows):
        return sorted(tuple((k, round(v, 6) if isinstance(v, float)
                             else v) for k, v in sorted(r.items()))
                      for r in rows)

    def check_oracle():
        got = canon(client.select_rows(
            "g, s, c, a FROM [//sys/views/agg/target]"))
        want = canon(client.select_rows(query))
        assert got == want, "view diverged from the recompute oracle"

    # Measured leg: sustained ingest waves; steady state must be
    # compile-free.
    before = obs.totals()
    waves = []
    ingested = 0
    n_waves = max(4, n_rows // wave_rows)
    t_leg = time.perf_counter()
    while len(waves) < n_waves and _iters_left(waves, n_waves):
        t0 = time.perf_counter()
        client.push_queue("//bench/stream", make_rows(pushed, wave_rows))
        pushed += wave_rows
        ingested += wave_rows
        report = refresher.refresh()
        assert report["lag_rows"] == 0
        waves.append(time.perf_counter() - t0)
    elapsed = time.perf_counter() - t_leg
    after = obs.totals()
    assert after["misses"] == before["misses"], \
        f"steady-state refresh compiled: {before} -> {after}"
    check_oracle()
    lags = sorted(waves)
    p50 = lags[len(lags) // 2]
    p99 = lags[min(len(lags) - 1, int(len(lags) * 0.99))]

    # Restart leg (in-process): a fresh evaluator = an empty in-memory
    # compile cache, i.e. a restarted daemon.  It must resume from the
    # committed offsets and serve every program from the AOT disk tier.
    client.cluster.evaluator = Evaluator()
    restarted = ViewRefresher(client, load_view(client, "agg"))
    before = obs.totals()
    client.push_queue("//bench/stream", make_rows(pushed, wave_rows))
    pushed += wave_rows
    report = restarted.refresh()
    after = obs.totals()
    restart_misses = after["misses"] - before["misses"]
    restart_disk = after["disk_hits"] - before["disk_hits"]
    assert restart_misses == restart_disk, \
        f"restart compiled fresh: {restart_misses} misses, " \
        f"{restart_disk} disk hits"
    assert report["rows_in"] == wave_rows, report
    check_oracle()

    # Restart leg (cross-process): same artifacts, fresh interpreter.
    child_src = f"""
import json, sys
from ytsaurus_tpu import config as yt_config
yt_config.set_compile_config(yt_config.CompileConfig(
    parameterize=True, disk_cache_dir={aot_dir!r}))
sys.argv = ["child"]
import bench
bench.bench_matview_child({batch_rows})
"""
    env = dict(_os.environ, JAX_PLATFORMS=_os.environ.get(
        "JAX_PLATFORMS", "cpu"), BENCH_CHILD="1")
    proc = _subprocess.run(
        [sys.executable, "-c", child_src],
        cwd=_os.path.dirname(_os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    child = json.loads([ln for ln in proc.stdout.splitlines()
                        if ln.startswith("{")][-1])
    assert child["fresh_compiles"] == 0, child
    assert child["disk_hits"] >= 1, child

    rate = (len(waves) * wave_rows) / elapsed
    print(f"# matview: {len(waves)} waves x {wave_rows} rows, "
          f"freshness p50 {p50 * 1e3:.1f}ms p99 {p99 * 1e3:.1f}ms, "
          f"steady fresh compiles 0 (asserted); restart leg "
          f"{restart_misses} misses all from disk; child process "
          f"{child['disk_hits']} disk hits, "
          f"{child['fresh_compiles']} fresh",
          file=sys.stderr)
    return "matview_rows_per_sec", rate, min(waves)


def bench_matview_child(batch_rows):
    """Cross-process restart leg of bench_matview: rebuild an identical
    view in a FRESH interpreter against the SAME AOT artifact directory;
    every program must come back from disk (0 fresh compiles)."""
    import tempfile

    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.query.engine.evaluator import (
        get_compile_observatory,
    )
    from ytsaurus_tpu.query.views import ViewRefresher, load_view
    from ytsaurus_tpu.schema import TableSchema

    client = connect(tempfile.mkdtemp(prefix="bench-matview-child-"))
    schema = TableSchema.make([("k", "int64"), ("g", "int64"),
                               ("v", "double")])
    client.create("table", "//bench/stream", recursive=True,
                  attributes={"schema": schema, "dynamic": True})
    client.mount_table("//bench/stream")
    client.create_materialized_view(
        "agg", "g, sum(v) AS s, count(*) AS c, avg(v) AS a "
               "FROM [//bench/stream] GROUP BY g",
        batch_rows=batch_rows)
    client.push_queue("//bench/stream", [
        {"k": i, "g": i % 97, "v": float(i % 1013)}
        for i in range(batch_rows + batch_rows // 3)])
    obs = get_compile_observatory()
    obs.reset()
    ViewRefresher(client, load_view(client, "agg")).refresh()
    totals = obs.totals()
    print(json.dumps({
        "disk_hits": totals["disk_hits"],
        "fresh_compiles": totals["misses"] - totals["disk_hits"],
    }), flush=True)


def bench_sanitizer_overhead(n_rows, iters):
    """Concurrency sanitizer (ISSUE 15): the DISABLED path must be a
    plain-lock no-op — `sanitizers.register_lock()` without
    YT_TPU_SANITIZE hands back the raw `threading.Lock`, so its
    per-acquire cost must match a plain lock within noise (asserted
    ≲0.1µs delta) — and the ENABLED path's per-acquire cost is recorded
    (held-set bookkeeping + edge probe; tier-1 pays it suite-wide, so
    the number feeds the 870s-budget arithmetic).  The emitted metric is
    enabled-path acquires/s with one lock held (the edge-probing case,
    i.e. the EXPENSIVE one)."""
    import threading

    from ytsaurus_tpu.utils import sanitizers

    n_round = min(n_rows, 400_000)

    def per_acquire(lock, rounds=7):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(n_round):
                with lock:
                    pass
            best = min(best, (time.perf_counter() - t0) / n_round)
        return best

    plain_cost = per_acquire(threading.Lock())

    assert not sanitizers.enabled(), \
        "bench must run with the sanitizer DISABLED (unset " \
        "YT_TPU_SANITIZE) to measure the production fast path"
    registered = sanitizers.register_lock("bench.sanitizer._lock")
    assert type(registered) is type(threading.Lock()), \
        "disabled register_lock must return the PLAIN lock, no wrapper"
    disabled_cost = per_acquire(registered)

    san = sanitizers.LockSanitizer()
    inst = sanitizers.InstrumentedLock(san, "bench.inst._lock")
    outer = sanitizers.InstrumentedLock(san, "bench.outer._lock")
    enabled_leaf_cost = per_acquire(inst)
    with outer:                         # one lock held: edge probe runs
        enabled_nested_cost = per_acquire(inst)

    delta = disabled_cost - plain_cost
    print(f"# sanitizer acquire costs: plain {plain_cost * 1e9:.0f} ns, "
          f"disabled-registered {disabled_cost * 1e9:.0f} ns "
          f"(delta {delta * 1e9:+.0f} ns), enabled leaf "
          f"{enabled_leaf_cost * 1e9:.0f} ns, enabled nested "
          f"{enabled_nested_cost * 1e9:.0f} ns", file=sys.stderr)
    assert abs(delta) < 0.1e-6, \
        f"disabled path must be a plain-lock no-op: " \
        f"{delta * 1e9:+.0f} ns/acquire delta vs plain threading.Lock"
    assert san.counters()["edges_observed"] == 1    # outer -> inst

    best = enabled_nested_cost * n_round
    return ("sanitizer_acquires_per_sec", 1.0 / enabled_nested_cost,
            best)


def bench_vector(n_rows, iters):
    """Vector similarity serving (ISSUE 16): the batched NEAREST kernel
    — ONE `(batch, dim) @ (dim, rows)` distance matmul + per-row top-k
    — swept over (dim × k × batch) on the n_rows-vector corpus, plus an
    8-device whole-plan NEAREST leg in a child process (the mesh path:
    per-shard top-k, one gather, exactly one host sync).

    Per-point lines report queries/s and vectors-scanned/s (the batch
    amortization story: batch=64 should scan ~an order of magnitude
    more vectors/s than batch=1 because the matmul reuses the corpus
    plane across the batch dimension).  The emitted metric is
    vectors-scanned/s at the serving sweet spot (dim=256, k=8,
    batch=64)."""
    import subprocess as _subprocess

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ytsaurus_tpu.query.vector import _nearest_jit

    rng = np.random.default_rng(3)
    valid = jnp.ones(n_rows, dtype=bool)
    headline = None
    for dim in (64, 256):
        plane = jnp.asarray(
            rng.standard_normal((n_rows, dim), dtype=np.float32))
        for k in (8, 64):
            for batch in (1, 16, 64):
                q = jnp.asarray(rng.standard_normal(
                    (batch, dim), dtype=np.float32))
                vals, idx = _nearest_jit(plane, valid, q,
                                         metric="l2", k_static=k)
                _sync(vals)              # warm-up / compile
                times = []
                while _iters_left(times, iters):
                    t0 = time.perf_counter()
                    vals, idx = _nearest_jit(plane, valid, q,
                                             metric="l2", k_static=k)
                    _sync(vals)
                    times.append(time.perf_counter() - t0)
                best = min(times)
                qps = batch / best
                scanned = n_rows * batch / best
                print(f"# vector dim={dim} k={k} batch={batch}: "
                      f"{qps:,.0f} queries/s, "
                      f"{scanned:,.0f} vectors-scanned/s",
                      file=sys.stderr)
                if dim == 256 and k == 8 and batch == 64:
                    headline = (scanned, best)

    # 8-device leg: the fused whole-plan NEAREST (distributed tentpole
    # path) in a child with a virtual 8-device CPU mesh.
    n_child = min(n_rows, 200_000)
    child_src = f"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import time
import numpy as np
from ytsaurus_tpu.chunks.columnar import ColumnarChunk
from ytsaurus_tpu.parallel.mesh import make_mesh
from ytsaurus_tpu.parallel.distributed import (
    DistributedEvaluator, ShardedTable, host_sync_count)
from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.schema import TableSchema

DIM = 64
N = {n_child}
per = N // 8
schema = TableSchema.make([("k", "int64"), ("emb", f"vector<float, 64>")])
rng = np.random.default_rng(5)
chunks = []
for s in range(8):
    rows = [dict(k=s * per + i, emb=[float(x) for x in v])
            for i, v in enumerate(rng.standard_normal((per, DIM)))]
    chunks.append(ColumnarChunk.from_rows(schema, rows))
mesh = make_mesh(8)
table = ShardedTable.from_chunks(mesh, chunks)
ev = DistributedEvaluator(mesh)
plan = build_query("k FROM [//t] NEAREST(emb, ?, 8)", {{"//t": schema}},
                   params=[[float(x) for x in rng.standard_normal(DIM)]])
run_whole_plan(ev, plan, table)          # warm-up / compile
s0 = host_sync_count()
t0 = time.perf_counter()
ITERS = 5
for _ in range(ITERS):
    out = run_whole_plan(ev, plan, table)
elapsed = time.perf_counter() - t0
assert host_sync_count() - s0 == ITERS, "fused NEAREST must be 1 sync/query"
assert len(out.to_rows()) == 8
print(f"CHILD {{ITERS / elapsed:.1f}} {{N * ITERS / elapsed:.0f}}")
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = _subprocess.run([sys.executable, "-c", child_src],
                           capture_output=True, text=True, env=env,
                           timeout=600)
    for line in proc.stdout.splitlines():
        if line.startswith("CHILD "):
            _, qps8, scanned8 = line.split()
            print(f"# vector spmd-8dev dim=64 k=8 batch=1: "
                  f"{float(qps8):,.1f} queries/s, "
                  f"{float(scanned8):,.0f} vectors-scanned/s "
                  f"(1 host sync/query, asserted)", file=sys.stderr)
            break
    else:
        raise RuntimeError(
            f"vector SPMD child failed:\n{proc.stderr[-2000:]}")

    scanned, best = headline
    return "vector_scan_rows_per_sec", scanned, best


def bench_tiering(n_rows, iters):
    """Adaptive tiered execution (ISSUE 18): a burst of DISTINCT cold
    query shapes served three ways over one resident chunk.

      inline   tiering OFF (the pre-PR discipline): every cold shape
               pays its XLA compile inline on the serving thread —
               cold-shape p50/p99 IS the compile time.
      tiered   tiering ON (hot_threshold=1): cold shapes serve from the
               no-compile interpreter immediately, bit-identically; the
               background compiler promotes each hot fingerprint
               off-thread, after which the SAME keys serve compiled
               (steady-state compiled share asserted >=95%).
      prewarm  restart leg: a FRESH evaluator prewarmed COMPILE-ONLY
               from the recorded shape mix serves the whole burst with
               zero inline compiles (asserted).

    Metric: tiered cold-shape throughput (queries/s through the
    interpreter).  Cold p50/p99 per leg, the p99 drop, background
    promotion latency, and the prewarm report print on stderr."""
    import numpy as _np

    from ytsaurus_tpu import config as _config
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.query.engine import evaluator as _ev
    from ytsaurus_tpu.query.engine.prewarm import prewarm_from_capture
    from ytsaurus_tpu.query.profile import get_flight_recorder
    from ytsaurus_tpu.query.statistics import QueryStatistics
    from ytsaurus_tpu.query.workload import WorkloadRecord
    from ytsaurus_tpu.schema import TableSchema

    schema = TableSchema.make([("k", "int64"), ("g", "int64"),
                               ("v", "int64")])
    rows = [{"k": i, "g": i % 97, "v": (i * 31) % 10_000}
            for i in range(n_rows)]
    chunk = ColumnarChunk.from_rows(schema, rows)
    schemas = {"//t": schema}

    # 20 structurally distinct shapes (distinct fingerprints even under
    # literal parameterization): filter-op x column, ORDER BY variants,
    # aggregate x group-key.  All inside the interpreter's coverage.
    shapes = []
    for col in ("k", "v"):
        for op in (">", "<", ">=", "<="):
            shapes.append(f"k, v FROM [//t] WHERE {col} {op} 500 LIMIT 9")
    for col in ("k", "g", "v"):
        for direction in ("asc", "desc"):
            shapes.append(f"k, v FROM [//t] WHERE v > 1 "
                          f"ORDER BY {col} {direction}, k LIMIT 7")
    for key in ("g", "v"):
        for fn in ("sum", "min", "max"):
            shapes.append(f"{key}, {fn}(k) AS a FROM [//t] GROUP BY {key}")
    plans = [build_query(q, schemas) for q in shapes]

    def run_cold_burst(evaluator):
        lat, tiers, compiles = [], [], 0
        for plan in plans:
            stats = QueryStatistics()
            t0 = time.perf_counter()
            evaluator.run_plan(plan, chunk, stats=stats)
            lat.append(time.perf_counter() - t0)
            tiers.append(stats.execution_tier)
            compiles += stats.compile_count
        return lat, tiers, compiles

    def pct(lat, q):
        return sorted(lat)[min(len(lat) - 1, int(q * len(lat)))] * 1e3

    # Leg 1: inline compiles (tiering off).
    _config.set_tiering_config(None)
    inline_lat, inline_tiers, inline_compiles = run_cold_burst(
        _ev.Evaluator())
    assert inline_compiles == len(shapes), inline_compiles
    try:
        # Leg 2: interpreter-first with background promotion.
        _config.set_tiering_config(_config.TieringConfig(
            enabled=True, hot_threshold=1))
        tiered = _ev.Evaluator()
        promotions_before = len(get_flight_recorder().promotions())
        t_cold = time.perf_counter()
        tiered_lat, tiered_tiers, tiered_compiles = run_cold_burst(tiered)
        cold_elapsed = time.perf_counter() - t_cold
        assert tiered_compiles == 0, tiered_compiles
        assert all(t == "interpreted" for t in tiered_tiers), tiered_tiers
        t_promo = time.perf_counter()
        tiered._background.drain(timeout=600)
        promo_wall = time.perf_counter() - t_promo
        events = get_flight_recorder().promotions()[promotions_before:]
        # Steady state: every shape again — all compiled now.
        _steady_lat, steady_tiers, steady_compiles = run_cold_burst(tiered)
        compiled_share = sum(
            t in ("compiled", "promoted-midstream")
            for t in steady_tiers) / len(steady_tiers)
        assert steady_compiles == 0, steady_compiles
        assert compiled_share >= 0.95, compiled_share

        # Leg 3: prewarmed restart — a fresh evaluator, warmed
        # compile-only from the shape mix, serves with 0 inline compiles.
        records = [WorkloadRecord(kind="select", query=q, literals=[])
                   for q in shapes]
        fresh = _ev.Evaluator()
        report = prewarm_from_capture(records, tables={"//t": chunk},
                                      evaluator=fresh)
        assert report["compiled"] + report["aot_hits"] == len(shapes), \
            report
        _pw_lat, pw_tiers, pw_compiles = run_cold_burst(fresh)
        assert pw_compiles == 0, pw_compiles
        assert all(t == "compiled" for t in pw_tiers), pw_tiers
    finally:
        _config.set_tiering_config(None)

    p99_drop = pct(inline_lat, 0.99) / max(pct(tiered_lat, 0.99), 1e-9)
    mean_promo = (sum(e["compile_seconds"] for e in events) /
                  len(events) * 1e3) if events else 0.0
    print(f"# tiering: {len(shapes)} cold shapes x {n_rows} rows; "
          f"inline p50={pct(inline_lat, 0.5):.1f}ms "
          f"p99={pct(inline_lat, 0.99):.1f}ms -> interpreted "
          f"p50={pct(tiered_lat, 0.5):.1f}ms "
          f"p99={pct(tiered_lat, 0.99):.1f}ms "
          f"(cold p99 {p99_drop:.1f}x lower); "
          f"{len(events)} background promotions "
          f"(mean compile {mean_promo:.0f}ms, drained {promo_wall:.2f}s), "
          f"steady compiled share {compiled_share * 100:.0f}%; "
          f"prewarm: {report['compiled']} compiled in "
          f"{report['seconds']:.2f}s, replay 0 inline compiles",
          file=sys.stderr)
    assert p99_drop >= 10.0, f"cold p99 drop {p99_drop:.1f}x < 10x"
    return ("tiering_cold_queries_per_sec", len(shapes) / cold_elapsed,
            cold_elapsed)


# --- per-primitive kernel microbench (ISSUE 19 move c) ----------------------
# tools/kernel_floors.json records rows/s floors per (device, n_rows);
# a measured primitive dipping under its floor fails the config.  Floors
# are written at 0.4x a measured run (YT_TPU_UPDATE_KERNEL_FLOORS=1) so
# machine jitter does not trip the gate; a real engine regression (2.5x+
# slowdown) does.  tests/test_bench_kernels.py asserts the smoke-scale
# floors inside the tier-1 pass.

KERNEL_FLOORS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "tools",
    "kernel_floors.json")


def _load_kernel_floors():
    try:
        with open(KERNEL_FLOORS_PATH) as f:
            return json.load(f)
    except Exception:
        return {}


def kernel_primitives(n_rows, iters):
    """Time each ops/segments.py backbone primitive; returns
    {name: (rows_per_sec, best_seconds)}.  Shared by the bench config
    and the tier-1 smoke test."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ytsaurus_tpu.ops import segments
    from ytsaurus_tpu.query.engine.joins import _lex_searchsorted
    from ytsaurus_tpu.schema import EValueType

    rng = np.random.default_rng(7)
    nseg = int(min(10_001, max(n_rows // 100, 2)))
    seg_sorted_np = np.sort(rng.integers(0, nseg, n_rows))
    seg_sorted = jnp.asarray(seg_sorted_np, dtype=jnp.int32)
    seg_unsorted = jnp.asarray(rng.permutation(seg_sorted_np),
                               dtype=jnp.int32)
    vals = jnp.asarray(rng.random(n_rows))
    keys64 = jnp.asarray(rng.integers(0, 1 << 60, n_rows, dtype=np.int64))
    valid = jnp.ones(n_rows, dtype=bool)
    starts = jnp.concatenate([jnp.ones(1, dtype=bool),
                              seg_sorted[1:] != seg_sorted[:-1]])
    mask = jnp.asarray(rng.random(n_rows) < 0.5)
    # Encoded join-key planes: (null_rank int8, value) pairs, the format
    # _emit_encoded_keys produces (joins.py).
    ones8 = jnp.ones(n_rows, dtype=jnp.int8)
    f_sorted = jnp.asarray(
        np.sort(rng.integers(1, 1 << 60, n_rows, dtype=np.int64)))
    probe_keys = jnp.asarray(
        rng.integers(1, 1 << 60, n_rows, dtype=np.int64))

    def timed(fn, *args):
        fn_j = jax.jit(fn)
        out = fn_j(*args)                  # warm-up / compile
        _sync(out)
        times = []
        while _iters_left(times, iters):
            t0 = time.perf_counter()
            out = fn_j(*args)
            _sync(out)
            times.append(time.perf_counter() - t0)
        return min(times)

    secs = {}
    secs["segscan_sum"] = timed(
        lambda d, st: segments.segment_scan("sum", d, st), vals, starts)
    secs["group_sum_sorted"] = timed(
        lambda d, sg, v: segments.segment_aggregate(
            "sum", d, v, sg, nseg, EValueType.double, assume_sorted=True),
        vals, seg_sorted, valid)
    secs["group_sum_scatter"] = timed(
        lambda d, sg, v: segments.segment_aggregate(
            "sum", d, v, sg, nseg, EValueType.double),
        vals, seg_unsorted, valid)
    secs["group_min_scatter"] = timed(
        lambda d, sg, v: segments.segment_aggregate(
            "min", d, v, sg, nseg, EValueType.double),
        vals, seg_unsorted, valid)
    secs["radix_rank_u64"] = timed(
        lambda k, v: segments.stable_argsort_u32(
            segments.monotone_u32_words(k, v)), keys64, valid)
    secs["packed_sort_14bit"] = timed(
        lambda sg, v: segments.packed_sort_indices([(sg, v, False, 14)]),
        seg_unsorted, valid)
    secs["hash_group_order"] = timed(
        lambda k, v: segments.hash_group_order([(k, v)], v), keys64, valid)
    secs["lex_probe"] = timed(
        lambda f, q, n8: _lex_searchsorted(
            [(n8, f)], jnp.int64(n_rows), n_rows, [(n8, q)], "left"),
        f_sorted, probe_keys, ones8)
    secs["compact_mask"] = timed(lambda m: segments.compact_mask(m), mask)
    return {name: (n_rows / t, t) for name, t in secs.items()}


def bench_kernels(n_rows, iters):
    """Per-primitive rows/s/core for the segmented-scan / radix / probe
    backbone (ISSUE 19): the floor every macro number multiplies.  The
    config metric is the SLOWEST primitive.  ops/pallas_radix.py is the
    staging ground for moving the rank loop on-chip; these numbers time
    the XLA path."""
    import jax
    platform = jax.devices()[0].platform
    results = kernel_primitives(n_rows, iters)
    floors_doc = _load_kernel_floors()
    entry = floors_doc.get(platform, {}).get(str(n_rows), {})
    failures = []
    for name, (rps, best) in sorted(results.items()):
        floor = entry.get(name)
        status = ""
        if floor is not None:
            status = " (floor %.3g)" % floor
            if rps < floor:
                failures.append((name, rps, floor))
                status += " REGRESSION"
        print("# kernel %-18s %12.1f rows/s  best %8.2fms%s"
              % (name, rps, best * 1e3, status), file=sys.stderr)
    if os.environ.get("YT_TPU_UPDATE_KERNEL_FLOORS"):
        floors_doc.setdefault(platform, {})[str(n_rows)] = {
            name: round(rps * 0.4, 1)
            for name, (rps, _) in sorted(results.items())}
        os.makedirs(os.path.dirname(KERNEL_FLOORS_PATH), exist_ok=True)
        tmp = KERNEL_FLOORS_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(floors_doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, KERNEL_FLOORS_PATH)
        print(f"# kernel floors updated: {KERNEL_FLOORS_PATH} "
              f"({platform}:{n_rows})", file=sys.stderr)
    assert not failures, \
        "kernel primitives under recorded floor: %s" % failures
    worst = min(results, key=lambda k: results[k][0])
    return ("kernels_min_rows_per_sec", results[worst][0],
            results[worst][1])



_CONFIGS = {
    "vector": (bench_vector, 4_000_000, 200_000),
    "q1": (bench_q1, 64_000_000, 2_000_000),
    "groupby": (bench_groupby, 64_000_000, 2_000_000),
    "topk": (bench_topk, 64_000_000, 2_000_000),
    "q3": (bench_q3, 4_000_000, 500_000),
    "sort": (bench_sort, 64_000_000, 1_000_000),
    "strings": (bench_strings, 10_000_000, 500_000),
    "window": (bench_window, 2_000_000, 500_000),
    "select": (bench_select, 16_000_000, 1_000_000),
    "serving": (bench_serving, 200_000, 100_000),
    "scan": (bench_scan, 500_000, 100_000),
    "trace_overhead": (bench_trace_overhead, 2_000_000, 500_000),
    "telemetry_overhead": (bench_telemetry_overhead, 200_000, 100_000),
    "replay": (bench_replay, 200_000, 100_000),
    "serving_steady": (bench_serving_steady, 200_000, 100_000),
    "slo": (bench_slo, 100_000, 50_000),
    "whole_plan": (bench_whole_plan, 8_000_000, 1_000_000),
    "mesh_overhead": (bench_mesh_overhead, 8_000_000, 1_000_000),
    "multiway_join": (bench_multiway_join, 4_000_000, 400_000),
    "matview": (bench_matview, 2_000_000, 500_000),
    "sanitizer_overhead": (bench_sanitizer_overhead, 400_000, 400_000),
    "tiering": (bench_tiering, 200_000, 50_000),
    "kernels": (bench_kernels, 64_000_000, 2_000_000),
}


def _emit(metric, rows_per_sec, device):
    platform, device_kind, device_count = device
    line = {
        "metric": metric,
        "value": round(rows_per_sec, 1),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_sec / BASELINE_ROWS_PER_SEC, 3),
        "platform": platform,
        "device_kind": device_kind,
        "device_count": device_count,
    }
    print(json.dumps(line), flush=True)
    return line


_METRIC_NAMES = {
    "q1": "tpch_q1_rows_per_sec",
    "groupby": "groupby_rows_per_sec",
    "topk": "topk_rows_per_sec",
    "q3": "tpch_q3_rows_per_sec",
    "sort": "sort_rows_per_sec",
    "strings": "strings_groupby_rows_per_sec",
    "window": "window_rows_per_sec",
    "select": "select_rows_per_sec",
    "serving": "serving_lookup_rows_per_sec",
    "scan": "scan_rows_per_sec",
    "trace_overhead": "trace_overhead_rows_per_sec",
    "telemetry_overhead": "telemetry_overhead_rows_per_sec",
    "replay": "replay_queries_per_sec",
    "serving_steady": "serving_steady_queries_per_sec",
    "slo": "slo_baseline_queries_per_sec",
    "whole_plan": "whole_plan_rows_per_sec",
    "mesh_overhead": "mesh_overhead_rows_per_sec",
    "multiway_join": "multiway_join_rows_per_sec",
    "matview": "matview_rows_per_sec",
    "sanitizer_overhead": "sanitizer_acquires_per_sec",
    "vector": "vector_scan_rows_per_sec",
    "tiering": "tiering_cold_queries_per_sec",
    "kernels": "kernels_min_rows_per_sec",
}


def _run_config(name, args):
    """Measure one config in THIS process (which owns the chip)."""
    from ytsaurus_tpu.utils.backend import (
        ensure_backend,
        place_compile_cache,
    )
    place_compile_cache()
    jax = ensure_backend()
    devs = jax.devices()
    platform = devs[0].platform
    fn, accel_rows, cpu_rows = _CONFIGS[name]
    default_rows = cpu_rows if platform == "cpu" else accel_rows
    n_rows = args.rows or (100_000 if args.smoke else default_rows)
    metric, rows_per_sec, best = fn(n_rows, args.iters)
    assert metric == _METRIC_NAMES[name]
    _emit(metric, rows_per_sec,
          (platform, devs[0].device_kind, len(devs)))
    print(f"# config={name} n_rows={n_rows} best={best*1e3:.2f}ms "
          f"device={platform}", file=sys.stderr)


def main():
    global _DEADLINE
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", choices=sorted(_CONFIGS) + ["all"],
                        default="all",
                        help="default 'all': one JSON line per BASELINE "
                             "config, headline q1 last")
    parser.add_argument("--smoke", action="store_true",
                        help="small row count, CPU-friendly")
    parser.add_argument("--rows", type=int, default=None)
    parser.add_argument("--iters", type=int, default=3)
    parser.add_argument("--budget", type=float,
                        default=float(os.environ.get("BENCH_BUDGET", 420)))
    args = parser.parse_args()
    _DEADLINE = time.monotonic() + args.budget

    if args.config == "all":
        # The parent stays OFF JAX: a chip belongs to one process, and
        # each config child owns it in turn.
        names = ("groupby", "topk", "q3", "sort", "strings", "window",
                 "select", "serving", "scan", "q1")
        return _run_all(names, args)
    _run_config(args.config, args)
    return 0


def _run_all(names, args):
    """Each config in its OWN subprocess with a hard timeout: a hung XLA
    compile must not starve the later configs or the headline line.  The
    headline q1 runs last (the driver parses the final line) with a
    dedicated time reserve.  A config that fails or times out prints no
    line and makes the whole run exit non-zero."""
    import subprocess
    q1_reserve = min(180.0, max(90.0, 0.35 * args.budget))
    failed = []
    for idx, name in enumerate(names):
        remaining = _DEADLINE - time.monotonic()
        if remaining < 30.0:
            print(f"# budget exhausted before config={name}",
                  file=sys.stderr)
            failed.append(name)
            continue
        if name == "q1":
            child_timeout = max(20.0, remaining - 10.0)
        else:
            left = len([n for n in names[idx:] if n != "q1"])
            child_timeout = max(45.0, (remaining - q1_reserve) / left)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--config", name, "--iters", str(args.iters),
               "--budget", str(max(child_timeout - 20.0, 20.0))]
        if args.smoke:
            cmd.append("--smoke")
        if args.rows:
            cmd.extend(["--rows", str(args.rows)])
        try:
            proc = subprocess.run(cmd, timeout=child_timeout,
                                  capture_output=True, text=True)
        except subprocess.TimeoutExpired as exc:
            tail = exc.stderr or ""
            if isinstance(tail, bytes):
                tail = tail.decode("utf-8", "replace")
            sys.stderr.write(tail[-500:])
            print(f"# config={name} child TIMED OUT after "
                  f"{child_timeout:.0f}s", file=sys.stderr)
            failed.append(name)
            continue
        sys.stderr.write(proc.stderr or "")
        lines = [ln for ln in (proc.stdout or "").splitlines()
                 if ln.startswith("{")]
        if proc.returncode != 0 or not lines:
            print(f"# config={name} child rc={proc.returncode}",
                  file=sys.stderr)
            failed.append(name)
            continue
        for ln in lines:
            print(ln, flush=True)
    if failed:
        print(f"# FAILED configs: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
