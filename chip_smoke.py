#!/usr/bin/env python3
"""Smoke of the in-process served path on ONE TPU chip: load -> select ->
lookup -> sort through the normal client entry points, every answer checked
against a numpy reference computed here from the same host arrays.

    python chip_smoke.py                    # one TPU chip, deployment sizes
    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu --rows 600000 \
        --dyn-rows 100000 --sort-rows 20000      # CPU rehearsal, tiny

One process, no children.  Earlier stdout lines are free-form JSON objects;
the LAST line is the verdict.  Not a benchmark: the seconds it prints are
single cold/warm readings of a smoke.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Every sort rides the tiled radix engine.  `auto` picks the one-program
# network sort below 8M rows, and libtpu 0.0.34 takes ~40 s PER KEY WORD to
# compile one (a 5-word MVCC version sort: over 5 minutes; its radix form:
# under a minute) — a cold smoke would not fit its 1200 s.  The engine is
# read at trace time; an exported YT_TPU_SORT_ENGINE wins.
os.environ.setdefault("YT_TPU_SORT_ENGINE", "radix")

HIGH_CARD = (
    "l_orderkey, sum(l_quantity) AS q FROM [//tpch/lineitem] "
    "GROUP BY l_orderkey ORDER BY sum(l_quantity) DESC, l_orderkey LIMIT 10")
SF1_LINEITEM_ROWS = 6_001_215
# Every select carries its own deadline: the serving plane's default (30 s,
# ServingConfig.default_timeout) is shorter than one cold chip compile.
SELECT_TIMEOUT = 1200.0


def emit(**fields):
    print(json.dumps(fields), flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def timed_select(yt, query, phase, warm_runs=3):
    """One cold + `warm_runs` warm select_rows(explain_analyze=True); every
    run must be answered by the compiled tier, the warm ones from the
    program cache.  Returns the cold run's rows."""
    t0 = time.perf_counter()
    cold = yt.select_rows(query, explain_analyze=True,
                          timeout=SELECT_TIMEOUT)
    cold_s = time.perf_counter() - t0
    check(yt.last_query_statistics.execution_tier == "compiled",
          f"{phase}: cold run answered by tier "
          f"{yt.last_query_statistics.execution_tier!r}")
    warm_s = []
    for _ in range(warm_runs):
        t0 = time.perf_counter()
        prof = yt.select_rows(query, explain_analyze=True,
                              timeout=SELECT_TIMEOUT)
        warm_s.append(time.perf_counter() - t0)
        stats = yt.last_query_statistics
        check(stats.execution_tier == "compiled",
              f"{phase}: warm run answered by tier {stats.execution_tier!r}")
        check(stats.compile_count == 0 and stats.cache_hits >= 1,
              f"{phase}: warm run compiled again (compile_count="
              f"{stats.compile_count}, cache_hits={stats.cache_hits})")
        check(prof.rows == cold.rows, f"{phase}: warm rows differ from cold")
    emit(phase=phase, cold_seconds=cold_s,
         cold_compile_time=cold.compile_time,
         cold_execute_time=cold.execute_time,
         warm_seconds=warm_s, warm_median_seconds=statistics.median(warm_s),
         rows_returned=len(cold.rows))
    return cold.rows


def check_q1(rows, host, label="q1"):
    """Q1's `rows` against plain numpy over the generator's host arrays:
    counts exact, `double` aggregates to rtol 1e-9."""
    import numpy as np

    from ytsaurus_tpu.models import tpch
    mask = host["l_shipdate"] <= tpch._DATE_1998_09_02
    qty, price = host["l_quantity"], host["l_extendedprice"]
    disc, tax = host["l_discount"], host["l_tax"]
    disc_price = price * (1 - disc)
    charge = disc_price * (1 + tax)
    want = {}
    for f, flag in enumerate([b"A", b"N", b"R"]):
        for s, status in enumerate([b"F", b"O"]):
            sel = mask & (host["l_returnflag"] == f) & \
                (host["l_linestatus"] == s)
            if not sel.any():
                continue
            want[(flag, status)] = {
                "sum_qty": qty[sel].sum(),
                "sum_base_price": price[sel].sum(),
                "sum_disc_price": disc_price[sel].sum(),
                "sum_charge": charge[sel].sum(),
                "avg_qty": qty[sel].mean(),
                "avg_price": price[sel].mean(),
                "avg_disc": disc[sel].mean(),
                "count_order": int(sel.sum()),
            }
    got = {(_as_bytes(r["l_returnflag"]), _as_bytes(r["l_linestatus"])): r
           for r in rows}
    check(set(got) == set(want),
          f"{label}: groups {sorted(got)} != {sorted(want)}")
    for key, agg in want.items():
        check(got[key]["count_order"] == agg["count_order"],
              f"{label} {key}: count {got[key]['count_order']} != "
              f"{agg['count_order']}")
        for name, value in agg.items():
            check(np.isclose(got[key][name], value, rtol=1e-9, atol=0.0),
                  f"{label} {key} {name}: {got[key][name]!r} != {value!r}")


def _as_bytes(value):
    return value.encode() if isinstance(value, str) else bytes(value)


def phase_static(yt, n_rows, seed):
    import numpy as np

    from ytsaurus_tpu.client import publish_table_chunks
    from ytsaurus_tpu.models import tpch

    t0 = time.perf_counter()
    chunk = tpch.generate_lineitem(n_rows, seed=seed)
    host = {c.name: np.asarray(chunk.columns[c.name].data[:n_rows])
            for c in tpch.LINEITEM_SCHEMA}
    gen_s = time.perf_counter() - t0
    plane_bytes = sum(col.data.nbytes + col.valid.nbytes
                      for col in chunk.columns.values())
    t0 = time.perf_counter()
    yt.create("table", "//tpch/lineitem", recursive=True,
              attributes={"schema": tpch.LINEITEM_SCHEMA})
    publish_table_chunks(yt, yt.cluster.chunk_store, "//tpch/lineitem",
                         [chunk])
    load_s = time.perf_counter() - t0
    check(yt.get("//tpch/lineitem/@row_count") == n_rows, "row_count attr")
    emit(phase="load_lineitem", rows=n_rows, capacity=chunk.capacity,
         plane_bytes=plane_bytes, generate_seconds=gen_s,
         load_seconds=load_s)
    del chunk

    check_q1(timed_select(yt, tpch.Q1, "q1"), host)

    # One warm run only: a warm run takes two minutes on a v5e chip.
    rows = timed_select(yt, HIGH_CARD, "high_cardinality_group_order",
                        warm_runs=1)
    sums = np.bincount(host["l_orderkey"], weights=host["l_quantity"])
    present = np.flatnonzero(np.bincount(host["l_orderkey"]))
    order = np.lexsort((present, -sums[present]))[:10]
    want_rows = [(int(present[i]), float(sums[present[i]])) for i in order]
    got_rows = [(r["l_orderkey"], r["q"]) for r in rows]
    check([k for k, _ in got_rows] == [k for k, _ in want_rows],
          f"high-cardinality keys {got_rows} != {want_rows}")
    check(np.allclose([q for _, q in got_rows], [q for _, q in want_rows],
                      rtol=1e-9, atol=0.0),
          f"high-cardinality sums {got_rows} != {want_rows}")
    return plane_bytes


def phase_dynamic(yt, n_rows, seed):
    import numpy as np

    from ytsaurus_tpu.schema import TableSchema

    path = "//smoke/dyn"
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("v", "int64")], unique_keys=True)
    yt.create("table", path, recursive=True,
              attributes={"schema": schema, "dynamic": True})
    yt.mount_table(path)
    t0 = time.perf_counter()
    for lo in range(0, n_rows, 50_000):
        hi = min(lo + 50_000, n_rows)
        yt.insert_rows(path, [{"k": i, "v": i * 3} for i in range(lo, hi)])
    insert_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    keys = [int(k) for k in rng.integers(0, n_rows, 64)]
    want = [{"k": k, "v": k * 3} for k in keys]

    def lookup(label):
        t0 = time.perf_counter()
        got = yt.lookup_rows(path, [(k,) for k in keys])
        cold = time.perf_counter() - t0
        check(got == want, f"lookup_rows {label}: {got[:3]} != {want[:3]}")
        t0 = time.perf_counter()
        check(yt.lookup_rows(path, [(k,) for k in keys]) == want,
              f"lookup_rows {label} (warm)")
        return cold, time.perf_counter() - t0

    before = lookup("before freeze")
    t0 = time.perf_counter()
    yt.freeze_table(path)
    freeze_s = time.perf_counter() - t0
    after = lookup("after freeze")
    emit(phase="dynamic_table", rows=n_rows, insert_seconds=insert_s,
         freeze_seconds=freeze_s,
         lookup64_before_freeze_cold_warm_seconds=before,
         lookup64_after_freeze_cold_warm_seconds=after)

    lo, hi = n_rows // 4, n_rows // 2
    rows = timed_select(
        yt, f"sum(v) AS s, count(*) AS c FROM [{path}] "
            f"WHERE k >= {lo} AND k < {hi} GROUP BY k >= {lo} AS in_range",
        "dynamic_range_aggregate")
    check(len(rows) == 1 and rows[0]["c"] == hi - lo
          and rows[0]["s"] == 3 * sum(range(lo, hi)),
          f"range aggregate {rows}")

    tx = yt.start_transaction()
    yt.insert_rows(path, [{"k": n_rows + 7, "v": -1}, {"k": keys[0], "v": -2}],
                   tx=tx)
    check(yt.lookup_rows(path, [(n_rows + 7,)]) == [None],
          "uncommitted write is visible")
    yt.commit_transaction(tx)
    got = yt.lookup_rows(path, [(n_rows + 7,), (keys[0],), (keys[1],)])
    check(got == [{"k": n_rows + 7, "v": -1}, {"k": keys[0], "v": -2},
                  {"k": keys[1], "v": keys[1] * 3}],
          f"committed transaction read back as {got}")
    emit(phase="transaction", ok=True)


def phase_sort(yt, n_rows, seed):
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    ks = rng.integers(-2**40, 2**40, n_rows)
    rows = [{"k": int(k), "v": i} for i, k in enumerate(ks)]
    t0 = time.perf_counter()
    yt.write_table("//smoke/sort_in", rows)
    write_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    yt.run_sort("//smoke/sort_in", "//smoke/sort_out", sort_by="k")
    sort_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = yt.read_table("//smoke/sort_out")
    read_s = time.perf_counter() - t0
    check(len(out) == n_rows, f"sorted table has {len(out)} rows")
    out_k = np.array([r["k"] for r in out])
    check(bool(np.all(out_k[:-1] <= out_k[1:])), "run_sort output unordered")
    check(sorted((r["k"], r["v"]) for r in out)
          == sorted((r["k"], r["v"]) for r in rows),
          "run_sort output is not a permutation of its input")
    emit(phase="sort_operation", rows=n_rows, write_table_seconds=write_s,
         run_sort_seconds=sort_s, read_table_seconds=read_s)


def phase_four_chips(n_rows, seed, devs):
    """`--chips 4`: Q1 as ONE fused SPMD program over a four-device mesh
    (`coordinate_distributed`), against the same query on one device and
    numpy.  No other phase runs."""
    import numpy as np

    from ytsaurus_tpu.chunks.columnar import concat_chunks
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        ShardedTable,
        coordinate_distributed,
        host_sync_count,
    )
    from ytsaurus_tpu.parallel.mesh import make_mesh
    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.query.statistics import QueryStatistics

    per_shard = n_rows // 4
    shards = [tpch.generate_lineitem(per_shard, seed=seed + i)
              for i in range(4)]
    host = {c.name: np.concatenate(
                [np.asarray(sh.columns[c.name].data[:per_shard])
                 for sh in shards]) for c in tpch.LINEITEM_SCHEMA}
    plan = build_query(tpch.Q1, {"//tpch/lineitem": tpch.LINEITEM_SCHEMA})
    mesh = make_mesh(4, devices=devs)
    table = ShardedTable.from_chunks(mesh, shards)
    for name, col in table.columns.items():
        homes = {sh.device for sh in col.data.addressable_shards}
        check(homes == set(devs[:4]),
              f"column {name} lives on {homes}, not on four devices")
    del table

    evaluator = DistributedEvaluator(mesh)
    seconds = []
    for _ in range(4):
        stats = QueryStatistics()
        syncs = host_sync_count()
        t0 = time.perf_counter()
        rows = coordinate_distributed(plan, mesh, shards,
                                      evaluator=evaluator,
                                      stats=stats).to_rows()
        seconds.append(time.perf_counter() - t0)
        check(stats.whole_plan == 1,
              f"the fused whole-plan rung did not serve Q1: {stats}")
        check(host_sync_count() - syncs == 1,
              f"{host_sync_count() - syncs} host syncs, expected 1")
    t0 = time.perf_counter()
    single = Evaluator().run_plan(plan, concat_chunks(shards)).to_rows()
    single_s = time.perf_counter() - t0
    emit(phase="q1_four_chips", rows=4 * per_shard, shards=4,
         cold_seconds=seconds[0], warm_seconds=seconds[1:],
         warm_median_seconds=statistics.median(seconds[1:]),
         one_device_cold_seconds=single_s)

    check_q1(rows, host, "four chips")
    check_q1(single, host, "one device")


def verdict(devs):
    """THE last line: the device as JAX reports it."""
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=SF1_LINEITEM_ROWS,
                    help="lineitem rows (default TPC-H SF1)")
    ap.add_argument("--dyn-rows", type=int, default=1_000_000)
    ap.add_argument("--sort-rows", type=int, default=200_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: ONLY Q1 over a four-device mesh and its "
                         "one-device comparison")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: run where JAX finds no TPU; the "
                         "last line then names the true platform")
    args = ap.parse_args()

    from ytsaurus_tpu.utils.backend import place_compile_cache
    cache_dir = place_compile_cache()

    import jax
    import jaxlib

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: JAX found no TPU (platform={platform!r})",
              file=sys.stderr)
        return 1
    check(jax.default_backend() == platform, "default backend != devices()[0]")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devs)} device(s)")
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = None

    from ytsaurus_tpu import native
    from ytsaurus_tpu.client import connect

    emit(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, platform=platform, device_kind=devs[0].device_kind,
         device_count=len(devs), compile_cache_dir=cache_dir,
         native_codec_loaded=native.lib() is not None,
         sort_engine=os.environ["YT_TPU_SORT_ENGINE"],
         rows=args.rows, dyn_rows=args.dyn_rows, sort_rows=args.sort_rows,
         seed=args.seed)

    t_start = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(args.rows, args.seed, devs)
        emit(phase="done", total_seconds=time.perf_counter() - t_start)
        return verdict(devs)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
        yt = connect(root)
        plane_bytes = phase_static(yt, args.rows, args.seed)
        phase_dynamic(yt, args.dyn_rows, args.seed)
        phase_sort(yt, args.sort_rows, args.seed)

        stray = [str(a.devices()) for a in jax.live_arrays()
                 if a.devices() != {devs[0]}]
        check(not stray, f"arrays off {devs[0]}: {stray[:5]}")
        mem = devs[0].memory_stats()
        peak = mem.get("peak_bytes_in_use") if mem else None
        if platform == "tpu":
            check(peak is not None and peak >= plane_bytes,
                  f"peak_bytes_in_use {peak} < lineitem planes {plane_bytes}:"
                  f" the table never lived in HBM")
        emit(phase="done", total_seconds=time.perf_counter() - t_start,
             peak_bytes_in_use=peak, lineitem_plane_bytes=plane_bytes,
             live_arrays=len(jax.live_arrays()))

    return verdict(devs)


if __name__ == "__main__":
    sys.exit(main())
