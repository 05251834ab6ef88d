#!/usr/bin/env python3
"""Checks of the yardstick itself, with no chip and none of the program:

    python3 benchmark/selfcheck.py

- `trace_reduce` on traces written out by hand (busy, idle, time per
  program and operation, and the booking of idle gaps to the innermost
  span of the client's host line can be worked out on paper) and on
  one small trace recorded on the chip (`data/q1_tiny.xplane.pb.gz`: Q1 over
  60,000 rows, a window of 0.2 s), against the numbers in
  `data/q1_tiny.expected.json`;
- `peaks.json`: the v5e's peaks are there and an unknown `device_kind` is an
  error, never a default;
- the LINEITEM generator's shapes (clause 4.2.3) and the numpy spec
  evaluator against hand-written Q1 and group + top-k arithmetic.
"""

import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import trace_reduce  # noqa: E402
from generators import tpch_dbgen  # noqa: E402
from readers import trace as trace_reader  # noqa: E402
from reference import ql_spec  # noqa: E402

# Device ops cover [0,20) and [30,40) ns of a 50 ns window of two client
# calls [0,25) and [25,50): busy 30 ns, idle 40%; the gap [20,30) is split
# at the calls' boundary (5 ns to the first, 5 to the second), the gap
# [40,50) lies in the second call.
HAND_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 15000 }
    events { metadata_id: 1 offset_ps: 30000 duration_ps: 10000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 30000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_run(123)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 25000 }
    events { metadata_id: 2 offset_ps: 25000 duration_ps: 25000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.select.q1" } }
  event_metadata { key: 2 value { id: 2 name: "bench.insert" } } }
"""


# Program spans (`yt.*`) nested in the client's calls on its host line
# `python3`, times in ns: bench.select.q1 [0,100) > yt.query.select [2,90) >
# yt.query.plan [2,10), yt.coordinator.shard [10,60) > yt.evaluator.launch
# [10,12), yt.evaluator.sync [12,58); then yt.query.decode [60,75),
# yt.query.record [75,80); a second call bench.select.q1 [110,130) opens no
# span.  A prefetch thread's yt.query.stage [40,55) is on another line, and
# JAX's own PjitFunction(run) [30,33) is no annotation of ours: neither owns
# anything.  The device runs a %while [0,40) whose body ops take [5,15)
# and [20,30), and a copy [60,70): busy 50 ns of 130.  Its idle [40,60) and
# [70,130) go to the innermost span on `python3`: sync 18 (40-58), shard 2
# (58-60), decode 5, record 5, query.select's own 10 (80-90), the client's
# own 10 (90-100) + 20 (110-130), between the calls 10 (100-110).
NESTED_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 20000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 60000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), body=%body" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8]{0} copy(f32[8]{0} %p)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 2000 duration_ps: 88000 }
    events { metadata_id: 3 offset_ps: 2000 duration_ps: 8000 }
    events { metadata_id: 4 offset_ps: 10000 duration_ps: 50000 }
    events { metadata_id: 5 offset_ps: 10000 duration_ps: 2000 }
    events { metadata_id: 6 offset_ps: 12000 duration_ps: 46000 }
    events { metadata_id: 7 offset_ps: 60000 duration_ps: 15000 }
    events { metadata_id: 8 offset_ps: 75000 duration_ps: 5000 }
    events { metadata_id: 9 offset_ps: 30000 duration_ps: 3000 }
    events { metadata_id: 1 offset_ps: 110000 duration_ps: 20000 } }
  lines { id: 2 name: "shard-prefetch_0" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 40000 duration_ps: 15000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.select.q1" } }
  event_metadata { key: 2 value { id: 2 name: "yt.query.select" } }
  event_metadata { key: 3 value { id: 3 name: "yt.query.plan" } }
  event_metadata { key: 4 value { id: 4 name: "yt.coordinator.shard" } }
  event_metadata { key: 5 value { id: 5 name: "yt.evaluator.launch" } }
  event_metadata { key: 6 value { id: 6 name: "yt.evaluator.sync" } }
  event_metadata { key: 7 value { id: 7 name: "yt.query.decode" } }
  event_metadata { key: 8 value { id: 8 name: "yt.query.record" } }
  event_metadata { key: 9 value { id: 9 name: "PjitFunction(run)" } }
  event_metadata { key: 10 value { id: 10 name: "yt.query.stage" } } }
"""
NESTED_IDLE_NS = {
    "yt.evaluator.sync": 18, "yt.coordinator.shard": 2,
    "yt.query.decode": 5, "yt.query.record": 5, "yt.query.select": 10,
    "bench.select.q1": 30, "between_calls": 10}


def close(a, b, tol=1e-12):
    return abs(a - b) <= tol * max(1.0, abs(b))


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    print("ok:", what)


def check_hand_trace():
    from jax.profiler import ProfileData
    got = trace_reduce.reduce_planes(
        ProfileData.from_text_proto(HAND_TRACE).planes)
    check(close(got["busy_s"], 30e-9) and close(got["window_s"], 50e-9),
          f"hand trace: busy 30 ns of 50 ns (got {got['busy_s']}, "
          f"{got['window_s']})")
    (name, seconds, calls), = got["programs"]
    check(name == "jit_run" and calls == 2 and close(seconds, 30e-9),
          "hand trace: jit_run ran twice for 30 ns")
    ops = dict(got["device_ops"])
    check(close(ops["%fusion.1 fusion f32[8]"], 20e-9) and
          close(ops["%copy.2 copy f32[8]"], 15e-9),
          "hand trace: time per operation, names shortened")
    check(idle_ns(got) == {"bench.insert": 15, "bench.select.q1": 5},
          "hand trace: 20 ns of idle gaps, split at the calls' boundary: "
          "15 ns to bench.insert, 5 ns to bench.select.q1")


def idle_ns(reduced):
    """The idle booking in whole nanoseconds, where it is exact."""
    out = {name: round(seconds * 1e9) for name, seconds in
           reduced["idle_gaps"]}
    for name, seconds in reduced["idle_gaps"]:
        if abs(seconds * 1e9 - out[name]) > 1e-6:
            raise AssertionError(f"{name}: {seconds} s is no whole ns")
    return out


def check_nested_trace():
    from types import SimpleNamespace

    from jax.profiler import ProfileData
    got = trace_reduce.reduce_planes(
        ProfileData.from_text_proto(NESTED_TRACE).planes)
    check(close(got["busy_s"], 50e-9) and close(got["window_s"], 130e-9),
          f"nested trace: busy 50 ns of 130 ns (got {got['busy_s']}, "
          f"{got['window_s']})")
    check(idle_ns(got) == NESTED_IDLE_NS,
          f"nested trace: idle booked to the innermost span of the "
          f"client's line, to the ns ({idle_ns(got)})")
    check([name for name, _ in got["idle_gaps"]][0] == "bench.select.q1",
          "nested trace: idle_gaps sorted by seconds")
    ops = {name: round(seconds * 1e9) for name, seconds in got["device_ops"]}
    check(ops == {"%while.1 while (s32[], u32[8])": 20,
                  "%fusion.2 fusion u32[8]": 20, "%copy.3 copy f32[8]": 10},
          f"nested trace: a %while keeps only what its body leaves "
          f"uncovered ({ops})")
    check(sum(ops.values()) == 50, "nested trace: self times add up to busy")
    ctx = SimpleNamespace(record=SimpleNamespace(trace=got))
    unspanned = trace_reader.read(
        {"kind": "trace", "stat": "idle_unspanned", "root": "yt.query.select"},
        ctx)
    check(close(unspanned, 50 / 130 * 100),
          f"nested trace: idle_unspanned = (10 + 30 + 10) / 130 "
          f"({unspanned}%)")


def check_recorded_trace():
    import gzip

    from jax.profiler import ProfileData
    with open(os.path.join(BENCH_DIR, "data", "q1_tiny.expected.json")) as f:
        want = json.load(f)
    with gzip.open(os.path.join(BENCH_DIR, "data",
                                "q1_tiny.xplane.pb.gz")) as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    got = trace_reduce.reduce_planes(planes)
    check(close(got["busy_s"], want["busy_s"], 1e-9) and
          close(got["window_s"], want["window_s"], 1e-9),
          f"recorded trace: busy {got['busy_s']} s of {got['window_s']} s")
    check(0 < got["busy_s"] < got["window_s"], "recorded trace: 0 < busy < "
          "window")
    programs = {name: (seconds, calls)
                for name, seconds, calls in got["programs"]}
    check(programs["jit_run"][1] == want["jit_run_calls"],
          f"recorded trace: jit_run ran {want['jit_run_calls']} times, once "
          f"per client call")
    check(close(programs["jit_run"][0], want["jit_run_s"], 1e-9),
          "recorded trace: jit_run's device seconds")
    check(sum(s for _, s in got["device_ops"]) >= got["busy_s"] * 0.999,
          "recorded trace: operations add up to at least the busy time")
    check(close(sum(s for _, s in got["idle_gaps"]) + got["busy_s"],
                got["window_s"], 1e-6),
          "recorded trace: idle gaps + busy = window")


def check_peaks():
    v5e = trace_reader.peaks_for("TPU v5 lite")
    check(v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12,
          "peaks: v5e 819 GB/s, 197 TFLOP/s bf16")
    for kind in ("TPU v9", "cpu", "source"):
        try:
            trace_reader.peaks_for(kind)
        except KeyError:
            continue
        raise AssertionError(f"peaks: {kind!r} did not raise")
    print("ok: peaks: an unknown device kind is an error")


def check_generator():
    """The table has the shapes clause 4.2.3 gives it."""
    with open(os.path.join(BENCH_DIR, "configs", "tpch-lineitem-sf1.json")) as f:
        config = json.load(f)
    sizes = config["rehearse_sizes"]
    host, vocabs = tpch_dbgen.generate(config, 3000000019, sizes)
    check(set(host) == {c["name"] for c in config["columns"]} and
          len(host) == 16 and
          all(len(a) == sizes["rows"] for a in host.values()),
          "generator: all 16 LINEITEM columns, the stated row count")
    lines = np.bincount(host["l_orderkey"])
    lines = lines[lines > 0]
    check(len(lines) == sizes["orders"] and lines.min() >= 1 and
          lines.max() <= 7 and np.all(host["l_orderkey"] % 32 < 8),
          "generator: 1..7 lines to an order, 8 keys used of every 32")
    check(np.all(host["l_linestatus"] ==
                 (host["l_shipdate"] > tpch_dbgen.CURRENT_DATE)) and
          np.all((host["l_returnflag"] == 1) ==
                 (host["l_receiptdate"] > tpch_dbgen.CURRENT_DATE)),
          "generator: line status and return flag follow from the dates")
    cents = host["l_extendedprice"] * 100
    check(np.all(np.abs(cents - np.rint(cents)) < 1e-6) and
          np.all(host["l_extendedprice"] >= 900 * host["l_quantity"]) and
          set(np.unique(host["l_discount"])) ==
          {i / 100.0 for i in range(11)},
          "generator: prices are whole cents of quantity x retail price, "
          "discounts 0.00..0.10")
    sizes_of = np.char.str_len(host["l_comment"])
    check(host["l_comment"].dtype == np.dtype("S43") and
          sizes_of.min() >= 10 and 25 < sizes_of.mean() < 29,
          f"generator: comments of 10..43 bytes, {sizes_of.mean():.1f} on "
          f"average")
    again, _ = tpch_dbgen.generate(config, 3000000019, sizes)
    check(all(np.array_equal(host[c], again[c]) for c in host),
          "generator: the same seed gives the same table")
    return host, vocabs


def check_spec_evaluator(host, vocabs):
    """Against arithmetic written out by hand, as chip_smoke.py does."""
    with open(os.path.join(BENCH_DIR, "traffic", "q1_stream.json")) as f:
        q1 = json.load(f)["queries"][0]["reference"]
    rows = {(r["l_returnflag"], r["l_linestatus"]): r
            for r in ql_spec.evaluate(q1, host, vocabs)}
    check(sorted(rows) == [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")],
          "spec evaluator: Q1 gives the spec's four groups")
    mask = host["l_shipdate"] <= 10471
    disc_price = host["l_extendedprice"] * (1 - host["l_discount"])
    charge = disc_price * (1 + host["l_tax"])
    for (flag, status), row in rows.items():
        sel = mask & (host["l_returnflag"] == "ANR".index(flag)) & \
            (host["l_linestatus"] == "FO".index(status))
        assert row["count_order"] == int(sel.sum())
        for name, want in (
                ("sum_qty", host["l_quantity"][sel].sum()),
                ("sum_disc_price", disc_price[sel].sum()),
                ("sum_charge", charge[sel].sum()),
                ("avg_disc", host["l_discount"][sel].mean())):
            assert np.isclose(row[name], want, rtol=1e-13), (name, row)
    print("ok: spec evaluator: Q1 equals the hand-written arithmetic")

    with open(os.path.join(BENCH_DIR, "traffic",
                           "group_topk_stream.json")) as f:
        topk = json.load(f)["queries"][0]["reference"]
    got = [(r["l_orderkey"], r["revenue"])
           for r in ql_spec.evaluate(topk, host, vocabs)]
    sums = np.bincount(host["l_orderkey"], weights=disc_price)
    present = np.flatnonzero(np.bincount(host["l_orderkey"]))
    order = np.lexsort((present, -sums[present]))[:10]
    check([k for k, _ in got] == [int(present[i]) for i in order] and
          np.allclose([v for _, v in got], sums[present[order]], rtol=1e-13),
          "spec evaluator: group + top-k equals bincount + lexsort")
    for name, spec in (("Q1", q1), ("group + top-k", topk)):
        f32 = ql_spec.evaluate(spec, host, vocabs, dtype=np.float32)
        mismatched, gap = ql_spec.compare(
            spec, f32, ql_spec.evaluate(spec, host, vocabs))
        check(mismatched > 0 or 1e-9 < gap < 1e-4, f"spec evaluator: {name}'s "
              f"float32 control lies {gap:.2e} off ({mismatched} rows)")


def main():
    check_hand_trace()
    check_nested_trace()
    check_peaks()
    check_spec_evaluator(*check_generator())
    check_recorded_trace()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
