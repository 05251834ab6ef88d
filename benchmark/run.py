#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  The cell's configuration, traffic mix and metrics
are data files found by the names in BENCHMARK.json (`configs/`, `traffic/`,
`metrics/`); drivers, generators and metric readers are modules found by the
names those files give.  The run loads the cell's data from the seed, warms
the cell's own program shapes (set-up), measures for `--seconds`, checks what
the timed calls returned against the plain reference, and prints the result
as the last line of standard output.  It fails where JAX finds no TPU;
`--rehearse` (never used by the driver) runs tiny sizes on the CPU, says so
on standard error and prints its readings there: its result line carries no
metric.
"""

import sys
import time

_T_PROCESS = time.perf_counter()

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)


# The program's span ring in a traced run: a 51-s window of ~20 spans a
# select down to a 3.9-ms select (~138 MB of host memory at ~527 B a span).
# Untraced runs keep the program's own ring.
TRACED_RING_SPANS = 262144


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


class Record:
    """What one window did: a line per request and (traced runs) the
    reduced device trace."""

    def __init__(self):
        self.requests = []
        self.failures = []
        self.window_start = None
        self.setup_s = None
        self.trace = None

    def start(self):
        self.window_start = time.perf_counter()
        return self.window_start

    def request(self, op, t0, t1, **fields):
        line = dict(fields, op=op, start_s=t0 - self.window_start,
                    wall_s=t1 - t0, end=t1)
        if fields.get("execute_s") is not None:
            line["host_s"] = line["wall_s"] - fields["execute_s"]
        self.requests.append(line)

    def failed(self, op, t0, t1, err):
        self.failures.append({"op": op, "wall_s": t1 - t0, "end": t1,
                              "error": repr(err)[:300]})

    @property
    def busy_window_s(self):
        """Window start to the last completion: what a rate divides by."""
        ends = [r["end"] for r in self.requests]
        return (max(ends) - self.window_start) if ends else 0.0


class Context:
    """The cell as its data files describe it, and what the drivers and
    readers share."""

    def __init__(self, bench, workload, seed, rehearse):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.bench = bench
        self.cell = cells[workload]
        config_entry = next(c for c in bench["configs"]
                            if c["name"] == self.cell["config"])
        with open(os.path.join(ROOT, config_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.cell["traffic"] + ".json")
        self.seed = seed
        self.rehearse = rehearse
        self.record = Record()
        self.annotate = contextlib.nullcontext   # set once JAX is up
        self.device_kind = None
        self.driver = None
        self._phase_t = time.perf_counter()

    def phase(self, what):
        """Builder's aid on standard error: where set-up's time goes."""
        now = time.perf_counter()
        print(f"phase {what}: {now - self._phase_t:.2f} s", file=sys.stderr)
        self._phase_t = now

    @property
    def sizes(self):
        """The configuration's sizes; a rehearsal takes its tiny ones."""
        return self.config["rehearse_sizes" if self.rehearse else "sizes"]

    def module(self, package, name):
        return importlib.import_module(f"{package}.{name}")

    def metric_defs(self, group):
        """The metrics of `group` (end_to_end | per_layer) this cell
        reports, each with its definition file."""
        out = []
        for entry in self.bench[group]:
            cells = entry.get("workloads")
            if cells is not None and self.cell["name"] not in cells:
                continue
            if cells is None and group == "per_layer":
                moved = next(m for m in self.bench["end_to_end"]
                             if m["name"] == entry["moves"])
                if "workloads" in moved and \
                        self.cell["name"] not in moved["workloads"]:
                    continue
            out.append((entry, metric_definition(entry["name"])))
        return out


def metric_definition(name):
    """A metric's definition file: `metrics/<name>.json`, or the one of the
    name's stem (`device_idle.topk` reads `metrics/device_idle.json`), so
    that one definition serves the same reading in several cells."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise SystemExit(f"metric {name!r} has no definition under metrics/")


def state_dir():
    """A directory of this run alone for the cluster's files and the
    trace: under the checkout and outside the benchmark's own `paths`,
    removed when the run ends."""
    parent = os.path.join(ROOT, ".bench_state")
    os.makedirs(parent, exist_ok=True)
    return tempfile.mkdtemp(prefix="run-", dir=parent)


def place_compile_cache(jax):
    """`JAX_COMPILATION_CACHE_DIR` where set, else the fixed
    `<checkout>/.jax_cache`; every program is kept, however fast it
    compiled, so that a second run compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def compute_metrics(ctx, group):
    metrics = {}
    for entry, definition in ctx.metric_defs(group):
        reader = ctx.module("readers", definition["kind"])
        value = reader.read(definition, ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return metrics


def judge(compared, limits):
    """Each number compared beside its limit, and whether all hold."""
    out, ok = {}, True
    for name, value in compared.items():
        limit = limits[name]["limit"]
        out[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return out, ok


def make_context(bench, args, jax):
    """The cell's context with its driver, once JAX is up."""
    devs = jax.devices()
    ctx = Context(bench, args.workload, args.seed, args.rehearse)
    for name, value in ctx.config.get("env", {}).items():
        os.environ[name] = value      # read by the program at trace time
    if len(devs) < ctx.cell["chips"]:
        raise SystemExit(f"benchmark: the cell needs {ctx.cell['chips']} "
                         f"chip(s), JAX sees {len(devs)}")
    ctx.annotate = jax.profiler.TraceAnnotation
    ctx.device_kind = devs[0].device_kind
    ctx.driver = ctx.module("drivers", ctx.traffic["driver"]).Driver(ctx)
    return ctx


def run_cell(bench, args, jax, t_start, with_control=False):
    """One cell once, in this process: set-up, window, check.  Returns the
    result line as a dict, and the control's readings where `with_control`
    asks for them (readings.py and the tests; never a benchmark run)."""
    devs = jax.devices()
    platform = devs[0].platform
    ctx = make_context(bench, args, jax)
    driver, record = ctx.driver, ctx.record

    from ytsaurus_tpu.client import connect

    driver.prepare()
    state = state_dir()
    root = os.path.join(state, "cluster")
    trace_dir = os.path.join(state, "trace")
    try:
        # Every run loads its tables from the seed, then opens the cluster
        # anew from its files, as a served process finds a deployed table.
        driver.load(connect(root))
        ctx.phase("load")
        yt = connect(root, fresh=True)
        driver.warm(yt)
        ctx.phase("warm")
        # Set-up's garbage (the loading cluster and what it was written
        # from: ~600,000 objects in cycles in the dynamic cell) is freed
        # here, not by the first full collection inside the window.
        gc.collect()
        ctx.phase("collect")
        record.setup_s = time.perf_counter() - t_start

        if args.trace:
            # the program's spans of the whole window, for the span reader
            from ytsaurus_tpu.utils import tracing
            tracing.get_collector().set_capacity(TRACED_RING_SPANS)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            driver.window(yt, args.seconds, record)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        if args.trace:
            import trace_reduce
            record.trace = trace_reduce.reduce_dir(trace_dir)
            if args.dump_dir:
                dump_trace(args.dump_dir, trace_dir, record.trace)
        if args.dump_dir:
            os.makedirs(args.dump_dir, exist_ok=True)
            with open(os.path.join(args.dump_dir, "requests.json"), "w") as f:
                json.dump(record.requests, f)

        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devs[:ctx.cell["chips"]])
        # The reference runs last: the window is closed and the peak read.
        compared, correct = judge(driver.check(yt), ctx.traffic["compared"])
        control = None
        if with_control:
            control, _ = judge(driver.check(yt, control=ctx.traffic["control"]),
                               ctx.traffic["compared"])
    finally:
        shutil.rmtree(state, ignore_errors=True)

    metrics = compute_metrics(ctx, "per_layer" if args.trace else "end_to_end")
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct and not record.failures),
              "attempted": len(record.requests) + len(record.failures),
              "failed": len(record.failures),
              "metrics": metrics, "device": device}
    if args.trace and record.trace:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        result["breakdown"] = {"device_ops": record.trace["device_ops"][:10],
                               "idle_gaps": record.trace["idle_gaps"][:10]}
    for failure in record.failures[:5]:
        print(f"failed request: {failure}", file=sys.stderr)
    if args.rehearse:
        # never under a device metric's name: a rehearsal's readings go to
        # standard error and its result line carries no metric
        print(f"REHEARSAL on {platform}: tiny sizes, no number here is a "
              f"measurement: {json.dumps(metrics)}", file=sys.stderr)
        result["metrics"] = {}
    result["compared"] = compared
    print_slowest(record)
    return result, control


def dump_trace(dump_dir, trace_dir, reduced):
    """Builder's aid (--dump-dir): the trace as one looks at it by hand."""
    import trace_reduce
    os.makedirs(dump_dir, exist_ok=True)
    path = trace_reduce.find_xplane(trace_dir)
    with open(os.path.join(dump_dir, "trace_describe.txt"), "w") as f:
        f.write(trace_reduce.describe(path, events=12))
    with open(os.path.join(dump_dir, "trace_reduced.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    if os.path.getsize(path) < 8 << 20:
        shutil.copy(path, os.path.join(dump_dir, "trace.xplane.pb"))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU sandbox only: tiny sizes, marked as a "
                         "rehearsal on standard error; never a measurement")
    ap.add_argument("--dump-dir", default=None,
                    help="builder's aid: keep the window's request lines "
                         "there and, with --trace 1, a description of the "
                         "trace and its full reduction")
    return ap.parse_args(argv)


def start_jax(rehearse):
    """JAX with the compile cache placed; None where no TPU is found and
    this is no rehearsal."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if not rehearse:     # a rehearsal's CPU programs are not worth keeping
        place_compile_cache(jax)
    platform = jax.devices()[0].platform
    if platform != "tpu" and not rehearse:
        print(f"benchmark: JAX found no TPU (platform={platform!r})",
              file=sys.stderr)
        return None
    return jax


def print_slowest(record, n=5):
    """Builder's aid on standard error: the spread of the window's request
    times, and when the slowest came."""
    walls = sorted(r["wall_s"] for r in record.requests)
    if not walls:
        return
    slow = sorted(record.requests, key=lambda r: -r["wall_s"])[:n]
    print(f"requests {len(walls)}: min {walls[0]:.4f} median "
          f"{walls[len(walls) // 2]:.4f} max {walls[-1]:.4f} s; slowest "
          + ", ".join(f"{r['op']}@{r['start_s']:.2f}s={r['wall_s']:.3f}s"
                      for r in slow), file=sys.stderr)


def print_compared(result):
    for name, pair in result["compared"].items():
        print(f"compared {name}: value {pair['value']!r} limit "
              f"{pair['limit']!r}", file=sys.stderr)


def main(argv=None):
    args = parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    jax = start_jax(args.rehearse)
    if jax is None:
        return 1
    result, _ = run_cell(bench, args, jax, _T_PROCESS)
    print_compared(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
