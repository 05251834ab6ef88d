"""Reader `span`: the program's own trace spans over the window's requests
(`ytsaurus_tpu/utils/tracing.py`: every `select_rows` roots one trace).

    {"kind": "span", "root": "query.select", "stat": "self_time",
     "spans": ["query.plan"], "scale": 1000}
    {"kind": "span", "root": "query.select", "stat": "duration",
     "spans": ["query.record"], "scale": 1000}
    {"kind": "span", "root": "query.select", "stat": "remainder",
     "minus": ["plan_ms_per_select", ...], "scale": 1000}

After the window the reader takes the collector's ring, keeps the traces
whose root started inside the window (`start_mono` is on the clock of
`Record.window_start`), and per trace adds the `self_time` (the span's
duration less what its children covered) or the `duration` of every span
of the names given: a name that occurs twice in a trace is added.
`remainder` is the root's duration less the per-trace values of the
definitions it names (files of this directory's `metrics/`), so that
these and the remainder add up to the root exactly, trace by trace.  The
reading is the median over the window's traces.

Nothing is read (None, and a line on standard error that says why) where
the program has no such spans (an older program), where the ring dropped
spans of the window, or where fewer whole traces than completed requests
are found.
"""

import json
import os
import sys

import numpy as np

_METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def read(definition, ctx):
    traces = window_traces(definition["root"], ctx)
    if not traces:
        return None
    values = [trace_value(definition, trace) for trace in traces]
    return float(np.median(values)) * definition.get("scale", 1)


def trace_value(definition, trace):
    """One trace's number: `trace` maps a span name to the trace's spans
    of that name, the root under "" besides."""
    stat = definition["stat"]
    if stat == "remainder":
        named = sum(trace_value(load_definition(stem), trace)
                    for stem in definition["minus"])
        return trace[""][0].duration - named
    if stat not in ("self_time", "duration"):
        raise ValueError(f"unknown statistic {stat!r}")
    return sum(getattr(span, stat) for name in definition["spans"]
               for span in trace.get(name, ()))


def load_definition(stem):
    with open(os.path.join(_METRICS, stem + ".json")) as f:
        return json.load(f)


def window_traces(root_name, ctx):
    """The window's traces, read once per run; None where they cannot be
    trusted to be the whole window."""
    cached = ctx.__dict__.setdefault("_span_windows", {})
    if root_name not in cached:
        cached[root_name] = _window_traces(root_name, ctx.record)
    return cached[root_name]


def _window_traces(root_name, record):
    from ytsaurus_tpu.utils import tracing
    collector = tracing.get_collector()
    spans = collector.snapshot()
    dropped = getattr(collector, "dropped", None)
    if dropped is None:         # a program from before PR 27
        return _nothing("the program's spans carry no self time")
    start = record.window_start
    # The ring keeps the newest spans in the order they finished: the
    # window is whole if nothing was dropped, or the oldest span kept
    # began before the window did.
    if dropped and (not spans or spans[0].start_mono >= start):
        return _nothing(f"the ring dropped spans of the window "
                        f"({dropped} dropped since the process began, "
                        f"{len(spans)} kept)")
    by_trace = {}
    for span in spans:
        by_trace.setdefault(span.trace_id, {}).setdefault(
            span.name, []).append(span)
    traces = []
    for trace in by_trace.values():
        roots = [s for s in trace.get(root_name, ())
                 if s.parent_span_id is None]
        if len(roots) == 1 and roots[0].start_mono >= start \
                and "error" not in roots[0].tags:
            trace[""] = roots
            traces.append(trace)
    if len(traces) < len(record.requests) or not traces:
        return _nothing(f"{len(traces)} whole {root_name} traces for "
                        f"{len(record.requests)} completed requests")
    traces.sort(key=lambda trace: trace[""][0].start_mono)
    print_slowest(traces, record)
    return traces


def _nothing(why):
    print(f"span reader: nothing read: {why}", file=sys.stderr)
    return None


def print_slowest(traces, record, n=5):
    """Builder's aid on standard error: the median self time of every span
    name over the window, the client's time outside the root, and the
    slowest traces each with its per-span self times (ms)."""
    def self_ms(trace):
        return {name: sum(s.self_time for s in spans) * 1e3
                for name, spans in trace.items() if name}
    names = sorted({name for trace in traces for name in trace if name})
    per_trace = [self_ms(trace) for trace in traces]
    medians = {name: float(np.median([t.get(name, 0.0) for t in per_trace]))
               for name in names}
    root_ms = float(np.median([t[""][0].duration for t in traces])) * 1e3
    print(f"spans {len(traces)} traces: median root {root_ms:.3f} ms; "
          f"median self ms " + ", ".join(
              f"{name}={value:.3f}" for name, value in medians.items()),
          file=sys.stderr)
    if len(traces) == len(record.requests):
        # one client, closed loop: the n-th trace is the n-th request
        outside = [r["wall_s"] - t[""][0].duration
                   for r, t in zip(record.requests, traces)]
        print(f"spans: client wall outside the root, median "
              f"{float(np.median(outside)) * 1e3:.3f} ms", file=sys.stderr)
    for trace in sorted(traces, key=lambda t: -t[""][0].duration)[:n]:
        root = trace[""][0]
        print(f"slow trace @{root.start_mono - record.window_start:.2f}s "
              f"{root.duration * 1e3:.3f} ms: " + ", ".join(
                  f"{name}={value:.3f}"
                  for name, value in sorted(self_ms(trace).items())),
              file=sys.stderr)
