"""Reader `span_window`: the window's time under spans that fall in few
traces, per select.

    {"kind": "span_window", "root": "query.select", "stat": "mean",
     "spans": ["host.stall", "host.overrun"], "scale": 1000}

Over the window's traces (`span.window_traces`: the same traces, the same
refusals), the durations of the spans of the names given are added and
divided by the number of traces: a mean, because a stall, a collection or
a compile lands in a few selects and the median select has none.  A span
inside another span of those names (a `jax.trace` nested in a
`jax.trace`, a `jax.cache_read` inside its `jax.compile`) is counted once,
by its outermost.  A window in which no trace has such a span reads 0.0:
none happened, or the program has no such instrument.
"""

from readers import span


def read(definition, ctx):
    traces = span.window_traces(definition["root"], ctx)
    if not traces:
        return None
    if definition["stat"] != "mean":
        raise ValueError(f"unknown statistic {definition['stat']!r}")
    names = set(definition["spans"])
    total = sum(trace_total(names, trace) for trace in traces)
    return total / len(traces) * definition.get("scale", 1)


def trace_total(names, trace):
    """The seconds one trace spent under spans of `names`, each interval
    once: the durations of those of them that no other of them encloses."""
    named = [s for name in names for s in trace.get(name, ())]
    if not named:
        return 0.0
    by_id = {s.span_id: s for group in trace.values() for s in group}
    total = 0.0
    for s in named:
        parent = by_id.get(s.parent_span_id)
        while parent is not None and parent.name not in names:
            parent = by_id.get(parent.parent_span_id)
        if parent is None:
            total += s.duration
    return total
