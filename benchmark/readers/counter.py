"""Reader `counter`: a statistic over the window's request lines (host
clock and the program's QueryStatistics counters), or a field of the run.

    {"kind": "counter", "stat": "rate", "field": "source_rows"}
    {"kind": "counter", "stat": "p95", "field": "wall_s", "scale": 1000,
     "where": {"op": "select"}}
    {"kind": "counter", "stat": "run", "field": "setup_s"}

A rate is all completed work over the time from the window's start to the
last completion; a percentile is over every request of the window.
"""

import numpy as np


def read(definition, ctx):
    record = ctx.record
    stat = definition["stat"]
    scale = definition.get("scale", 1)
    if stat == "run":
        return getattr(record, definition["field"]) * scale
    where = definition.get("where", {})
    rows = [r for r in record.requests
            if all(r.get(k) == v for k, v in where.items())]
    values = [r[definition["field"]] for r in rows
              if r.get(definition["field"]) is not None]
    if not values:
        return None
    if stat == "rate":
        return float(sum(values)) / record.busy_window_s * scale
    if stat == "sum":
        return float(sum(values)) * scale
    if stat == "median":
        return float(np.median(values)) * scale
    if stat == "p95":
        return float(np.percentile(values, 95)) * scale
    raise ValueError(f"unknown statistic {stat!r}")
