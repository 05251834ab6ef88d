"""Reader `trace`: numbers from the reduced device trace of the window.

    {"kind": "trace", "stat": "idle_share"}
    {"kind": "trace", "stat": "hbm_roofline"}
    {"kind": "trace", "stat": "idle_unspanned", "root": "yt.query.select"}

`hbm_roofline` is the least time the chip's HBM could take to deliver the
bytes the cell's requests need (the driver's `bytes_needed_per_request`:
columns read x rows x device width, whatever implements the query), over
the device-busy time per request in the trace.  It is bound by bytes: these
queries do a few operations per byte.

`idle_unspanned` is the share of the window in which the device is idle
and the client's host line is in no program span below the root: the
idle parts `trace_reduce` books to the root itself, to the benchmark's
own call (`bench.*`) or to `between_calls`.

No device plane in the trace (a rehearsal): nothing to read.
"""

import json
import os

import trace_reduce

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks_for(device_kind):
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       f"add its published peaks with their source")
    return table[device_kind]


def read(definition, ctx):
    trace = ctx.record.trace
    if not trace or not trace["busy_s"]:
        return None
    stat = definition["stat"]
    if stat == "idle_share":
        return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
    if stat == "hbm_roofline":
        done = len(ctx.record.requests)
        if not done:
            return None
        peak = peaks_for(ctx.device_kind)["hbm_bytes_per_s"]
        least_s = ctx.driver.bytes_needed_per_request() / peak
        return least_s / (trace["busy_s"] / done) * 100.0
    if stat == "idle_unspanned":
        unspanned = sum(seconds for name, seconds in trace["idle_gaps"]
                        if name == definition["root"] or
                        not name.startswith(trace_reduce.PROGRAM_PREFIX))
        return unspanned / trace["window_s"] * 100.0
    raise ValueError(f"unknown statistic {stat!r}")
