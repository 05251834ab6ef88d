"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy seconds and window length, time per device
program, the operations that took most device time, and the longest idle
gaps by what the host was doing in them.

How a TPU trace is laid out (looked at by hand, PERF.md section 3): one
plane per chip, `/device:TPU:<n>`; its line `XLA Ops` has one event per
executed HLO operation, its line `XLA Modules` one per executed program
(`jit_<name>(<fingerprint>)`).  Host threads are lines of the plane
`/host:CPU`; the benchmark's `jax.profiler.TraceAnnotation`s are events
there, on the clock of the device lines.

Busy is the union of the intervals of `XLA Ops` (of `XLA Modules` where a
plane has no ops line), averaged over the device planes.  The window runs
from the first to the last host annotation of the benchmark (`bench.*`),
or over the device events where there is none.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."


def _intervals(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _union_seconds(intervals, lo=None, hi=None):
    """Length of the union of (start, end, ...) intervals, clipped."""
    total, cur_start, cur_end = 0, None, None
    for start, end, *_ in sorted(intervals):
        if lo is not None:
            start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total / 1e9


def _gaps(intervals, lo, hi):
    """(start, end) of the stretches of [lo, hi] no interval covers."""
    out, cursor = [], lo
    for start, end, *_ in sorted(intervals):
        if start > cursor:
            out.append((cursor, min(start, hi)))
        cursor = max(cursor, end)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


def _strip_fingerprint(name):
    return re.sub(r"\(\d+\)$", "", name)


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?) ([\w\-]+)\(")


def short_op_name(name):
    """An `XLA Ops` event is named by its whole HLO line; keep the result
    name, the opcode and the result type without layouts."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    lhs, out_type, opcode = m.groups()
    return f"{lhs} {opcode} {re.sub(r'{[^}]*}', '', out_type)}"[:120]


def reduce_planes(planes):
    """`planes`: iterable of profiler planes (name, lines -> events)."""
    device_lines, annotations = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            device_lines.append(lines)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                annotations += [iv for iv in _intervals(line)
                                if iv[2].startswith(ANNOTATION_PREFIX)]
    per_device = []
    for lines in device_lines:
        busy_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if busy_line is None:
            continue
        per_device.append({
            "ops": _intervals(busy_line),
            "modules": _intervals(lines[MODULES_LINE])
            if MODULES_LINE in lines else []})
    per_device = [d for d in per_device if d["ops"]]
    if not per_device:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "programs": [], "idle_gaps": []}

    if annotations:
        lo = min(a[0] for a in annotations)
        hi = max(a[1] for a in annotations)
    else:
        lo = min(iv[0] for d in per_device for iv in d["ops"])
        hi = max(iv[1] for d in per_device for iv in d["ops"])
    busy = [_union_seconds(d["ops"], lo, hi) for d in per_device]

    op_seconds, program_seconds, program_calls = {}, {}, {}
    for d in per_device:
        for start, end, name in d["ops"]:
            name = short_op_name(name)
            op_seconds[name] = op_seconds.get(name, 0.0) + (end - start) / 1e9
        for start, end, name in d["modules"]:
            name = _strip_fingerprint(name)
            program_seconds[name] = program_seconds.get(name, 0.0) + \
                (end - start) / 1e9
            program_calls[name] = program_calls.get(name, 0) + 1
    n = len(per_device)

    # Idle gaps of the first device, each booked to the benchmark's host
    # annotation that covers its midpoint (none: the host was between calls).
    # The benchmark's annotations are one client's calls: they do not overlap.
    gap_seconds = {}
    spans = sorted(annotations)
    starts = [s for s, _, _ in spans]
    for a, b in _gaps(per_device[0]["ops"], lo, hi):
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        owner = spans[i][2] if i >= 0 and mid < spans[i][1] \
            else "between_calls"
        gap_seconds[owner] = gap_seconds.get(owner, 0.0) + (b - a) / 1e9

    def top(table, scale=1.0):
        return [[name, seconds * scale] for name, seconds in
                sorted(table.items(), key=lambda kv: -kv[1])]

    return {
        "busy_s": sum(busy) / n,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "device_ops": top(op_seconds, 1.0 / n),
        "programs": [[name, seconds / n, program_calls[name] // n]
                     for name, seconds in top(program_seconds)],
        "idle_gaps": top(gap_seconds),
    }


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path):
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_dir(trace_dir):
    return reduce_file(find_xplane(trace_dir))


def describe(path, events=3):
    """Planes, lines, event counts and a few event names: what one looks
    at by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = [e.name for e in evs[:events]]
            out.append(f"  line {line.name!r}: {len(evs)} events, e.g. {names}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
