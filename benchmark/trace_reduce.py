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

An operation's time is its self time: a control-flow op (`%while`) on the
ops line encloses the ops of its body on the same line, and keeps only
what they leave uncovered.  The first device's idle stretches are split
across the host annotations that cover them: each part goes to the
innermost `bench.*` or program (`yt.*`, `utils/tracing.py`) annotation
open at that time on the host line that carries the covering `bench.*`
call, and to `between_calls` outside every call.  The parts add up to
each stretch to the nanosecond.
"""

import bisect
import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
PROGRAM_PREFIX = "yt."
BETWEEN_CALLS = "between_calls"


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


def _self_seconds(intervals):
    """Nanoseconds per name, each event counted less what the events that
    lie inside it on the same line cover: an op's own time, never its
    body's too."""
    events = sorted(intervals, key=lambda iv: (iv[0], -iv[1]))
    covered = [0] * len(events)
    open_ = []                      # indices of the enclosing events
    for i, (start, end, _) in enumerate(events):
        while open_ and events[open_[-1]][1] <= start:
            open_.pop()
        if open_ and end <= events[open_[-1]][1]:
            covered[open_[-1]] += end - start
        open_.append(i)
    out = {}
    for (start, end, name), cover in zip(events, covered):
        out[name] = out.get(name, 0) + (end - start - cover)
    return out


def _innermost(intervals):
    """(start, end, name) pieces of the time the intervals cover, in order,
    each owned by the interval that began last among those open over it
    (the innermost, for intervals that nest)."""
    intervals = sorted(intervals, key=lambda iv: (iv[0], -iv[1]))
    cuts = sorted({t for start, end, _ in intervals for t in (start, end)})
    out, heap, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(intervals) and intervals[i][0] <= a:
            start, end, name = intervals[i]
            heapq.heappush(heap, (-start, end, i, name))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][3]
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _owners(host_lines, lo, hi):
    """(start, end, name) pieces that tile [lo, hi]: in each of the
    benchmark's calls the innermost `bench.*` / `yt.*` annotation open on
    the call's own host line, `between_calls` outside every call."""
    owned = [(lo, hi, BETWEEN_CALLS)]
    for line in host_lines:
        calls = sorted(iv for iv in line if iv[2].startswith(ANNOTATION_PREFIX))
        starts = [start for start, _, _ in calls]

        def in_a_call(iv):
            i = bisect.bisect_right(starts, iv[0]) - 1
            return i >= 0 and iv[1] <= calls[i][1]
        owned += [iv for iv in line if in_a_call(iv)]
    return _innermost(owned)


def _book(gaps, pieces):
    """Nanoseconds of the gaps per owner, `pieces` tiling every gap."""
    out, j = {}, 0
    for a, b in gaps:
        while pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            start, end, name = pieces[k]
            out[name] = out.get(name, 0) + min(end, b) - max(start, a)
            k += 1
    return out


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
    device_lines, host_lines = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            device_lines.append(lines)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_lines.append([
                    iv for iv in _intervals(line)
                    if iv[2].startswith((ANNOTATION_PREFIX, PROGRAM_PREFIX))])
    annotations = [iv for line in host_lines for iv in line
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
        for name, ns in _self_seconds(d["ops"]).items():
            name = short_op_name(name)
            op_seconds[name] = op_seconds.get(name, 0.0) + ns / 1e9
        for start, end, name in d["modules"]:
            name = _strip_fingerprint(name)
            program_seconds[name] = program_seconds.get(name, 0.0) + \
                (end - start) / 1e9
            program_calls[name] = program_calls.get(name, 0) + 1
    n = len(per_device)

    # Idle stretches of the first device, split across what the client's
    # host line was in over them.
    booked = _book(_gaps(per_device[0]["ops"], lo, hi),
                   _owners(host_lines, lo, hi))
    gap_seconds = {name: ns / 1e9 for name, ns in booked.items()}

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
