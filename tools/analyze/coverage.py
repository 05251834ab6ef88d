"""Failpoint & span coverage pass (`yt analyze --pass coverage`).

Two disciplines established by PR 2 (deterministic failpoints) and PR 5
(span-site rules), enforced statically:

  failpoint-coverage   a function in the server/chunk/rpc planes that
                       performs REAL I/O (file open/replace/remove,
                       socket connect) must contain a failpoint probe
                       (`<site>.hit()` / `.write_hit()` / `.fire()`) —
                       or carry an explicit waiver
                       (`# analyze: allow(failpoint): reason`) on its
                       def line.  The chaos soak can only prove recovery
                       for faults it can inject.
  span-discipline      root-span creation (`start_query_span`, bare
                       `TraceContext(...)`)
                       is allowed ONLY at the declared entry points; an
                       interior site that roots a fresh trace orphans
                       itself from the caller's flight recording —
                       interior code uses `child_span` (PR 5 rule).
"""

from __future__ import annotations

import ast

from tools.analyze.core import (
    Finding,
    SourceFile,
    dotted_name,
    walk_functions,
)

PASS_NAME = "coverage"

# Planes whose I/O functions must be injectable.
FAILPOINT_PREFIXES = (
    "ytsaurus_tpu/chunks/",
    "ytsaurus_tpu/rpc/",
    "ytsaurus_tpu/server/",
)

# Call shapes that constitute REAL I/O for coverage purposes.  Curated
# to state-bearing operations (durability/wire boundaries), not every
# os.path probe.
_IO_CALLS = {
    "open",
    "os.replace", "os.rename", "os.remove", "os.unlink", "os.fsync",
    "socket.create_connection", "asyncio.open_connection",
}

# Failpoint probe shapes: a call whose attribute is one of these on any
# receiver (`_FP_READ.hit()`, `site.write_hit(blob)`, `_FP.fire()`).
_PROBE_ATTRS = {"hit", "write_hit", "fire"}

# Modules allowed to root traces (the PR 5 entry points) — everything
# else must use child_span.
SPAN_ENTRY_FILES = {
    "ytsaurus_tpu/client.py",           # gateway select/lookup roots
    "ytsaurus_tpu/operations/scheduler.py",   # operation roots
    "ytsaurus_tpu/server/http_proxy.py",      # X-YT-Trace-Id ingress
    "ytsaurus_tpu/utils/tracing.py",          # the substrate itself
    "ytsaurus_tpu/rpc/server.py",             # wire-context restore
}

_ROOT_SPAN_CALLS = {"start_query_span", "tracing.start_query_span"}


def _is_io_call(call: ast.Call) -> "str | None":
    name = dotted_name(call.func)
    if name in _IO_CALLS:
        return name
    return None


def _has_probe(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _PROBE_ATTRS:
            return True
    return False


def _check_failpoints(f: SourceFile, findings: "list[Finding]") -> None:
    for cls, fn in walk_functions(f.tree):
        io_sites = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = _is_io_call(node)
                if name is not None and \
                        not f.waived("failpoint", node.lineno):
                    io_sites.append((name, node.lineno))
        if not io_sites or _has_probe(fn):
            continue
        if f.function_waived("failpoint", fn):
            continue
        names = ", ".join(sorted({n for n, _ in io_sites}))
        qual = f"{cls}.{fn.name}" if cls else fn.name
        findings.append(Finding(
            PASS_NAME, "failpoint", f.path, fn.lineno,
            f"{qual} performs I/O ({names} at line"
            f"{'s' if len(io_sites) > 1 else ''} "
            f"{', '.join(str(l) for _, l in io_sites)}) but contains "
            f"no failpoints probe — register a site "
            f"(utils/failpoints.register_site) and call `.hit()` at "
            f"the boundary, or waive with `# analyze: "
            f"allow(failpoint): reason`"))


def _check_spans(f: SourceFile, findings: "list[Finding]") -> None:
    if f.path in SPAN_ENTRY_FILES:
        return
    for node in ast.walk(f.tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        rooted = None
        if name in _ROOT_SPAN_CALLS:
            rooted = name
        elif name == "TraceContext" or name.endswith(".TraceContext"):
            rooted = "TraceContext(...)"
        if rooted is None or f.waived("span-root", node.lineno):
            continue
        findings.append(Finding(
            PASS_NAME, "span-root", f.path, node.lineno,
            f"{rooted} roots a fresh trace outside the declared entry "
            f"points ({', '.join(sorted(SPAN_ENTRY_FILES))}) — interior "
            f"sites use child_span so the work stays inside the "
            f"caller's trace"))


def run(files: "list[SourceFile]") -> "list[Finding]":
    findings: list[Finding] = []
    for f in files:
        if any(f.path.startswith(p) for p in FAILPOINT_PREFIXES):
            _check_failpoints(f, findings)
        _check_spans(f, findings)
    return findings
