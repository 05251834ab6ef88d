"""The `yt` command-line interface.

Ref shape: yt/python/yt/wrapper/cli_impl.py — one binary, subcommand per
driver command, `--proxy` (or YT_PROXY env) selects the cluster, table
data flows through stdin/stdout in wire formats.

Usage (python -m ytsaurus_tpu.cli, or the `yt()` console entry):

  yt --proxy 127.0.0.1:9013 list /
  yt create map_node //home/me -r
  yt write-table //t --format json   < rows.json
  yt read-table //t --format dsv
  yt select-rows 'k, sum(v) AS s FROM [//t] GROUP BY k'
  yt map 'grep foo' --src //in --dst //out
  yt sort --src //in --dst //out --sort-by k
  yt start-tx / commit-tx / lock ...

The proxy address is the PRIMARY RPC endpoint (the thin-client plane);
`--user` stamps the authenticated principal.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

from ytsaurus_tpu.errors import YtError


def _json_default(value):
    if isinstance(value, bytes):
        return value.decode("utf-8", "replace")
    return str(value)


def _print(value) -> None:
    if value is None:
        return
    if isinstance(value, bytes):
        sys.stdout.buffer.write(value)
        if not value.endswith(b"\n"):
            sys.stdout.buffer.write(b"\n")
        return
    print(json.dumps(value, default=_json_default, indent=2))


def _rows_arg(rows: Optional[str]):
    blob = rows.encode() if rows else sys.stdin.buffer.read()
    return blob


def _decode_deep(value):
    """Bytes → str recursively (orchid values round-tripped through the
    YSON wire carry byte strings)."""
    if isinstance(value, bytes):
        return value.decode("utf-8", "replace")
    if isinstance(value, dict):
        return {_decode_deep(k): _decode_deep(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_decode_deep(v) for v in value]
    return value


def _fetch_trace(cl, trace_id: str):
    """Span tree of one trace: the remote orchid (`/tracing/traces/<id>`
    — what the monitoring /traces endpoint also renders) when the client
    has one, else this process's own collector."""
    tree = None
    if hasattr(cl, "get_orchid"):
        try:
            tree = cl.get_orchid(f"/tracing/traces/{trace_id}")
        except YtError:
            tree = None
    if not tree:
        from ytsaurus_tpu.utils.tracing import span_tree
        tree = span_tree(trace_id)
    return _decode_deep(tree) if tree else None


def _fetch_accounting(cl) -> dict:
    """The /accounting snapshot: the remote orchid when the client has
    one (daemon-side usage), else this process's own accountant.  A
    FAILING remote read propagates — silently falling back to this
    short-lived process's empty accountant would print an all-zero
    table and read as "cluster idle" when the daemon is broken."""
    if hasattr(cl, "get_orchid"):
        return _decode_deep(cl.get_orchid("/accounting") or {})
    from ytsaurus_tpu.query.accounting import get_accountant
    return get_accountant().snapshot()


# The `yt top` table columns (a readable subset of USAGE_FIELDS).
_TOP_COLUMNS = ("queries", "lookups", "rows_read", "bytes_read",
                "compile_seconds", "execute_seconds", "wall_seconds",
                "throttled", "jobs")

# Fair-share columns appended when --by pool (ISSUE 17): the admission
# controller's live allocation next to the historical usage — share is
# the pool's fair allocation in slots, use its running queries, demand
# running + queued.  demand >> share is the "who is being squeezed"
# signal the brown-out ladder and the SLO bench act on.
_FAIR_COLUMNS = ("share", "use", "demand")


def _serving_pool_rollup(gateways: list) -> dict:
    """Aggregate per-pool fair-share state across the live gateways."""
    rollup: dict = {}
    for gw in gateways or []:
        admission = (gw or {}).get("admission") or {}
        for name, pool in (admission.get("pools") or {}).items():
            agg = rollup.setdefault(
                name, {"share": 0.0, "use": 0, "demand": 0})
            agg["share"] += float(pool.get("fair_slots", 0.0))
            agg["use"] += int(pool.get("in_flight", 0))
            agg["demand"] += int(pool.get("demand",
                                          pool.get("in_flight", 0) +
                                          pool.get("waiting", 0)))
    return rollup


def _format_top(snapshot: dict, by: str, sort_key: str,
                limit: int, serving: Optional[dict] = None) -> str:
    """`yt top --by pool`: per-tenant resource usage, heaviest first —
    the serving-plane answer to "who is eating the cluster"."""
    rollup = dict(snapshot.get(f"by_{by}") or {})
    fair = _serving_pool_rollup((serving or {}).get("gateways")) \
        if by == "pool" else {}
    # A pool can be queued (demand) before any query of it finishes
    # (usage) — fair-share-only pools still get a row.
    for name in fair:
        rollup.setdefault(name, {})
    rows = sorted(rollup.items(),
                  key=lambda kv: -float(kv[1].get(sort_key, 0.0)))
    if limit > 0:
        rows = rows[:limit]
    totals = snapshot.get("totals") or {}

    def fmt(record, field):
        value = float(record.get(field, 0.0))
        if field.endswith("_seconds"):
            return f"{value:.3f}"
        if field == "bytes_read":
            return f"{value / 1e6:.1f}MB" if value >= 1e6 \
                else f"{value:.0f}"
        return f"{value:.0f}"

    def fair_cells(name):
        if not fair:
            return []
        pool = fair.get(name)
        if pool is None:
            return ["-"] * len(_FAIR_COLUMNS)
        return [f"{pool['share']:.2f}", f"{pool['use']:.0f}",
                f"{pool['demand']:.0f}"]

    fair_header = list(_FAIR_COLUMNS) if fair else []
    header = [by, *_TOP_COLUMNS, *fair_header]
    table = [[name, *[fmt(record, f) for f in _TOP_COLUMNS],
              *fair_cells(name)]
             for name, record in rows]
    fair_totals = []
    if fair:
        fair_totals = [
            f"{sum(p['share'] for p in fair.values()):.2f}",
            f"{sum(p['use'] for p in fair.values()):.0f}",
            f"{sum(p['demand'] for p in fair.values()):.0f}"]
    table.append(["TOTAL", *[fmt(totals, f) for f in _TOP_COLUMNS],
                  *fair_totals])
    widths = [max(len(str(row[i])) for row in [header, *table])
              for i in range(len(header))]
    lines = ["  ".join(str(cell).rjust(width)
                       for cell, width in zip(row, widths))
             for row in [header, *table]]
    return "\n".join(lines)


def _fetch_serving(cl) -> dict:
    """The /serving snapshot (fair-share admission state) for the
    `yt top --by pool` share/use/demand columns.  Best-effort: a
    cluster without a serving plane just drops the columns — usage
    history still renders."""
    try:
        if hasattr(cl, "get_orchid"):
            return _decode_deep(cl.get_orchid("/serving") or {})
        from ytsaurus_tpu.query.serving import serving_snapshot
        return {"gateways": serving_snapshot()}
    except Exception:   # noqa: BLE001 — the fair-share columns are an
        # overlay on the usage table, not the table itself.
        return {}


def _fetch_workload(cl) -> dict:
    """The /workload snapshot: remote orchid when the client has one,
    else this process's own workload log (same propagate-don't-mask
    policy as `yt top`)."""
    if hasattr(cl, "get_orchid"):
        return _decode_deep(cl.get_orchid("/workload") or {})
    from ytsaurus_tpu.query.workload import get_workload_log
    return get_workload_log().snapshot()


def _fetch_compile(cl) -> dict:
    """The /compile snapshot (compilation observatory)."""
    if hasattr(cl, "get_orchid"):
        return _decode_deep(cl.get_orchid("/compile") or {})
    from ytsaurus_tpu.query.engine.evaluator import (
        get_compile_observatory,
    )
    return get_compile_observatory().snapshot()


def _fetch_mesh(cl) -> dict:
    """The /mesh snapshot (mesh execution observatory)."""
    if hasattr(cl, "get_orchid"):
        return _decode_deep(cl.get_orchid("/mesh") or {})
    from ytsaurus_tpu.parallel.mesh_observatory import (
        get_mesh_observatory,
    )
    return get_mesh_observatory().snapshot()


_COMPILE_TOP_COLUMNS = ("compiles", "hits", "disk_hits",
                        "compile_seconds", "shape_count", "evictions",
                        "last_miss_cause")


def _format_table(header: list, rows: list) -> str:
    table = [header, *rows]
    widths = [max(len(str(row[i])) for row in table)
              for i in range(len(header))]
    return "\n".join("  ".join(str(cell).rjust(width)
                               for cell, width in zip(row, widths))
                     for row in table)


def _format_compile_top(snapshot: dict, sort_key: str,
                        limit: int) -> str:
    """`yt compile-cache top`: fingerprints ranked by compile burn —
    the observability answer to "what is this fleet recompiling"."""
    rows = list(snapshot.get("fingerprints") or [])
    rows.sort(key=lambda r: -float(r.get(sort_key) or 0.0))
    if limit > 0:
        rows = rows[:limit]
    totals = snapshot.get("totals") or {}

    def fmt(record, field):
        value = record.get(field)
        if field == "compile_seconds":
            return f"{float(value or 0.0):.3f}"
        if field == "last_miss_cause":
            return str(value or "-")
        return f"{int(value or 0)}"

    body = [[r.get("fingerprint", "?"),
             *[fmt(r, f) for f in _COMPILE_TOP_COLUMNS]] for r in rows]
    lines = [_format_table(["fingerprint", *_COMPILE_TOP_COLUMNS],
                           body)]
    lines.append(f"totals: {int(totals.get('hits', 0))} hits / "
                 f"{int(totals.get('misses', 0))} misses / "
                 f"{int(totals.get('evictions', 0))} evictions over "
                 f"{int(totals.get('fingerprints', 0))} fingerprints")
    disk = snapshot.get("disk")
    if disk:
        lines.append(
            f"disk tier: {int(disk.get('hits', 0))} hits / "
            f"{int(disk.get('misses', 0))} misses / "
            f"{int(disk.get('errors', 0))} errors; "
            f"{int(disk.get('files', 0))} artifacts, "
            f"{int(disk.get('bytes', 0))} bytes "
            f"(cap {int(disk.get('capacity_bytes', 0))}) "
            f"at {disk.get('dir')}")
    # Captured XLA artifacts (behind WorkloadConfig.capture_artifacts):
    # local AND SPMD executables with their cost_analysis FLOPs/bytes
    # (ISSUE 20 — fused/stitched programs stopped showing up blank).
    artifacts = snapshot.get("artifacts") or []
    if artifacts:
        lines.append("artifacts:")

        def num(value):
            return "-" if value is None else f"{int(float(value))}"

        lines.append(_format_table(
            ["fingerprint", "flops", "bytes_accessed",
             "compile_seconds"],
            [[art.get("fingerprint", "?"), num(art.get("flops")),
              num(art.get("bytes_accessed")),
              f"{float(art.get('compile_seconds') or 0.0):.3f}"]
             for art in artifacts]))
    return "\n".join(lines)


_MESH_TOP_SORT = {"skew": "skew_max", "bytes": "exchange_bytes",
                  "memory": "memory_watermark_bytes"}

_MESH_TOP_COLUMNS = ("path", "shards", "executions", "skew_max",
                     "exchange_bytes", "quota_headroom",
                     "memory_watermark_bytes", "drift_max", "skewed")


def _format_mesh_top(snapshot: dict, sort_key: str, limit: int) -> str:
    """`yt mesh top`: SPMD program fingerprints ranked by shard skew /
    exchange bytes / memory watermark — the observability answer to
    "which program is hot and where"."""
    field = _MESH_TOP_SORT.get(sort_key, sort_key)
    rows = list(snapshot.get("programs") or [])
    rows.sort(key=lambda r: -float(r.get(field) or 0.0))
    if limit > 0:
        rows = rows[:limit]

    def fmt(record, col):
        value = record.get(col)
        if col == "path":
            return str(value or "-")
        if col in ("skew_max", "quota_headroom", "drift_max"):
            return f"{float(value or 0.0):.3f}"
        return f"{int(value or 0)}"

    body = [[r.get("fingerprint", "?"),
             *[fmt(r, col) for col in _MESH_TOP_COLUMNS]] for r in rows]
    totals = snapshot.get("totals") or {}
    lines = [_format_table(["fingerprint", *_MESH_TOP_COLUMNS], body)]
    lines.append(
        f"totals: {int(totals.get('executions', 0))} executions "
        f"({int(totals.get('balanced', 0))} balanced / "
        f"{int(totals.get('skewed', 0))} skewed) over "
        f"{int(totals.get('programs', 0))} programs, "
        f"{int(totals.get('compiled', 0))} compile captures")
    return "\n".join(lines)


def _format_prewarm_report(report: dict) -> str:
    lines = [
        f"prewarmed {report.get('capture', '<capture>')}: "
        f"{report.get('compiled', 0)} compiled, "
        f"{report.get('aot_hits', 0)} AOT hits, "
        f"{report.get('already_cached', 0)} already cached "
        f"({report.get('seconds', 0.0):.3f}s compile+load)",
        f"records: {report.get('records', 0)} selects, "
        f"{report.get('skipped', 0)} skipped",
    ]
    reasons = report.get("skip_reasons") or {}
    if reasons:
        lines.append("skips: " + ", ".join(
            f"{why} {n}" for why, n in sorted(reasons.items())))
    return "\n".join(lines)


def _format_replay_report(report: dict) -> str:
    lat = report.get("latency") or {}
    cache = report.get("compile_cache") or {}

    def rate(value):
        return "n/a" if value is None else f"{value * 100:.2f}%"

    lines = [
        f"replayed {report.get('queries', 0)} queries in "
        f"{report.get('elapsed_seconds', 0.0):.3f}s "
        f"(offered {report.get('offered_rate') or 'max'}/s, achieved "
        f"{report.get('achieved_rate')}/s)",
        f"outcomes: {report.get('ok', 0)} ok, "
        f"{report.get('throttled', 0)} throttled, "
        f"{report.get('deadline', 0)} deadline, "
        f"{report.get('error', 0)} error",
        f"latency: p50 {lat.get('p50_ms', 0)}ms  p99 "
        f"{lat.get('p99_ms', 0)}ms  p999 {lat.get('p999_ms', 0)}ms  "
        f"max {lat.get('max_ms', 0)}ms",
        f"compile cache: {cache.get('hits', 0)} hits / "
        f"{cache.get('misses', 0)} misses "
        f"({cache.get('disk_hits', 0)} disk hits, "
        f"{cache.get('fresh_compiles', 0)} fresh compiles; "
        f"hit rate {rate(cache.get('hit_rate'))}, steady-state "
        f"{rate(cache.get('steady_hit_rate'))})",
    ]
    slowest = report.get("slowest") or []
    if slowest:
        lines.append("slowest (trace ids -> /traces or `yt trace`):")
        for entry in slowest:
            lines.append(
                f"  {entry.get('wall_ms', 0)}ms  "
                f"trace={entry.get('trace_id') or '<unsampled>'}  "
                f"[{entry.get('outcome')}] {entry.get('query')}")
    return "\n".join(lines)


def _format_profile(profile) -> str:
    """ExecutionProfile object (in-process client) OR its dict form
    (remote client / HTTP proxy) → the pretty EXPLAIN ANALYZE text, via
    the one shared renderer in query/profile.py."""
    if hasattr(profile, "format"):
        return profile.format()
    from ytsaurus_tpu.query.profile import format_profile_dict
    return format_profile_dict(_decode_deep(dict(profile)))


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="yt")
    parser.add_argument("--proxy", default=os.environ.get("YT_PROXY"),
                        help="primary address host:port (env YT_PROXY)")
    parser.add_argument("--user", default=os.environ.get("YT_USER", "root"))
    sub = parser.add_subparsers(dest="subcommand", required=True)

    def cmd(name, *args_defs, **kw):
        p = sub.add_parser(name, **kw)
        for flags, opts in args_defs:
            p.add_argument(*flags, **opts)
        return p

    cmd("list", (("path",), {"nargs": "?", "default": "/"}))
    cmd("get", (("path",), {}))
    cmd("set", (("path",), {}), (("value",), {}))
    cmd("exists", (("path",), {}))
    cmd("create", (("type",), {}), (("path",), {}),
        (("-r", "--recursive"), {"action": "store_true"}),
        (("-i", "--ignore-existing"), {"action": "store_true"}),
        (("--attributes",), {"default": None}))
    cmd("remove", (("path",), {}),
        (("-f", "--force"), {"action": "store_true"}))
    cmd("copy", (("src",), {}), (("dst",), {}),
        (("-r", "--recursive"), {"action": "store_true"}))
    cmd("move", (("src",), {}), (("dst",), {}),
        (("-r", "--recursive"), {"action": "store_true"}))
    cmd("link", (("target",), {}), (("link",), {}))
    cmd("write-table", (("path",), {}),
        (("--format",), {"default": "json"}),
        (("--append",), {"action": "store_true"}),
        (("--rows",), {"default": None, "help": "inline rows (else stdin)"}))
    cmd("read-table", (("path",), {}), (("--format",), {"default": "json"}))
    cmd("select-rows", (("query",), {}),
        (("--explain-analyze",), {"action": "store_true",
                                  "help": "print the per-query "
                                          "ExecutionProfile (wall/"
                                          "compile/execute split + span "
                                          "tree) instead of rows"}),
        (("--param",), {"action": "append", "default": None,
                        "dest": "params",
                        "help": "bind the next `?` placeholder (JSON "
                                "value; a JSON list binds a query "
                                "vector); repeat per placeholder"}))
    cmd("nearest-rows", (("path",), {}), (("column",), {}),
        (("query_vector",), {"help": "JSON list of floats"}),
        (("k",), {"type": int}),
        (("--metric",), {"default": "l2",
                         "choices": ["l2", "cosine", "dot"]}))
    cmd("trace", (("trace_id",), {}),
        (("--json",), {"action": "store_true",
                       "help": "raw span tree instead of the pretty "
                               "rendering"}))
    cmd("top", (("--by",), {"default": "pool",
                            "choices": ["pool", "user"],
                            "help": "roll resource usage up by pool "
                                    "(default) or user"}),
        (("--sort",), {"default": "wall_seconds",
                       "help": "usage column to sort by (descending); "
                               "e.g. rows_read, bytes_read, queries"}),
        (("--limit",), {"type": int, "default": 20}),
        (("--json",), {"action": "store_true",
                       "help": "raw accounting snapshot instead of the "
                               "table"}))
    cmd("workload", (("action",), {"choices": ["capture", "export",
                                               "import", "show"],
                                   "help": "capture: pull the cluster's "
                                           "workload log into --out; "
                                           "export: this process's log; "
                                           "import: load a capture into "
                                           "the local log; show: "
                                           "fingerprint roll-up"}),
        (("--out",), {"default": None,
                      "help": "capture file to write (capture/export)"}),
        (("--file",), {"default": None,
                       "help": "capture file to read (import)"}),
        (("--limit",), {"type": int, "default": 0,
                        "help": "cap records written/shown (0 = all "
                                "retained)"}),
        (("--json",), {"action": "store_true"}))
    cmd("replay", (("--capture",), {"required": True,
                                    "help": "versioned workload capture "
                                            "(yt workload capture/"
                                            "export)"}),
        (("--speed",), {"type": float, "default": 1.0,
                        "help": "time-compression of the recorded "
                                "inter-arrival spacing"}),
        (("--rate",), {"type": float, "default": None,
                       "help": "fixed open-loop offered rate (qps); "
                               "overrides recorded spacing"}),
        (("--limit",), {"type": int, "default": 0,
                        "help": "replay only the first N records"}),
        (("--workers",), {"type": int, "default": 16}),
        (("--pool",), {"default": None}),
        (("--timeout",), {"type": float, "default": None}),
        (("--json",), {"action": "store_true",
                       "help": "raw report instead of the pretty "
                               "rendering"}))
    cmd("prewarm", (("--capture",), {"required": True,
                                     "help": "versioned workload capture "
                                             "to replay COMPILE-ONLY "
                                             "(ISSUE 18): every distinct "
                                             "program the capture "
                                             "implies compiles into the "
                                             "memory/disk/cluster AOT "
                                             "tiers without executing a "
                                             "query"}),
        (("--limit",), {"type": int, "default": 0,
                        "help": "prewarm only the first N select "
                                "records (0 = all)"}),
        (("--json",), {"action": "store_true"}))
    cmd("analyze",
        # No `choices` here: the pass registry lives in tools/analyze
        # (PASSES); the driver validates, so a new pass needs no CLI
        # lockstep edit.
        (("--pass",), {"dest": "passes", "action": "append",
                       "default": None,
                       "help": "run only this pass (repeatable; "
                               "default: all — locks, guards, jax, "
                               "coverage, errors, sensors; guards = "
                               "annotation-free lock-guard inference + "
                               "atomicity lint + annotation drift, "
                               "rules guard-inference/guard-read/"
                               "atomicity/guard-drift)"}),
        (("--json",), {"action": "store_true",
                       "help": "machine-readable findings (pass, rule, "
                               "path, line, message, severity) + "
                               "ratchet verdict + lock-order graph + "
                               "the guards reconciliation graph "
                               "(inferred locks, superset edges, "
                               "sanitizer site map)"}),
        (("--update-baseline",), {"action": "store_true",
                                  "help": "rewrite tools/analyze/"
                                          "baseline.json to the current "
                                          "counts (tighten the ratchet "
                                          "AFTER fixing findings)"}),
        (("--no-baseline",), {"action": "store_true",
                              "help": "report raw findings instead of "
                                      "the ratchet verdict"}),
        (("--analyze-root",), {"default": None,
                               "help": "repo root to analyze (default: "
                                       "the installed tree)"}))
    cmd("view", (("action",), {"choices": ["create", "list", "show",
                                           "pause", "resume", "remove",
                                           "refresh"],
                               "help": "continuous queries (ISSUE 13): "
                                       "create registers an incremental "
                                       "materialized view over an "
                                       "ordered table; list/show read "
                                       "the registry + lag/freshness; "
                                       "pause/resume gate the daemon; "
                                       "refresh drains the cursor "
                                       "inline"}),
        (("name",), {"nargs": "?", "default": None}),
        (("--query",), {"default": None,
                        "help": "view QL (create), e.g. 'g, sum(v) AS "
                                "s FROM [//q] GROUP BY g'"}),
        (("--source",), {"default": None,
                         "help": "ordered source table (defaults to "
                                 "the query's FROM table)"}),
        (("--target",), {"default": None,
                         "help": "sorted target table (default: "
                                 "//sys/views/<name>/target)"}),
        (("--pool",), {"default": "views",
                       "help": "resource pool the refresh work is "
                               "accounted under"}),
        (("--batch-rows",), {"type": int, "default": None}),
        (("--max-batches",), {"type": int, "default": 0,
                              "help": "refresh: cap drained batches "
                                      "(0 = to the head)"}),
        (("--drop-target",), {"action": "store_true",
                              "help": "remove: also drop the target "
                                      "table"}),
        (("--json",), {"action": "store_true"}))
    cmd("compile-cache", (("action",), {"choices": ["top"]}),
        (("--limit",), {"type": int, "default": 20}),
        (("--sort",), {"default": "compile_seconds",
                       "help": "observatory column to rank by "
                               "(descending); e.g. compiles, "
                               "shape_count, evictions"}),
        (("--json",), {"action": "store_true"}))
    cmd("mesh", (("action",), {"choices": ["top"]}),
        (("--limit",), {"type": int, "default": 20}),
        (("--sort",), {"default": "skew",
                       "help": "rank programs by skew | bytes | memory "
                               "(or any roll-up column, e.g. "
                               "executions, drift_max)"}),
        (("--json",), {"action": "store_true"}))
    cmd("insert-rows", (("path",), {}),
        (("--rows",), {"default": None}))
    cmd("lookup-rows", (("path",), {}), (("--keys",), {"required": True}))
    cmd("mount-table", (("path",), {}))
    cmd("unmount-table", (("path",), {}))
    cmd("map", (("mapper_command",), {}),
        (("--src",), {"required": True}), (("--dst",), {"required": True}),
        (("--format",), {"default": "json"}),
        (("--pool",), {"default": "default"}),
        (("--job-count",), {"type": int, "default": None}))
    cmd("sort", (("--src",), {"required": True}),
        (("--dst",), {"required": True}),
        (("--sort-by",), {"required": True,
                          "help": "comma-separated key columns"}))
    cmd("reduce", (("reducer_command",), {}),
        (("--src",), {"required": True}), (("--dst",), {"required": True}),
        (("--reduce-by",), {"required": True,
                            "help": "comma-separated key columns"}),
        (("--sort-by",), {"default": None}),
        (("--format",), {"default": "json"}),
        (("--job-count",), {"type": int, "default": None}))
    cmd("map-reduce", (("reducer_command",), {}),
        (("--mapper-command",), {"default": None}),
        (("--src",), {"required": True}), (("--dst",), {"required": True}),
        (("--reduce-by",), {"required": True}),
        (("--sort-by",), {"default": None}),
        (("--partition-count",), {"type": int, "default": None}),
        (("--format",), {"default": "json"}))
    cmd("merge", (("--src",), {"required": True,
                               "help": "comma-separated input tables"}),
        (("--dst",), {"required": True}),
        (("--mode",), {"default": "unordered"}))
    cmd("erase", (("path",), {}))
    cmd("vanilla", (("--tasks",), {"required": True,
                                   "help": "JSON: {name: {job_count, "
                                           "command}}"}),
        (("--max-gang-restarts",), {"type": int, "default": 2}))
    cmd("remote-copy", (("--cluster",), {"required": True,
                                         "help": "source cluster "
                                                 "host:port"}),
        (("--src",), {"required": True}), (("--dst",), {"required": True}))
    cmd("abort-op", (("op_id",), {}))
    cmd("start-tx")
    cmd("commit-tx", (("tx",), {}))
    cmd("abort-tx", (("tx",), {}))
    cmd("lock", (("path",), {}), (("--tx",), {"required": True}),
        (("--mode",), {"default": "exclusive"}))
    cmd("create-user", (("name",), {}))
    cmd("create-account", (("name",), {}))
    cmd("check-permission", (("user",), {}), (("permission",), {}),
        (("path",), {}))
    cmd("get-operation", (("op_id",), {}))
    cmd("orchid", (("path",), {"nargs": "?", "default": "/"}))
    return parser


def _run_analyze(a) -> int:
    """`yt analyze`: the static-analysis suite (tools/analyze), run
    OFFLINE — no proxy, no cluster, no jax import.  The analyzer is
    loaded from the repo checkout next to this package."""
    import importlib.util
    repo = a.analyze_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    driver = os.path.join(repo, "tools", "analyze", "__main__.py")
    if not os.path.exists(driver):
        print(f"error: analyzer not found at {driver} (run from a "
              f"repo checkout, or pass --analyze-root)", file=sys.stderr)
        return 2
    if repo not in sys.path:
        sys.path.insert(0, repo)
    spec = importlib.util.spec_from_file_location("yt_analyze_main",
                                                  driver)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    argv = ["--root", repo]
    for name in a.passes or []:
        argv += ["--pass", name]
    if a.json:
        argv.append("--json")
    if a.update_baseline:
        argv.append("--update-baseline")
    if a.no_baseline:
        argv.append("--no-baseline")
    return mod.main(argv)


# Subcommands that run locally, without a cluster connection.
_OFFLINE_COMMANDS = {"analyze"}


def run(argv: "list[str] | None" = None,
        client=None) -> int:
    args = build_parser().parse_args(argv)
    if args.subcommand in _OFFLINE_COMMANDS:
        return _run_analyze(args)
    caller_owns_client = client is not None
    if client is None:
        if not args.proxy:
            print("error: --proxy (or YT_PROXY) is required",
                  file=sys.stderr)
            return 2
        # The thin client never needs the accelerator (and a chip belongs
        # to one process): pin the platform BEFORE any lazy jax import.
        # YT_CLI_PLATFORM overrides for on-device operations.
        import jax
        jax.config.update("jax_platforms",
                          os.environ.get("YT_CLI_PLATFORM", "cpu"))
        from ytsaurus_tpu.remote_client import RemoteYtClient
        client = RemoteYtClient(args.proxy, user=args.user)
    try:
        _print(_dispatch(client, args))
        return 0
    except YtError as err:
        print(json.dumps(err.to_dict(), default=_json_default),
              file=sys.stderr)
        return 1
    finally:
        if not caller_owns_client and hasattr(client, "close"):
            client.close()


def _dispatch(cl, a):
    c = a.subcommand
    if c == "list":
        return cl.list(a.path)
    if c == "get":
        return cl.get(a.path)
    if c == "set":
        return cl.set(a.path, json.loads(a.value))
    if c == "exists":
        return cl.exists(a.path)
    if c == "create":
        attributes = json.loads(a.attributes) if a.attributes else None
        return cl.create(a.type, a.path, attributes=attributes,
                         recursive=a.recursive,
                         ignore_existing=a.ignore_existing)
    if c == "remove":
        return cl.remove(a.path, force=a.force)
    if c == "copy":
        return cl.copy(a.src, a.dst, recursive=a.recursive)
    if c == "move":
        return cl.move(a.src, a.dst, recursive=a.recursive)
    if c == "link":
        return cl.link(a.target, a.link)
    if c == "write-table":
        return cl.write_table(a.path, _rows_arg(a.rows), format=a.format,
                              append=a.append)
    if c == "read-table":
        return cl.read_table(a.path, format=a.format)
    if c == "select-rows":
        params = [json.loads(p) for p in a.params] if a.params else None
        if a.explain_analyze:
            profile = cl.select_rows(a.query, explain_analyze=True,
                                     params=params)
            print(_format_profile(profile))
            return None
        return cl.select_rows(a.query, params=params)
    if c == "nearest-rows":
        return cl.nearest_rows(a.path, a.column,
                               json.loads(a.query_vector), a.k,
                               metric=a.metric)
    if c == "trace":
        tree = _fetch_trace(cl, a.trace_id)
        if not tree:
            raise YtError(f"no such trace {a.trace_id!r} "
                          "(unsampled, evicted, or wrong cluster)")
        if a.json:
            return tree
        from ytsaurus_tpu.query.profile import format_span_tree
        print(f"trace {a.trace_id}")
        print("\n".join(format_span_tree(tree)))
        return None
    if c == "top":
        snapshot = _fetch_accounting(cl)
        serving = _fetch_serving(cl) if a.by == "pool" else None
        if a.json:
            if serving:
                snapshot = dict(snapshot)
                snapshot["serving"] = serving
            return snapshot
        print(_format_top(snapshot, a.by, a.sort, a.limit, serving))
        return None
    if c == "workload":
        from ytsaurus_tpu.query import workload as wl
        if a.action in ("capture", "export"):
            if not a.out:
                raise YtError("workload capture/export requires --out")
            if a.action == "capture":
                snapshot = _fetch_workload(cl)
                records = [wl.WorkloadRecord.from_dict(r)
                           for r in snapshot.get("records") or []]
            else:
                records = wl.get_workload_log().records()
            written = wl.write_capture(a.out, records,
                                       limit=a.limit or None)
            return {"written": written, "path": a.out}
        if a.action == "import":
            if not a.file:
                raise YtError("workload import requires --file")
            return {"imported":
                    wl.get_workload_log().import_capture(a.file)}
        snapshot = _fetch_workload(cl)            # show
        if a.json:
            return snapshot
        rows = snapshot.get("fingerprints") or []
        if a.limit:
            rows = rows[:a.limit]
        print(_format_table(
            ["fingerprint", "kind", "count", "ok", "throttled",
             "deadline", "errors", "wall_s", "compile_s", "query"],
            [[r.get("fingerprint"), r.get("kind"), r.get("count"),
              r.get("ok"), r.get("throttled"), r.get("deadline"),
              r.get("errors"),
              f"{float(r.get('wall_seconds') or 0):.3f}",
              f"{float(r.get('compile_seconds') or 0):.3f}",
              str(r.get("query"))[:60]] for r in rows]))
        return None
    if c == "replay":
        from ytsaurus_tpu.query import workload as wl
        records = wl.load_capture(a.capture)   # fails loudly on version
        report = wl.replay(cl, records, speed=a.speed, rate=a.rate,
                           max_workers=a.workers, pool=a.pool,
                           timeout=a.timeout, limit=a.limit or None)
        if a.json:
            return report
        print(_format_replay_report(report))
        return None
    if c == "prewarm":
        # Compile-only capture replay (ISSUE 18): the caches being
        # warmed live in the SERVING process, so this needs an
        # in-process client (tests, embedded use, `yt ... --proxy`
        # pointing at a thin client cannot reach them).  Daemons warm
        # themselves at startup via YT_TPU_PREWARM_CAPTURE.
        if getattr(cl, "cluster", None) is None:
            raise YtError(
                "prewarm requires an in-process client: the compile "
                "caches live in the serving process.  Start the daemon "
                "with YT_TPU_PREWARM_CAPTURE=<capture> (or set "
                "tiering.prewarm_capture) to warm a replica at startup")
        from ytsaurus_tpu.query.engine.prewarm import prewarm_capture_file
        report = prewarm_capture_file(
            a.capture, client=cl,
            evaluator=cl.cluster.evaluator,
            limit=a.limit or None)
        if a.json:
            return report
        print(_format_prewarm_report(report))
        return None
    if c == "view":
        return _dispatch_view(cl, a)
    if c == "compile-cache":
        snapshot = _fetch_compile(cl)
        if a.json:
            return snapshot
        print(_format_compile_top(snapshot, a.sort, a.limit))
        return None
    if c == "mesh":
        snapshot = _fetch_mesh(cl)
        if a.json:
            return snapshot
        print(_format_mesh_top(snapshot, a.sort, a.limit))
        return None
    if c == "insert-rows":
        rows = json.loads(_rows_arg(a.rows))
        return cl.insert_rows(a.path, rows)
    if c == "lookup-rows":
        keys = [tuple(k) for k in json.loads(a.keys)]
        return cl.lookup_rows(a.path, keys)
    if c == "mount-table":
        return cl.mount_table(a.path)
    if c == "unmount-table":
        return cl.unmount_table(a.path)
    if c == "map":
        kw = {"format": a.format, "pool": a.pool}
        if a.job_count:
            kw["job_count"] = a.job_count
        op = cl.run_map(a.mapper_command, a.src, a.dst, **kw)
        return {"operation_id": op.id, "state": op.state}
    if c == "sort":
        op = cl.run_sort(a.src, a.dst, a.sort_by.split(","))
        return {"operation_id": op.id, "state": op.state}
    if c == "reduce":
        kw = {"format": a.format}
        if a.sort_by:
            kw["sort_by"] = a.sort_by.split(",")
        if a.job_count:
            kw["job_count"] = a.job_count
        op = cl.run_reduce(a.reducer_command, a.src, a.dst,
                           reduce_by=a.reduce_by.split(","), **kw)
        return {"operation_id": op.id, "state": op.state}
    if c == "map-reduce":
        kw = {"format": a.format}
        if a.sort_by:
            kw["sort_by"] = a.sort_by.split(",")
        if a.partition_count:
            kw["partition_count"] = a.partition_count
        op = cl.run_map_reduce(a.mapper_command, a.reducer_command,
                               a.src, a.dst,
                               reduce_by=a.reduce_by.split(","), **kw)
        return {"operation_id": op.id, "state": op.state}
    if c == "merge":
        op = cl.run_merge(a.src.split(","), a.dst, mode=a.mode)
        return {"operation_id": op.id, "state": op.state}
    if c == "erase":
        op = cl.run_erase(a.path)
        return {"operation_id": op.id, "state": op.state}
    if c == "vanilla":
        op = cl.run_vanilla(json.loads(a.tasks),
                            max_gang_restarts=a.max_gang_restarts)
        return {"operation_id": op.id, "state": op.state,
                "result": op.result}
    if c == "remote-copy":
        op = cl.run_remote_copy(a.cluster, a.src, a.dst)
        return {"operation_id": op.id, "state": op.state,
                "result": op.result}
    if c == "abort-op":
        op = cl.abort_operation(a.op_id)
        return {"operation_id": op.id, "state": op.state}
    if c == "start-tx":
        return cl.start_tx()
    if c == "commit-tx":
        return cl.commit_tx(a.tx)
    if c == "abort-tx":
        return cl.abort_tx(a.tx)
    if c == "lock":
        return cl.lock(a.path, mode=a.mode, tx=a.tx)
    if c == "create-user":
        return cl.create_user(a.name)
    if c == "create-account":
        return cl.create_account(a.name)
    if c == "check-permission":
        return cl.check_permission(a.user, a.permission, a.path)
    if c == "get-operation":
        return cl._execute("get_operation", {"operation_id": a.op_id})
    if c == "orchid":
        return cl.get_orchid(a.path)
    raise AssertionError(c)


def _dispatch_view(cl, a):
    """`yt view <action>` — the continuous-query verbs."""
    def require_name():
        if not a.name:
            raise YtError(f"view {a.action} requires a view name")
        return a.name

    if a.action == "create":
        if not a.query:
            raise YtError("view create requires --query")
        return cl.create_materialized_view(
            require_name(), a.query, source=a.source, target=a.target,
            pool=a.pool, batch_rows=a.batch_rows)
    if a.action == "list":
        statuses = []
        for name in cl.list_views():
            try:
                statuses.append(cl.get_view(name))
            except YtError as err:
                # One broken view (dropped source, unmounted tablet)
                # must not hide the registry — least of all the entry
                # the operator wants to remove.  JSON keeps the error
                # in its own field; placeholders are render-only.
                statuses.append({"name": name, "error": str(err)})
        if a.json:
            return statuses
        print(_format_table(
            ["view", "state", "source", "target", "offset", "lag",
             "pool"],
            [[s["name"], s.get("state", "error"),
              s.get("source", s.get("error", "")[:60]),
              s.get("target", "-"), s.get("offset", "-"),
              s.get("lag_rows", "-"), s.get("pool", "-")]
             for s in statuses]))
        return None
    if a.action == "show":
        return cl.get_view(require_name())
    if a.action == "pause":
        return cl.pause_view(require_name())
    if a.action == "resume":
        return cl.resume_view(require_name())
    if a.action == "remove":
        cl.remove_view(require_name(), drop_target=a.drop_target)
        return {"removed": a.name}
    if a.action == "refresh":
        return cl.refresh_view(require_name(),
                               max_batches=a.max_batches)
    raise AssertionError(a.action)


def main() -> None:
    raise SystemExit(run())


if __name__ == "__main__":
    main()
