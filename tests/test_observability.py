"""Observability: sensors, tracing, Orchid, monitoring endpoint, RPC wiring."""

import json
import re
import urllib.request

import pytest

from ytsaurus_tpu.server.monitoring import MonitoringServer
from ytsaurus_tpu.server.orchid import OrchidService, OrchidTree, default_orchid
from ytsaurus_tpu.utils.profiling import (
    Histogram,
    Profiler,
    ProfilerRegistry,
)
from ytsaurus_tpu.utils.tracing import (
    TraceContext,
    child_span,
    current_trace,
    get_collector,
)


# -- sensors -------------------------------------------------------------------

def test_counter_gauge_summary():
    reg = ProfilerRegistry()
    prof = Profiler("/test", registry=reg)
    prof.counter("requests").increment()
    prof.counter("requests").increment(2)
    prof.gauge("depth").set(7)
    prof.summary("latency").record(0.5)
    prof.summary("latency").record(1.5)

    assert prof.counter("requests").get() == 3
    assert prof.gauge("depth").get() == 7
    s = prof.summary("latency")
    assert s.count == 2 and s.sum == 2.0 and s.min == 0.5 and s.max == 1.5

    text = reg.render_prometheus()
    assert "test_requests 3" in text
    assert "test_depth 7" in text
    assert "test_latency_sum 2.0" in text


def test_tags_make_distinct_sensors():
    reg = ProfilerRegistry()
    prof = Profiler("/q", registry=reg)
    prof.with_tags(pool="a").counter("n").increment()
    prof.with_tags(pool="b").counter("n").increment(5)
    text = reg.render_prometheus()
    assert 'q_n{pool="a"} 1' in text
    assert 'q_n{pool="b"} 5' in text


def test_histogram_buckets():
    h = Histogram(bounds=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.record(v)
    samples = dict((suffix, val) for _k, suffix, val in h.samples())
    assert samples['.bucket{le="1.0"}'] == 1
    assert samples['.bucket{le="10.0"}'] == 2
    assert samples['.bucket{le="+Inf"}'] == 3
    assert samples[".count"] == 3


def test_registry_collect_snapshot():
    reg = ProfilerRegistry()
    Profiler("/x", registry=reg).counter("c").increment(4)
    snap = reg.collect()
    assert snap["/x/c"] == 4


# -- tracing -------------------------------------------------------------------

def test_span_nesting_and_collection():
    with TraceContext("root") as root:
        assert current_trace() is root
        with child_span("child", table="//t") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_span_id == root.span_id
    assert current_trace() is None
    spans = get_collector().find(root.trace_id)
    names = {s.name for s in spans}
    assert names == {"root", "child"}
    child_rec = next(s for s in spans if s.name == "child")
    assert child_rec.tags["table"] == "//t"


def test_trace_wire_round_trip():
    ctx = TraceContext("a", sampled=True)
    ctx.set_baggage("user", "alice")
    wire = ctx.to_wire()
    # Simulate YSON transport byte-keys.
    wire = {k.encode(): v for k, v in wire.items()}
    remote = TraceContext.from_wire(wire, "server_side")
    assert remote.trace_id == ctx.trace_id
    assert remote.parent_span_id == ctx.span_id
    assert remote.baggage == {"user": "alice"}


def test_unsampled_spans_not_collected():
    ctx = TraceContext("quiet", sampled=False)
    with ctx:
        pass
    assert not get_collector().find(ctx.trace_id)


# -- prometheus exposition validator (ISSUE 5 satellite) -----------------------

_METRIC_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*\Z")
_LABEL_NAME_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*\Z")


def parse_prometheus_exposition(text: str) -> list:
    """STRICT parse of the text exposition format: returns
    [(metric, labels_dict, value)] or raises ValueError on any grammar
    violation (bad names, unescaped label values, trailing garbage,
    duplicate series).  New sensors that would break a Prometheus scrape
    must fail HERE, in tests, not in production scrapes."""
    series = []
    seen = set()
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line or line.startswith("#"):
            continue

        def fail(reason):
            raise ValueError(f"line {lineno}: {reason}: {line!r}")

        i = line.find("{")
        labels = {}
        if i == -1:
            name, _, value_str = line.partition(" ")
        else:
            name = line[:i]
            # Label block: char-by-char so escapes inside quoted values
            # are honored (\\ \" \n are the ONLY legal escapes).
            pos = i + 1
            while True:
                j = line.find("=", pos)
                if j == -1:
                    fail("label without '='")
                label_name = line[pos:j]
                if not _LABEL_NAME_RE.match(label_name):
                    fail(f"bad label name {label_name!r}")
                if line[j + 1] != '"':
                    fail("unquoted label value")
                value_chars = []
                k = j + 2
                while k < len(line) and line[k] != '"':
                    ch = line[k]
                    if ch == "\\":
                        esc = line[k + 1] if k + 1 < len(line) else ""
                        if esc not in ("\\", '"', "n"):
                            fail(f"illegal escape \\{esc}")
                        value_chars.append(
                            {"\\": "\\", '"': '"', "n": "\n"}[esc])
                        k += 2
                    else:
                        value_chars.append(ch)
                        k += 1
                if k >= len(line):
                    fail("unterminated label value")
                if label_name in labels:
                    fail(f"duplicate label {label_name!r}")
                labels[label_name] = "".join(value_chars)
                k += 1
                if k < len(line) and line[k] == ",":
                    pos = k + 1
                    continue
                if k < len(line) and line[k] == "}":
                    break
                fail("expected ',' or '}' after label value")
            rest = line[k + 1:]
            if not rest.startswith(" "):
                fail("missing space before value")
            value_str = rest[1:]
        if not _METRIC_NAME_RE.match(name):
            fail(f"bad metric name {name!r}")
        if " " in value_str:
            fail("trailing garbage after value")
        try:
            value = float(value_str)
        except ValueError:
            if value_str not in ("+Inf", "-Inf", "NaN"):
                fail(f"bad sample value {value_str!r}")
            value = float(value_str.replace("Inf", "inf"))
        key = (name, tuple(sorted(labels.items())))
        if key in seen:
            fail(f"duplicate series {key!r}")
        seen.add(key)
        series.append((name, labels, value))
    return series


def test_exposition_validator_rejects_bad_lines():
    for bad in ("1metric 2", "m{x=1} 2", 'm{x="a} 2', 'm{x="a\\q"} 2',
                'm{x="a"}2', "m two", "m 1 extra", 'm{x="a",} 2',
                "m 1\nm 1"):
        with pytest.raises(ValueError):
            parse_prometheus_exposition(bad)
    ok = parse_prometheus_exposition('m{x="a\\"b\\\\c\\nd"} 1.5')
    assert ok == [("m", {"x": 'a"b\\c\nd'}, 1.5)]


def test_render_prometheus_survives_hostile_label_values():
    reg = ProfilerRegistry()
    prof = Profiler("/evil", registry=reg)
    prof.with_tags(q='say "hi"\nback\\slash').counter("n").increment()
    prof.with_tags(name="a.b/c-d").histogram(
        "lat", bounds=(0.1, 1.0)).record(0.5)
    prof.summary("s").record(2.0)
    series = parse_prometheus_exposition(reg.render_prometheus())
    (evil,) = [(n, l, v) for n, l, v in series if n == "evil_n"]
    assert evil[1] == {"q": 'say "hi"\nback\\slash'} and evil[2] == 1
    buckets = {l["le"]: v for n, l, v in series
               if n == "evil_lat_bucket"}
    assert buckets == {"0.1": 0, "1.0": 1, "+Inf": 1}


def test_live_registry_exposition_is_valid():
    """The GLOBAL registry — after real spans/sensors from other tests
    have landed in it — must render a grammatically valid exposition
    with no duplicate series."""
    from ytsaurus_tpu.utils.profiling import get_registry
    from ytsaurus_tpu.utils.tracing import TraceContext

    # Make sure at least one span-duration histogram (dotted span name
    # as a label value) is present.
    with TraceContext("exposition.check"):
        pass
    series = parse_prometheus_exposition(get_registry().render_prometheus())
    assert any(n == "tracing_span_seconds_count" and
               l.get("name") == "exposition.check"
               for n, l, v in series)


# -- orchid --------------------------------------------------------------------

def test_orchid_get_descends_into_producer_output():
    tree = OrchidTree()
    tree.register("/tablets", lambda: {"t1": {"rows": 10}, "t2": {"rows": 3}})
    tree.register_value("/version", "1.0")
    assert tree.get("/tablets/t1/rows") == 10
    assert tree.get("/version") == "1.0"
    assert tree.list("/tablets") == ["t1", "t2"]
    assert tree.list("/") == ["tablets", "version"]


def test_orchid_missing_path():
    from ytsaurus_tpu.errors import YtError
    tree = OrchidTree()
    tree.register("/a", lambda: {"b": 1})
    with pytest.raises(YtError):
        tree.get("/a/nope")
    with pytest.raises(YtError):
        tree.get("/zzz")


def test_default_orchid_has_sensors_and_spans():
    tree = default_orchid()
    assert isinstance(tree.get("/monitoring/sensors"), dict)
    assert isinstance(tree.get("/tracing/recent_spans"), list)


# -- monitoring http -----------------------------------------------------------

def test_monitoring_endpoints():
    reg = ProfilerRegistry()
    Profiler("/mon", registry=reg).counter("hits").increment(2)
    tree = OrchidTree()
    tree.register("/state", lambda: {"phase": "leading", "peers": [1, 2]})
    server = MonitoringServer(tree, reg)
    server.start()
    try:
        base = f"http://{server.address}"
        assert urllib.request.urlopen(f"{base}/healthz").read() == b"ok"
        metrics = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "mon_hits 2" in metrics
        state = json.loads(
            urllib.request.urlopen(f"{base}/orchid/state").read())
        assert state == {"phase": "leading", "peers": [1, 2]}
        phase = json.loads(
            urllib.request.urlopen(f"{base}/orchid/state/phase").read())
        assert phase == "leading"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/orchid/zzz")
    finally:
        server.stop()


# -- rpc propagation -----------------------------------------------------------

def test_rpc_propagates_trace_and_counts_requests():
    from ytsaurus_tpu.rpc import Channel, RpcServer
    from ytsaurus_tpu.rpc.server import Service, rpc_method
    from ytsaurus_tpu.utils import profiling

    seen = {}

    class Echo(Service):
        name = "echo"

        @rpc_method()
        def ping(self, body, attachments):
            ctx = current_trace()
            seen["trace_id"] = ctx.trace_id if ctx else None
            seen["baggage"] = dict(ctx.baggage) if ctx else {}
            return {"pong": True}

    server = RpcServer([Echo()])
    server.start()
    channel = Channel(server.address, timeout=10)
    try:
        with TraceContext("client_op") as root:
            root.set_baggage("user", "bob")
            body, _ = channel.call("echo", "ping", {})
        assert body["pong"] is True
        assert seen["trace_id"] == root.trace_id
        assert seen["baggage"].get("user") in ("bob", b"bob")
        # Server span was exported with the same trace id.
        names = {s.name for s in get_collector().find(root.trace_id)}
        assert "echo.ping" in names
        # Request sensor ticked.
        counter = profiling.Profiler("/rpc/server").with_tags(
            service="echo", method="ping").counter("request_count")
        assert counter.get() >= 1
    finally:
        channel.close()
        server.stop()


def test_orchid_service_over_rpc():
    from ytsaurus_tpu.rpc import Channel, RpcServer

    tree = OrchidTree()
    tree.register("/live", lambda: {"n": 42})
    server = RpcServer([OrchidService(tree)])
    server.start()
    channel = Channel(server.address, timeout=10)
    try:
        body, _ = channel.call("orchid", "get", {"path": "/live/n"})
        assert body["value"] == 42
        body, _ = channel.call("orchid", "list", {"path": "/"})
        names = [n.decode() if isinstance(n, bytes) else n
                 for n in body["names"]]
        assert names == ["live"]
    finally:
        channel.close()
        server.stop()


# -- log rotation (ref core/logging's compressed rotating writer) --------------

def test_rotating_log_handler_gzips_history(tmp_path):
    import gzip
    import json as _json
    import logging as _logging

    from ytsaurus_tpu.utils.logging import (
        StructuredFormatter,
        make_rotating_handler,
    )

    path = str(tmp_path / "daemon.log")
    handler = make_rotating_handler(path, max_bytes=2000, backups=2)
    logger = _logging.getLogger("rotation-test")
    logger.setLevel(_logging.INFO)
    logger.addHandler(handler)
    logger.propagate = False
    for i in range(200):
        logger.info("event %d with some padding to fill bytes", i)
    handler.close()
    live = open(path).read().splitlines()
    assert live and all(_json.loads(line)["category"] == "rotation-test"
                        for line in live)
    import os as _os
    rotated = [f for f in _os.listdir(tmp_path)
               if f.startswith("daemon.log.") and f.endswith(".gz")]
    assert 1 <= len(rotated) <= 2            # history capped at backups
    with gzip.open(tmp_path / rotated[0], "rt") as f:
        row = _json.loads(f.readline())
    assert "event" in row["message"]
    # The live file respects the size cap (plus at most one record).
    assert _os.path.getsize(path) < 4000


def test_env_wired_file_logging(tmp_path, monkeypatch):
    """YTSAURUS_TPU_LOG_FILE adds the rotating file sink at configure
    time (fresh interpreter via subprocess: _configure is once-only)."""
    import subprocess
    import sys

    log_path = tmp_path / "wired.log"
    code = (
        "from ytsaurus_tpu.utils.logging import get_logger, log_event\n"
        "import logging\n"
        "log_event(get_logger('Wired'), logging.WARNING, 'hello',"
        " k=1)\n")
    import pathlib
    repo_root = str(pathlib.Path(__file__).resolve().parents[1])
    env = {"YTSAURUS_TPU_LOG_FILE": str(log_path),
           "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "PYTHONPATH": repo_root}
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
    import json as _json
    # Per-process disambiguation: the actual file carries the child pid.
    (actual,) = list(log_path.parent.glob("wired-*.log"))
    lines = [_json.loads(line) for line in open(actual)]
    assert lines[0]["message"] == "hello" and lines[0]["k"] == 1
