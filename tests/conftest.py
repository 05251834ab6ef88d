"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; all sharding/collective tests run on
a virtual 8-device CPU platform (xla_force_host_platform_device_count), per the
same strategy the reference uses for multi-node tests without a real cluster
(yt/python/yt/environment/yt_env.py local-mode clusters).

This must run before any JAX backend initializes: the platform is switched via
jax.config (which takes effect lazily at first backend use), so the suite runs
on the CPU whatever JAX_PLATFORMS the caller exported.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Every test runs "sanitized": structural invariant checks at subsystem
# boundaries (utils/invariants.py — the debug-build assertion analog).
# Plain assignment, not setdefault: an inherited =0 from a profiling
# shell must not silently turn the sanitizer off for the whole suite.
os.environ["YT_TPU_INVARIANTS"] = "1"
# ... and "lock-sanitized" (ISSUE 15): utils/sanitizers.py wraps every
# registered hot lock, recording held-lock sets and acquisition-order
# edges live.  Must be set BEFORE any ytsaurus_tpu module constructs
# its locks (registration reads it once per lock creation);
# pytest_sessionfinish below reconciles the observed dynamic lock-order
# graph against the static analyzer's superset graph.
os.environ["YT_TPU_SANITIZE"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: minutes-long compile-heavy suites excluded from the tier-1 "
        "quick pass (ROADMAP.md runs -m 'not slow')")
    # Buffer donation (ISSUE 19) stays armed in tests; CPU backends
    # ignore it with a per-call warning pytest's capture would surface.
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable")


def pytest_sessionfinish(session, exitstatus):
    """The dynamic⊆static lock-order gate (ISSUE 15): every acquisition
    edge the runtime sanitizer observed across the WHOLE tier-1 run must
    exist in the static reconciliation graph — an edge the AST
    propagation cannot derive fails the build with the acquisition
    stacks attached (teach tools/analyze, or restructure the locking).
    Runs only when the suite actually exercised the sanitizer, and only
    on otherwise-green runs (a red run's report would bury the real
    failure)."""
    from ytsaurus_tpu.utils import sanitizers

    san = sanitizers.get_sanitizer()
    if san is None or exitstatus != 0 or not san.edge_snapshot():
        return
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.analyze import guard_inference, load_files

    graph = guard_inference.reconciliation_graph(load_files(repo))
    violations = sanitizers.reconcile(graph["edges"], graph["site_map"])
    report = san.counters()
    print(f"\n[sanitizer] {report['acquires']} instrumented acquires, "
          f"{report['edges_observed']} distinct lock-order edges, "
          f"{report['inversions']} inversions, "
          f"{report['sync_under_lock']} blocking-ops-under-lock, "
          f"{report['hold_violations']} hold-budget violations; "
          f"dynamic⊆static: "
          f"{'OK' if not violations else 'VIOLATED'}")
    if violations:
        for violation in violations:
            print(f"[sanitizer] {violation}")
        session.exitstatus = 1


@pytest.fixture(autouse=True)
def _failpoint_leak_guard():
    """Leak guard (ISSUE 2 satellite): a test that leaves a failpoint
    schedule active would inject faults into every later test — fail THAT
    test, loudly, and disarm before anything else runs."""
    yield
    from ytsaurus_tpu.utils import failpoints

    leaked = failpoints.active_spec()
    if leaked is not None:
        failpoints.deactivate()
        pytest.fail(f"test left failpoints active: {leaked!r}")


@pytest.fixture
def failpoints_active():
    """Scoped activation helper: `failpoints_active(spec, seed=7)` arms a
    schedule for the remainder of the test and guarantees disarm on
    teardown (even when the test body raises)."""
    from ytsaurus_tpu.utils import failpoints

    def arm(spec: str, seed: int = 0):
        failpoints.activate(spec, seed=seed)

    yield arm
    failpoints.deactivate()


@pytest.fixture(scope="session")
def mesh8():
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return Mesh(np.array(devices[:8]).reshape(8), ("shard",))
