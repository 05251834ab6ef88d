"""Daemon entry: `python -m ytsaurus_tpu.server.daemon --role primary|node`.

The multiplexed-binary pattern (ref server/all/main.cpp): one entry point,
role picked by flag.

  primary  — metadata master + tablet host + transaction coordinator +
             scheduler + driver proxy, with chunk data placed on remote
             data nodes (RpcChunkStore) once any register; falls back to a
             local store location until then.
  node     — blob chunk store + journal location, heartbeating to the
             primary.

The bound port is written to <root>/<role>.port for launcher discovery.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time


# analyze: allow(failpoint): bootstrap plumbing — a failed port write kills the spawn, surfaced by the cluster-start timeout
def _write_port_file(root: str, role: str, port: int) -> None:
    path = os.path.join(root, f"{role}.port")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(port))
    os.replace(tmp, path)


# analyze: allow(failpoint): daemon entry point — its I/O is bootstrap plumbing; fault sites live in the planes it hosts
def run_primary(root: str, port: int, replication_factor: int = 2,
                journal_nodes: int = 3,
                bootstrap_timeout: float = 60.0,
                election: bool = False, master_index: int = 0,
                lease_ttl: float = 6.0, kafka: bool = False,
                clocks: "str | None" = None) -> None:
    from ytsaurus_tpu import yson
    from ytsaurus_tpu.client import YtClient, YtCluster
    from ytsaurus_tpu.cypress.election import LeaderElector
    from ytsaurus_tpu.cypress.master import Master
    from ytsaurus_tpu.cypress.quorum import QuorumWal
    from ytsaurus_tpu.errors import YtError
    from ytsaurus_tpu.rpc import Channel, RetryingChannel, RpcServer
    from ytsaurus_tpu.server.remote_store import RpcChunkStore
    from ytsaurus_tpu.server.services import (
        DriverService,
        MasterService,
        NodeTracker,
        NodeTrackerService,
    )

    from ytsaurus_tpu.server.monitoring import MonitoringServer
    from ytsaurus_tpu.server.orchid import OrchidService, default_orchid

    os.makedirs(root, exist_ok=True)
    tracker = NodeTracker()
    # Bootstrap service set first: nodes must be able to register before
    # the master recovers (quorum WAL recovery reads their journals).
    role = {"value": "follower" if election else "leader"}
    server = RpcServer([NodeTrackerService(tracker),
                        MasterService(role)], port=port)
    server.start()
    _write_port_file(root, "primary", server.port)
    orchid = default_orchid()
    orchid.register("/node_tracker/alive", tracker.alive)
    orchid.register("/master/role", lambda: role["value"])
    server.add_service(OrchidService(orchid))
    monitoring = MonitoringServer(orchid)
    monitoring.start()
    _write_port_file(root, "primary.monitoring", monitoring.port)
    print(f"primary bootstrap on {server.address}", flush=True)

    # Journal membership is STICKY: chosen once, persisted, reused across
    # restarts so recovery always consults the same journal owners.
    journal_cfg_path = os.path.join(root, "journal_config.yson")
    wanted: list[str] | None = None
    if os.path.exists(journal_cfg_path):
        with open(journal_cfg_path, "rb") as f:
            wanted = [j.decode() if isinstance(j, bytes) else j
                      for j in yson.loads(f.read())["journal_node_ids"]]
    def _fetch_published_membership(
            ) -> "tuple[list[str] | None, bool]":
        """(highest-epoch membership record found on any alive node,
        every-alive-node-answered).  Under multi-master election the
        journal nodes are the shared source of truth for WHICH nodes
        form the quorum set — each master guessing from its own
        registration-order view could yield non-intersecting quorum
        sets (acked-write loss).  The completeness bit gates choosing a
        FRESH membership: "no record found" only counts when every node
        actually answered."""
        best: "tuple[int, list[str]] | None" = None
        complete = True
        for _, addr in sorted(tracker.alive().items()):
            channel = Channel(addr, timeout=5)
            try:
                body, _ = channel.call("data_node",
                                       "journal_membership_get",
                                       {"journal": "master_wal"})
                members = body.get("member_ids")
                if members is not None:
                    members = [m.decode() if isinstance(m, bytes) else m
                               for m in members]
                    epoch = int(body.get("epoch", 0))
                    if best is None or epoch > best[0]:
                        best = (epoch, members)
            except YtError:
                complete = False
                continue
            finally:
                channel.close()
        return (best[1] if best is not None else None), complete

    deadline = time.monotonic() + bootstrap_timeout
    chosen: dict[str, str] = {}
    had_prior_config = wanted is not None
    fresh_bootstrap = False
    clean_sweeps = 0
    if election:
        # Under election the sticky LOCAL config is advisory only: the
        # record published on the journal nodes (highest epoch) always
        # wins, since membership may have been upgraded by another
        # master while this one was down.
        wanted = None
    while time.monotonic() < deadline:
        alive = tracker.alive()
        if election:
            # Prefer membership already published to the journal nodes
            # (a previous leader's choice) over choosing our own.
            published, complete = _fetch_published_membership()
            if published is not None:
                if published != wanted:
                    wanted = published
                    continue
            elif wanted is None:
                # A fresh membership may be chosen ONLY by master 0, on
                # a root with no prior config (a restart implies a
                # published record exists somewhere — wait for it), and
                # only after two consecutive COMPLETE sweeps of enough
                # nodes found nothing (a transiently unreachable node
                # may be the one holding the record).
                clean_sweeps = clean_sweeps + 1 \
                    if complete and len(alive) >= journal_nodes else 0
                if master_index != 0 or had_prior_config or \
                        clean_sweeps < 2:
                    time.sleep(0.3)
                    continue
                chosen = dict(sorted(alive.items())[:journal_nodes])
                fresh_bootstrap = True
                break
        if wanted is not None:
            if all(i in alive for i in wanted):
                chosen = {i: alive[i] for i in wanted}
                break
        elif len(alive) >= journal_nodes:
            chosen = dict(sorted(alive.items())[:journal_nodes])
            break
        time.sleep(0.2)
    else:
        if wanted is not None:
            raise YtError(f"journal nodes {wanted} did not register within "
                          f"{bootstrap_timeout}s")
        if election:
            # No degraded bootstrap under election: divergent degraded
            # sets across masters can fail to intersect.
            raise YtError(
                f"election bootstrap needs {journal_nodes} journal nodes "
                f"(or a published membership) within {bootstrap_timeout}s")
        # Fewer nodes than asked for: take what registered rather than
        # collapsing to a local-only WAL.  Epoch acquisition needs a
        # strict majority of remotes, so an ODD remote count (default 3)
        # keeps takeover live under one dead journal node; an even count
        # still appends fine but requires all remotes up at takeover.
        alive = tracker.alive()
        if alive and journal_nodes > 0:
            chosen = dict(sorted(alive.items())[:journal_nodes])
            print(f"# only {len(chosen)}/{journal_nodes} journal nodes "
                  f"registered within {bootstrap_timeout}s; using "
                  f"{sorted(chosen)} (membership upgrades after recovery "
                  "as more nodes register)", flush=True)
        else:
            print(f"# no data nodes within {bootstrap_timeout}s; "
                  "falling back to local-only WAL", flush=True)

    def _persist_journal_config(ids: list[str]) -> None:
        tmp = journal_cfg_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(yson.dumps({"journal_node_ids": sorted(ids)},
                               binary=True))
        os.replace(tmp, journal_cfg_path)

    if chosen and wanted is None:
        _persist_journal_config(sorted(chosen))

    master_dir = os.path.join(root, "master")
    os.makedirs(master_dir, exist_ok=True)
    wal = None
    elector = None

    def _build_channels(members: dict) -> list:
        return [RetryingChannel(Channel(addr, timeout=30),
                                attempts=2, backoff=0.1)
                for _, addr in sorted(members.items())]

    if chosen:
        channels = _build_channels(chosen)

        def make_wal():
            # First adoption of this quorum config (we just wrote the
            # journal membership): any existing local log predates the
            # quorum and is authoritative — it seeds the replicas
            # instead of being outvoted by their empty journals.  Under
            # election only a verified FRESH bootstrap (master 0, no
            # prior config, complete no-record sweeps) may treat local
            # history as authoritative: anything else would reset the
            # journals from a stale or empty local log.
            # Election mode uses a REMOTE-ONLY quorum: a failover
            # successor recovers with a fresh local location, so read
            # and write quorums must intersect over the shared journal
            # nodes alone (see QuorumWal.count_local_ack).
            locations = 1 + len(channels)
            return QuorumWal(
                os.path.join(master_dir, Master.CHANGELOG),
                journal_name="master_wal",
                remote_channels=channels,
                quorum=(len(channels) // 2 + 1) if election
                else locations // 2 + 1,
                count_local_ack=not election,
                bootstrap_from_local=(
                    fresh_bootstrap if election else wanted is None),
                lease_ttl=lease_ttl if election else 0.0)

        wal = make_wal()
        print(f"quorum WAL over local + {sorted(chosen)} "
              f"(quorum {wal.quorum})", flush=True)
    if election and wal is None:
        raise YtError("--election requires journal nodes (the journal "
                      "plane carries votes and leases)")
    def _publish_membership() -> None:
        """Write the (epoch-stamped) membership to every journal node so
        any master resolves the same quorum set."""
        for replica in wal.replicas:
            try:
                replica.channel.call(
                    "data_node", "journal_membership_put",
                    {"journal": wal.journal_name, "epoch": wal.epoch,
                     "writer": wal.writer_id,
                     "member_ids": sorted(chosen)}, idempotent=False)
            except YtError as err:
                print(f"# membership publish failed on one node: {err}",
                      flush=True)

    if election:
        # Candidate loop: wait for the lease plane to be takeover-free,
        # then try to win the epoch (which also claims the lease on each
        # granting location).  A lost race returns to standby.
        while True:
            elector = LeaderElector(
                "master_wal",
                lambda: [r.channel for r in wal.replicas],
                wal.writer_id, lease_ttl=lease_ttl,
                hold_down=master_index * (lease_ttl / 4.0))
            print(f"standby (master {master_index}): awaiting "
                  "leadership", flush=True)
            elector.wait_until_electable()
            # Re-resolve membership RIGHT BEFORE takeover: the previous
            # leader may have upgraded it while this standby slept, and
            # recovering over a stale subset could drop records acked on
            # the newer set (then re-publish the stale set at a higher
            # epoch, poisoning future bootstraps).
            latest, _ = _fetch_published_membership()
            if latest is not None and sorted(latest) != sorted(chosen):
                alive_now = tracker.alive()
                if all(i in alive_now for i in latest):
                    print(f"membership changed to {sorted(latest)}; "
                          "rebuilding WAL", flush=True)
                    elector.stop()
                    wal.close()
                    chosen.clear()
                    chosen.update({i: alive_now[i] for i in latest})
                    _persist_journal_config(sorted(chosen))
                    channels = _build_channels(chosen)
                    wal = make_wal()
                    continue
            try:
                master = Master(master_dir, wal=wal)
                break
            except YtError as err:
                print(f"takeover failed: {err}; back to standby",
                      flush=True)
                elector.stop()
                wal.close()          # no fd leak across retries
                time.sleep(1.0)
                wal = make_wal()     # fresh writer identity for next try
        _publish_membership()
    else:
        master = Master(master_dir, wal=wal)
    # A membership persisted while under-strength (slow node startup on a
    # previous boot) upgrades here, AFTER recovery: new locations are
    # seeded with the full committed log before the larger quorum is
    # adopted, so the sticky config never pins the cluster to a degraded
    # journal set forever.
    if wal is not None and len(chosen) < journal_nodes:
        extra = {i: a for i, a in sorted(tracker.alive().items())
                 if i not in chosen}
        extra = dict(list(extra.items())[:journal_nodes - len(chosen)])
        adopted = {}
        for node_id, addr in sorted(extra.items()):
            channel = RetryingChannel(Channel(addr, timeout=30),
                                      attempts=2, backoff=0.1)
            # One node at a time: only nodes the WAL actually KEPT are
            # persisted — a failed catch-up must not become a phantom
            # quorum member that outvotes acknowledged records next boot.
            if wal.extend([channel]) == 1:
                adopted[node_id] = addr
        if adopted:
            chosen.update(adopted)
            _persist_journal_config(sorted(chosen))
            if election:
                _publish_membership()
            print(f"quorum WAL membership upgraded to "
                  f"{sorted(chosen)} (quorum {wal.quorum})",
                  flush=True)
    if election and elector is not None:
        def on_lease_lost():
            # The automaton may be ahead of what a new leader recovered;
            # serving (even reads) risks confusion — fail-stop for a
            # supervised restart as a follower (Hydra restart semantics).
            master._poisoned = True
            role["value"] = "follower"
            print("leadership lost (lease not renewable); exiting for "
                  "supervised restart", flush=True)
            os._exit(17)

        # Epoch via callable: _maybe_reacquire bumps it after orphaned
        # fences, and renewals must follow or a healthy leader's
        # renewals are denied everywhere.
        elector.start_renewing(lambda: wal.epoch, on_lease_lost)
    # The primary holds NO chunk location of its own: all chunk data lives
    # on data-node processes.
    store = RpcChunkStore(tracker.alive_nodes,
                          replication_factor=replication_factor)
    cluster = YtCluster(root, chunk_store=store, master=master)
    cluster.node_directory = tracker.alive    # enables exec-node dispatch
    if clocks:
        # Tablet commits take timestamps from the CLOCK QUORUM, not an
        # in-process provider: timestamps stay monotone across master
        # failover because the oracle outlives any master (ref
        # clock_server/cluster_clock).
        from ytsaurus_tpu.tablet.clock import QuorumTimestampProvider
        provider = QuorumTimestampProvider(
            [a.strip() for a in clocks.split(",") if a.strip()])
        cluster.transactions.timestamps = provider
        print(f"tablet timestamps from clock quorum: {clocks}",
              flush=True)
    client = YtClient(cluster)
    server.add_service(DriverService(client))
    # Cluster compile-artifact tier (ISSUE 17): AOT executables publish
    # to the chunk store on compile and fetch on miss, so a replica
    # added mid-storm joins HOT — zero inline compiles for shapes its
    # peers already built.  Content-addressed ids make this safe to
    # share across every primary of the cluster.
    from ytsaurus_tpu.query.engine.aot_cache import (
        ClusterArtifactStore,
        set_cluster_store,
    )
    artifact_store = ClusterArtifactStore(store)
    set_cluster_store(artifact_store)
    orchid.register("/query/compile_cache/cluster",
                    artifact_store.snapshot)
    # Adaptive tiering plane (ISSUE 18): the tier ladder's live state —
    # kill switch, promotion queue, per-fingerprint interpreted-run
    # roll-up — next to the compile cache it feeds.
    orchid.register("/query/tiers", cluster.evaluator.tier_snapshot)
    monitoring.tier_evaluator = cluster.evaluator
    # Capture-driven prewarm (ISSUE 18 tentpole, piece c): replay an
    # exported workload capture COMPILE-ONLY before serving traffic, so
    # a restarted replica's first queries hit warm programs instead of
    # paying inline compiles.  Gated on the env var (daemon idiom) or
    # TieringConfig.prewarm_capture; a missing/broken capture logs and
    # serves cold — prewarm is an optimization, never a boot gate.
    from ytsaurus_tpu.config import tiering_config
    prewarm_capture = os.environ.get("YT_TPU_PREWARM_CAPTURE") or \
        tiering_config().prewarm_capture
    if prewarm_capture:
        from ytsaurus_tpu.query.engine.prewarm import prewarm_capture_file
        try:
            report = prewarm_capture_file(prewarm_capture, client=client,
                                          evaluator=cluster.evaluator)
            print(f"prewarm {prewarm_capture}: "
                  f"{report['compiled']} compiled, "
                  f"{report['aot_hits']} AOT hits, "
                  f"{report['skipped']} skipped "
                  f"({report['seconds']:.3f}s)", flush=True)
        except Exception as err:   # noqa: BLE001 — serve cold instead
            print(f"prewarm failed ({prewarm_capture}): {err}",
                  flush=True)
    # Background re-replication: a dead node's chunks regain their
    # replication factor within ~interval, read or no read (ref
    # chunk_replicator.h).  A follower's empty node tracker makes its
    # scans no-ops, so starting unconditionally is safe under election.
    # Liveness from the metadata tree keeps deleted chunks from being
    # resurrected off a node that missed their removal.
    from ytsaurus_tpu.server.chunk_replicator import ChunkReplicator
    replicator = ChunkReplicator(
        tracker.alive_nodes, replication_factor=replication_factor,
        liveness_provider=client.referenced_chunk_ids)
    replicator.start()
    orchid.register("/chunk_replicator", lambda: dict(replicator.stats))
    # Small-chunk background compaction (ref chunk_merger.h:136).
    from ytsaurus_tpu.server.chunk_merger import ChunkMerger
    merger = ChunkMerger(client).start()
    orchid.register("/chunk_merger", lambda: dict(merger.stats))
    # Continuous CPU profiler + span export (ref ytprof cpu_profiler.h,
    # jaeger/tracer.h): always-on statistical sampling served via
    # Orchid; finished spans batch-flush to <root>/traces.jsonl.
    try:
        profiler_interval = float(
            os.environ.get("YT_TPU_PROFILER_INTERVAL", 0.05))
    except ValueError:
        # 'off'/'50ms'/'': the operator meant SOMETHING non-default —
        # disable rather than refuse to boot the primary.
        print("# YT_TPU_PROFILER_INTERVAL unparseable; profiler off",
              flush=True)
        profiler_interval = 0.0
    if profiler_interval > 0:
        from ytsaurus_tpu.utils.profiler import (
            SamplingProfiler,
            TraceExporter,
            jsonl_sink,
        )
        cpu_profiler = SamplingProfiler(
            interval=profiler_interval).start()
        orchid.register("/profiler", lambda: {
            **cpu_profiler.state(),
            "hotspots": cpu_profiler.hotspots()})
        orchid.register("/profiler/collapsed",
                        lambda: cpu_profiler.collapsed())
        exporter = TraceExporter(
            jsonl_sink(os.path.join(root, "traces.jsonl"))).start()
        orchid.register("/tracing/export", lambda: dict(exporter.stats))
        # The exporter DRAINS the collector: recent_spans now serves
        # from the exporter's tail or it would always read empty.
        orchid.register("/tracing/recent_spans",
                        lambda: list(exporter.recent))
    # Generalized service discovery (ref server/discovery_server): any
    # process can publish into named groups; NodeTracker stays the
    # data-node special case.
    from ytsaurus_tpu.server.discovery import (
        DAEMONS_GROUP,
        DiscoveryService,
        DiscoveryTracker,
        announce_daemon,
    )
    discovery = DiscoveryTracker()
    server.add_service(DiscoveryService(discovery))
    orchid.register("/discovery", discovery.list_groups)
    # Cluster telemetry plane (ISSUE 6): start the sampler that fills
    # the metrics-history rings + evaluates SLO burn rates, register
    # this primary's monitoring endpoint in /daemons, and wire the
    # /cluster roll-up to scrape every registered member.
    from ytsaurus_tpu.utils.profiling import start_telemetry
    start_telemetry()
    announce_daemon(discovery, "primary", monitoring.address,
                    role="primary")
    monitoring.cluster_members = \
        lambda: discovery.list_members(DAEMONS_GROUP)
    if kafka:
        # Kafka wire protocol over queues (ref server/kafka_proxy):
        # in-process with the primary, like the query tracker / queue
        # agent, so consumer registrations ride the same client.
        from ytsaurus_tpu.server.kafka_proxy import KafkaProxy
        kafka_proxy = KafkaProxy(client).start()
        _write_port_file(root, "kafka", kafka_proxy.port)
        print(f"kafka proxy serving on {kafka_proxy.address}", flush=True)
    if os.environ.get("YT_TPU_SEQUOIA", "") not in ("", "0"):
        # Sequoia resolve ground table (cypress/sequoia.py): path
        # resolution served from a dynamic table, kept consistent off
        # the mutation stream.
        from ytsaurus_tpu.cypress.sequoia import SequoiaResolver
        sequoia = SequoiaResolver(client).enable()
        # verify() is a full tree walk + three ground-table scans under
        # the mutation lock — far too heavy to run on EVERY /sequoia
        # Orchid read (each read would stall the whole mutation stream).
        # Reads serve cached counters; verification runs on a background
        # cadence, and /sequoia/verify is the explicit on-demand action.
        verify_state = {"divergent": [], "verify_runs": 0,
                        "verified_at": None}

        def _sequoia_verify():
            # The tree walk compares live tree vs table snapshots: hold
            # the mutation lock so a concurrent mutation can't produce a
            # torn (spuriously divergent) read.
            with client.cluster.master.mutation_lock:
                divergent = sequoia.verify()
            verify_state["divergent"] = divergent
            verify_state["verify_runs"] += 1
            verify_state["verified_at"] = time.time()
            return {"divergent": divergent,
                    "verify_runs": verify_state["verify_runs"]}

        def _sequoia_state():
            return {"enabled": True,
                    "records": len(sequoia._paths),
                    "divergent": list(verify_state["divergent"]),
                    "verify_runs": verify_state["verify_runs"],
                    "verified_at": verify_state["verified_at"]}

        _sequoia_verify()                  # one startup pass seeds the cache
        verify_interval = float(
            os.environ.get("YT_TPU_SEQUOIA_VERIFY_INTERVAL", 60))

        def _sequoia_verify_loop() -> None:
            while True:
                time.sleep(verify_interval)
                try:
                    _sequoia_verify()
                except Exception as exc:  # noqa: BLE001 — keep the cadence
                    print(f"# sequoia verify failed: {exc}", flush=True)

        if verify_interval > 0:
            threading.Thread(target=_sequoia_verify_loop, daemon=True,
                             name="sequoia-verify").start()
        orchid.register("/sequoia", _sequoia_state)
        orchid.register("/sequoia/verify", _sequoia_verify)
        print("sequoia ground tables enabled", flush=True)
    role["value"] = "leader"
    print(f"primary serving on {server.address}"
          + (f" (leader, master {master_index})" if election else ""),
          flush=True)
    threading.Event().wait()       # serve until killed


def run_node(root: str, port: int, primary_address: str,
             node_id: str | None = None) -> None:
    from ytsaurus_tpu.chunks.store import FsChunkStore
    from ytsaurus_tpu.rpc import Channel, RetryingChannel, RpcServer
    from ytsaurus_tpu.server.services import DataNodeService

    from ytsaurus_tpu.server.monitoring import MonitoringServer
    from ytsaurus_tpu.server.orchid import OrchidService, default_orchid

    from ytsaurus_tpu.server.exec_service import ExecNodeService

    os.makedirs(root, exist_ok=True)
    node_id = node_id or os.path.basename(os.path.normpath(root))
    store = FsChunkStore(os.path.join(root, "chunks"))
    service = DataNodeService(store, os.path.join(root, "journals"))
    exec_service = ExecNodeService(store)
    orchid = default_orchid()
    orchid.register("/data_node", lambda: {
        "id": node_id, "chunk_count": len(store.list_chunks())})
    orchid.register("/exec_node", lambda: exec_service.exec_stats({}, ()))
    # Periodic checksum scrub: corrupt chunks quarantine themselves and
    # the master's replicator restores RF from healthy holders.
    scrub_interval = float(os.environ.get("YT_TPU_SCRUB_INTERVAL", 300))
    scrub_state = {"checked": 0, "corrupt": 0}

    def scrub_loop() -> None:
        while True:
            time.sleep(scrub_interval)
            try:
                out = service.scrub_chunks({}, ())
                scrub_state["checked"] += out["checked"]
                scrub_state["corrupt"] += len(out["corrupt"])
            except Exception as exc:  # noqa: BLE001 — keep scrubbing
                print(f"# scrub failed: {exc}", file=sys.stderr,
                      flush=True)

    if scrub_interval > 0:
        threading.Thread(target=scrub_loop, daemon=True,
                         name="chunk-scrubber").start()
    orchid.register("/data_node/scrub", lambda: dict(scrub_state))
    server = RpcServer([service, exec_service,
                        OrchidService(orchid)], port=port)
    server.start()
    _write_port_file(root, "node", server.port)
    # P2P hot-chunk distribution (ref data_node/p2p.h TP2PDistributor):
    # reads past the heat threshold seed copies onto peers, discovered
    # through the primary's node tracker.
    from ytsaurus_tpu.server.p2p import P2PDistributor
    self_address = f"127.0.0.1:{server.port}"

    def p2p_peers() -> list:
        from ytsaurus_tpu.errors import YtError as _YtError
        from ytsaurus_tpu.rpc import Channel
        # Every primary answers (the node already heartbeats them all);
        # falling over keeps discovery alive when one master is down.
        for addr in primary_address.split(","):
            if not addr.strip():
                continue
            channel = Channel(addr.strip(), timeout=10)
            try:
                body, _ = channel.call("node_tracker", "list_nodes", {})
                return [a.decode() if isinstance(a, bytes) else a
                        for a in body.get("alive") or []]
            except _YtError:
                continue
            finally:
                channel.close()
        return []

    p2p = P2PDistributor(
        store, lambda: self_address, p2p_peers,
        hot_threshold=int(os.environ.get("YT_TPU_P2P_THRESHOLD", 50)),
        window=float(os.environ.get("YT_TPU_P2P_WINDOW", 5.0)),
        cooldown=float(os.environ.get("YT_TPU_P2P_COOLDOWN", 120.0)),
    ).start()
    service.p2p = p2p
    orchid.register("/data_node/p2p", lambda: dict(p2p.stats))
    monitoring = MonitoringServer(orchid)
    monitoring.start()
    _write_port_file(root, "node.monitoring", monitoring.port)
    # Telemetry plane (ISSUE 6): every daemon samples its own sensors
    # into bounded history rings; the primary's /cluster scrapes them.
    from ytsaurus_tpu.server.discovery import DAEMONS_GROUP
    from ytsaurus_tpu.utils.profiling import start_telemetry
    start_telemetry()
    print(f"data node {node_id} serving on {server.address}", flush=True)

    # Multi-master: heartbeat EVERY primary (comma-separated), each on
    # its OWN thread — a hung (not dead) master must not stall the
    # heartbeats that keep this node alive on the healthy leader.
    address = server.address

    def beat(primary: str) -> None:
        channel = RetryingChannel(Channel(primary, timeout=10),
                                  attempts=2, backoff=0.1)
        while True:
            try:
                channel.call("node_tracker", "heartbeat",
                             {"id": node_id, "address": address})
                # Telemetry membership rides the same cadence: the
                # primary's /cluster roll-up scrapes every /daemons
                # member's monitoring endpoint.  Own try: the discovery
                # service only comes up after WAL recovery, and its
                # absence during bootstrap must not spam the log (the
                # node_tracker beat above already succeeded).
                try:
                    channel.call("discovery", "heartbeat",
                                 {"group": DAEMONS_GROUP,
                                  "member_id": node_id,
                                  "address": monitoring.address,
                                  "attributes": {"role": "node"}})
                except Exception:   # noqa: BLE001
                    pass
            except Exception as exc:  # noqa: BLE001 — keep heartbeating
                print(f"# heartbeat to {primary} failed: {exc}",
                      file=sys.stderr, flush=True)
            time.sleep(2.0)

    primaries = [a.strip() for a in primary_address.split(",")
                 if a.strip()]
    for primary in primaries[1:]:
        threading.Thread(target=beat, args=(primary,),
                         daemon=True, name=f"heartbeat-{primary}").start()
    beat(primaries[0])


# analyze: allow(failpoint): daemon entry point — bootstrap plumbing; clock-quorum faults are injected via journal sites
def run_clock(root: str, port: int, journals: "str | None", index: int,
              lease_ttl: float,
              journals_file: "str | None" = None) -> None:
    """Clock-quorum peer (ref server/clock_server/cluster_clock +
    server/timestamp_provider): serves HLC timestamps under a
    quorum-persisted ceiling, independent of the masters — tablet
    commits keep taking timestamps with the primary down.

    The RPC port binds FIRST (answering NotClockLeader until the core
    exists), so launchers can learn the address before the journal
    plane is even up; --journals-file is polled for the journal
    addresses, breaking the clock↔node startup ordering cycle without
    pre-allocating ports."""
    import time as _time

    from ytsaurus_tpu.rpc import Channel, RpcServer
    from ytsaurus_tpu.tablet.clock import (
        ClockServer,
        ClockService,
        NotClockLeader,
    )

    os.makedirs(root, exist_ok=True)
    holder: dict = {"clock": None}

    class _LateBound:
        def generate_batch(self, count=1):
            clock = holder["clock"]
            if clock is None:
                raise NotClockLeader()
            return clock.generate_batch(count)

        @property
        def is_leader(self):
            clock = holder["clock"]
            return bool(clock is not None and clock.is_leader)

    server = RpcServer([ClockService(_LateBound())], port=port)
    server.start()
    _write_port_file(root, "clock", server.port)
    print(f"clock peer {index} serving on {server.address}", flush=True)
    if journals is None:
        while True:
            try:
                with open(journals_file) as f:
                    journals = f.read().strip()
                if journals:
                    break
            except FileNotFoundError:
                pass
            _time.sleep(0.2)
    channels = [Channel(a.strip(), timeout=10)
                for a in journals.split(",") if a.strip()]
    holder["clock"] = ClockServer(root, channels, index=index,
                                  lease_ttl=lease_ttl).start()
    threading.Event().wait()


def run_proxy(root: str, port: int, primary_address: str) -> None:
    """HTTP proxy daemon: REST /api/v4 bridged to the primary's RPC plane
    (ref: the standalone http_proxy process, server/http_proxy)."""
    from ytsaurus_tpu.remote_client import RemoteYtClient
    from ytsaurus_tpu.server.http_proxy import HttpProxy

    os.makedirs(root, exist_ok=True)
    proxy = HttpProxy(
        lambda user: RemoteYtClient(primary_address, user=user),
        port=port)
    _write_port_file(root, "proxy", proxy.port)
    print(f"http proxy serving on {proxy.address} -> {primary_address}",
          flush=True)
    proxy.serve_forever()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--role",
                        choices=("primary", "node", "proxy",
                                 "master_cache", "tcp_proxy", "clock",
                                 "scheduler"),
                        required=True)
    parser.add_argument("--journals", default=None,
                        help="journal-node addresses (clock role)")
    parser.add_argument("--journals-file", default=None,
                        help="file to poll for journal addresses "
                             "(clock role; alternative to --journals)")
    parser.add_argument("--clocks", default=None,
                        help="clock-peer addresses (primary role): take "
                             "tablet timestamps from the clock quorum")
    parser.add_argument("--root", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--primary", default=None,
                        help="primary address (node role)")
    parser.add_argument("--replication-factor", type=int, default=2)
    parser.add_argument("--journal-nodes", type=int, default=3,
                        help="remote WAL locations (0 = local-only WAL); "
                             "odd counts keep takeover live under one "
                             "dead journal node")
    parser.add_argument("--node-id", default=None)
    parser.add_argument("--bootstrap-timeout", type=float, default=60.0)
    parser.add_argument("--election", action="store_true",
                        help="multi-master mode: lease-based leader "
                             "election over the journal plane")
    parser.add_argument("--master-index", type=int, default=0,
                        help="this master's index (staggers takeover "
                             "attempts; index 0 bootstraps fresh "
                             "clusters)")
    parser.add_argument("--lease-ttl", type=float, default=6.0)
    parser.add_argument("--kafka", action="store_true",
                        help="serve the Kafka wire protocol over queues "
                             "(primary role; port in <root>/kafka.port)")
    args = parser.parse_args()

    # Daemons never touch accelerators (a chip belongs to one process,
    # and a LocalCluster runs several): pin CPU before any jax import.
    import jax
    jax.config.update("jax_platforms", "cpu")

    if args.role == "primary":
        run_primary(args.root, args.port, args.replication_factor,
                    journal_nodes=args.journal_nodes,
                    bootstrap_timeout=args.bootstrap_timeout,
                    election=args.election,
                    master_index=args.master_index,
                    lease_ttl=args.lease_ttl, kafka=args.kafka,
                    clocks=args.clocks)
    elif args.role == "proxy":
        if not args.primary:
            parser.error("--primary is required for --role proxy")
        run_proxy(args.root, args.port, args.primary)
    elif args.role == "master_cache":
        if not args.primary:
            parser.error("--primary is required for --role master_cache")
        from ytsaurus_tpu.server.master_cache import run_master_cache
        run_master_cache(args.root, args.port, args.primary)
    elif args.role == "scheduler":
        if not args.primary:
            parser.error("--primary is required for --role scheduler")
        from ytsaurus_tpu.server.scheduler_daemon import run_scheduler
        run_scheduler(args.root, args.port, args.primary)
    elif args.role == "clock":
        if not args.journals and not args.journals_file:
            parser.error("--journals or --journals-file is required "
                         "for --role clock")
        run_clock(args.root, args.port, args.journals,
                  args.master_index, args.lease_ttl,
                  journals_file=args.journals_file)
    elif args.role == "tcp_proxy":
        if not args.primary:
            parser.error("--primary is required for --role tcp_proxy")
        from ytsaurus_tpu.server.tcp_proxy import TcpProxy
        os.makedirs(args.root, exist_ok=True)
        proxy = TcpProxy([a.strip() for a in args.primary.split(",")
                          if a.strip()], port=args.port).start()
        _write_port_file(args.root, "tcp_proxy", proxy.port)
        print(f"tcp proxy serving on {proxy.address} -> {args.primary}",
              flush=True)
        threading.Event().wait()
    else:
        if not args.primary:
            parser.error("--primary is required for --role node")
        run_node(args.root, args.port, args.primary, node_id=args.node_id)


if __name__ == "__main__":
    main()
