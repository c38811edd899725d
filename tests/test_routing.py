"""Elastic serving: load-aware replica read routing, residency tiers,
and hot-shard rebalancing (docs/cluster.md "Read routing & rebalancing";
parallel/routing.py, parallel/balancer.py).

Covers: policy selection semantics against a real (unopened) Cluster —
primary byte-for-byte vs the legacy grouping, loaded scoring with the
no-data fallback, round-robin spread, residency preference with one
replica budget-constrained, breaker pre-skip (and its all-open waiver);
the 3-node differential (loaded answers byte-identical to primary under
interleaved writes); skew-corpus replica spread over real HTTP;
piggybacked load/residency folding; and the balancer: handoff
convergence with oracle-identical answers, overlay-aware writes,
epoch-gated overlay application on a restarted (state-wiped) node, and
balancer=off restoring static jump-hash exactly.
"""

import json
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel.cluster import Cluster
from pilosa_tpu.server.handler import serialize_result
from pilosa_tpu.server.server import Config, Server
from pilosa_tpu.storage import Holder

from test_cluster import _free_ports, _req, query


def make_routing_cluster(tmp_path, n=3, replica_n=2, **overrides):
    ports = _free_ports(n)
    hosts = [f"localhost:{p}" for p in ports]
    servers = []
    for i, p in enumerate(ports):
        cfg = Config(
            data_dir=str(tmp_path / f"node{i}"),
            bind=f"localhost:{p}",
            node_id=f"node{i}",
            cluster_hosts=hosts,
            replica_n=replica_n,
            anti_entropy_interval=0,  # driven manually in tests
        )
        for k, v in overrides.items():
            setattr(cfg, k, v)
        srv = Server(cfg)
        srv.open()
        servers.append(srv)
    return servers


def close_all(servers):
    for s in servers:
        try:
            s.close()
        except Exception:
            pass


# -- router selection semantics (no servers: a Cluster is enough) -----------


@pytest.fixture
def bare_cluster():
    """Unopened 3-node cluster over a memory holder: placement, router,
    and breaker state are all live without any sockets."""
    cl = Cluster("node0", ["localhost:1", "localhost:2", "localhost:3"],
                 replica_n=2, holder=Holder(None))
    yield cl
    cl.close()


def legacy_group(cl, index, shards):
    """The pre-routing grouping, reimplemented verbatim: self if an
    owner, else the first READY owner (executor.go:2435)."""
    groups = {}
    for s in shards:
        owners = cl.placement.shard_nodes(index, s)
        ready = [o for o in owners if cl.by_id[o].state == "READY"]
        order = ready or owners
        target = cl.node_id if cl.node_id in order else order[0]
        groups.setdefault(target, []).append(s)
    return groups


def test_primary_policy_matches_legacy_grouping(bare_cluster):
    cl = bare_cluster
    cl.router.policy = "primary"
    shards = list(range(24))
    assert cl.router.group_shards("i", shards) == \
        legacy_group(cl, "i", shards)
    # balancer off + empty overlay: owner sets are EXACTLY static
    # jump-hash
    for s in shards:
        assert cl.shard_owner_nodes("i", s) == \
            cl.placement.shard_nodes("i", s)


def test_loaded_with_no_history_falls_back_to_primary(bare_cluster):
    cl = bare_cluster
    cl.router.policy = "loaded"
    shards = list(range(16))
    assert cl.router.group_shards("i", shards) == \
        legacy_group(cl, "i", shards)
    assert cl.router.fallbacks >= 1
    assert cl.router.snapshot()["fallbacks"] >= 1


def test_loaded_prefers_low_load_replica(bare_cluster):
    cl = bare_cluster
    cl.router.policy = "loaded"
    cl.router.residency_routing = False
    # find a shard with two distinct remote owners so the score decides
    shard = next(s for s in range(64)
                 if "node0" not in cl.placement.shard_nodes("i", s))
    a, b = cl.placement.shard_nodes("i", shard)
    # equal RTT history; b is drowning in queued work
    cl.router.note_dispatch(a, 1)
    cl.router.note_done(a, 0.01)
    cl.router.note_dispatch(b, 1)
    cl.router.note_done(b, 0.01)
    cl.router.note_query_load(b, {"inFlight": 50, "queued": 10})
    groups = cl.router.group_shards("i", [shard])
    assert groups == {a: [shard]}
    # flip: now a is overloaded and b idle
    cl.router.note_query_load(a, {"inFlight": 50, "queued": 10})
    cl.router.note_query_load(b, {"inFlight": 0, "queued": 0})
    assert cl.router.group_shards("i", [shard]) == {b: [shard]}


def test_round_robin_spreads_owners(bare_cluster):
    cl = bare_cluster
    cl.router.policy = "round-robin"
    shard = next(s for s in range(64)
                 if "node0" not in cl.placement.shard_nodes("i", s))
    seen = set()
    for _ in range(6):
        ((nid, _),) = cl.router.group_shards("i", [shard]).items()
        seen.add(nid)
    assert seen == set(cl.placement.shard_nodes("i", shard))


def test_residency_preference_with_budget_constrained_replica(bare_cluster):
    """One replica advertises the shard HBM-resident, the other is
    budget-constrained (nothing resident): equal load must route to the
    resident one; with residency-routing off the tie reverts to
    placement order."""
    cl = bare_cluster
    cl.router.policy = "loaded"
    cl.router.residency_routing = True
    shard = next(s for s in range(64)
                 if "node0" not in cl.placement.shard_nodes("i", s))
    a, b = cl.placement.shard_nodes("i", shard)
    for nid in (a, b):
        cl.router.note_dispatch(nid, 1)
        cl.router.note_done(nid, 0.01)
    # b holds the shard resident; a (budget-constrained) holds nothing
    cl.router.note_status(b, {"residency": {"i": {"hbm": [shard],
                                                  "host": []}}})
    cl.router.note_status(a, {"residency": {}})
    assert cl.router.group_shards("i", [shard]) == {b: [shard]}
    snap = cl.router.snapshot()["peers"][b]
    assert snap["residencyAgeS"] is not None
    assert snap["residentShards"]["i"]["hbm"] == 1
    # host-staged beats disk-only too
    cl.router.note_status(b, {"residency": {"i": {"hbm": [],
                                                  "host": [shard]}}})
    assert cl.router.group_shards("i", [shard]) == {b: [shard]}
    # pure-load mode ignores residency: equal scores, placement order
    cl.router.residency_routing = False
    assert cl.router.group_shards("i", [shard]) == {a: [shard]}


def test_breaker_skip_before_dispatch_and_all_open_waiver(bare_cluster):
    cl = bare_cluster
    cl.router.policy = "primary"
    shard = next(s for s in range(64)
                 if "node0" not in cl.placement.shard_nodes("i", s))
    a, b = cl.placement.shard_nodes("i", shard)
    # open a's breaker directly
    ba = cl.client._breaker(cl.by_id[a].host)
    ba.state = "open"
    skips0 = cl.router.breaker_skips
    assert cl.router.group_shards("i", [shard]) == {b: [shard]}
    assert cl.router.breaker_skips == skips0 + 1
    assert cl.by_id[a].state == "DOWN"  # skip converges with NODE_DOWN
    # ALL candidates open: the skip is waived so the fan-out still
    # dispatches (and surfaces the fail-fast error loudly)
    cl.by_id[a].state = "READY"
    bb = cl.client._breaker(cl.by_id[b].host)
    bb.state = "open"
    groups = cl.router.group_shards("i", [shard])
    assert sum(groups.values(), []) == [shard]
    assert cl.router.breaker_skips == skips0 + 1  # no new skip counted


def test_overlay_epoch_gating_and_owner_extension(bare_cluster):
    cl = bare_cluster
    owners = cl.placement.shard_nodes("i", 0)
    extra = next(n.id for n in cl.nodes if n.id not in owners)
    cl._apply_overlay({"epoch": 3, "overlay": [["i", 0, [extra]]]})
    assert cl.overlay_epoch == 3
    assert cl.shard_owner_nodes("i", 0) == owners + [extra]
    assert cl.owned_shards(extra, "i", [0, 1]) \
        == [0] + ([1] if extra in cl.placement.shard_nodes("i", 1) else [])
    # older or duplicate epochs are idempotent no-ops
    cl._apply_overlay({"epoch": 2, "overlay": []})
    assert cl.overlay_epoch == 3
    assert cl.shard_owner_nodes("i", 0) == owners + [extra]
    # a newer empty table clears it
    cl._apply_overlay({"epoch": 4, "overlay": []})
    assert cl.shard_owner_nodes("i", 0) == owners


def test_shard_load_tracker_hot_and_spread():
    from pilosa_tpu.parallel.balancer import ShardLoadTracker
    tr = ShardLoadTracker(window_s=1000)
    for _ in range(40):
        tr.note("i", [7], "node1")
    for _ in range(8):
        tr.note("i", [7], "node2")
    for s in range(4):
        tr.note("i", [s], "node0")
    hot = tr.hot_shards(threshold=2.0)
    assert hot and hot[0][:2] == ("i", 7) and hot[0][2] == 48
    snap = tr.snapshot()
    top = snap["hottest"][0]
    assert top["shard"] == 7 and set(top["nodes"]) == {"node1", "node2"}
    assert tr.node_counts()["node1"] == 40
    # rotation keeps the previous window visible, then ages it out
    tr.rotate()
    assert tr.hot_shards(threshold=2.0)[0][2] == 48
    tr.rotate()
    assert tr.hot_shards(threshold=2.0) == []


# -- 3-node end-to-end suite -------------------------------------------------


@pytest.fixture(scope="module")
def rcluster(tmp_path_factory):
    """3-node replica_n=2 cluster with the ``sk`` corpus loaded (shared
    read-only by the skew/piggyback/residency tests, so each test does
    not pay 3 server startups)."""
    servers = make_routing_cluster(
        tmp_path_factory.mktemp("routing"), n=3, replica_n=2,
        read_routing="loaded")
    p0 = _setup(servers, "sk")
    cols = list(range(0, 4 * SHARD_WIDTH, SHARD_WIDTH // 8))
    _req(p0, "POST", "/index/sk/field/a/import",
         {"rowIDs": [1] * len(cols), "columnIDs": cols})
    yield servers
    close_all(servers)


def _setup(servers, name):
    p0 = servers[0].port
    _req(p0, "POST", f"/index/{name}", {})
    _req(p0, "POST", f"/index/{name}/field/a", {})
    _req(p0, "POST", f"/index/{name}/field/v",
         {"options": {"type": "int", "min": -500, "max": 500}})
    return p0


def test_differential_loaded_vs_primary_interleaved_writes(rcluster):
    """Byte-identity: the same queries answer identically under
    read-routing=primary and loaded, across interleaved writes, and
    match a single-node oracle holding identical data."""
    from pilosa_tpu.storage import FieldOptions

    servers = rcluster
    p0 = _setup(servers, "dr")
    rng = np.random.default_rng(17)
    n = 2500
    cols = rng.integers(0, 4 * SHARD_WIDTH, size=n)
    rows = rng.integers(0, 8, size=n)
    vcols = np.unique(cols[: n // 2])
    vvals = rng.integers(-500, 500, size=vcols.size)
    _req(p0, "POST", "/index/dr/field/a/import",
         {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()})
    _req(p0, "POST", "/index/dr/field/v/import",
         {"columnIDs": vcols.tolist(), "values": vvals.tolist()})

    oh = Holder(None)
    idx = oh.create_index("dr")
    idx.create_field("a").import_bits(rows, cols)
    idx.create_field("v", FieldOptions(
        type="int", min=-500, max=500)).import_values(vcols, vvals)
    idx.add_existence(cols)
    oracle = Executor(oh, use_mesh=True)

    queries = ["Count(Row(a=3))", "Row(a=1)",
               "Count(Intersect(Row(a=1), Row(a=2)))",
               "Sum(Row(a=4), field=v)", "Min(field=v)", "Max(field=v)",
               "TopN(a, n=0)", "Rows(a)",
               "GroupBy(Rows(a), limit=6)"]

    def run_policy(policy):
        for s in servers:
            s.cluster.router.policy = policy
        return [query(p0, "dr", q) for q in queries]

    try:
        for phase in range(2):
            want = [
                [json.loads(json.dumps(serialize_result(r)))
                 for r in oracle.execute("dr", q)] for q in queries]
            got_primary = run_policy("primary")
            got_loaded = run_policy("loaded")
            assert got_loaded == got_primary == want, f"phase {phase}"
            # interleaved writes (fan to every replica synchronously)
            wcol = int(rng.integers(0, 4 * SHARD_WIDTH))
            w = f"Set({wcol}, a=2) Clear({int(cols[phase])}, a={int(rows[phase])})"
            _req(p0, "POST", "/index/dr/query", w)
            oracle.execute("dr", w)
            idx.add_existence(np.array([wcol]))
    finally:
        for s in servers:
            s.cluster.router.policy = "loaded"
        oracle.close()


def test_skew_corpus_spreads_hot_shard(rcluster):
    """Skewed load on one shard with replica_n=2: loaded routing must
    serve the hot shard from MORE than one node (the idle-replica
    problem this subsystem exists to fix)."""
    servers = rcluster
    p0 = servers[0].port
    cols = list(range(0, 4 * SHARD_WIDTH, SHARD_WIDTH // 8))
    coord = servers[0].cluster
    # pick a hot shard with two REMOTE owners so spread is observable
    # regardless of the local bias
    hot = next(s for s in range(4)
               if "node0" not in coord.placement.shard_nodes("sk", s))
    hot_q = "Count(Row(a=1))"
    # seed RTT history (first waves fall back to primary and pay XLA
    # compiles; they must not count toward the spread assertion)
    for _ in range(4):
        query(p0, "sk", hot_q)
    tracker = coord.load_tracker
    tracker.rotate()
    tracker.rotate()

    served = set()
    for _round in range(3):
        threads = [threading.Thread(
            target=query, args=(p0, "sk", hot_q)) for _ in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        snap = tracker.snapshot(top=16)
        for entry in snap["hottest"]:
            if entry["index"] == "sk" and entry["shard"] == hot:
                served |= set(entry["nodes"])
        if len(served) > 1:
            break
    assert len(served) > 1, \
        f"hot shard {hot} only ever served by {served}"
    # answers stayed correct throughout
    [cnt] = query(p0, "sk", hot_q)
    assert cnt == len(cols)


def test_piggybacked_load_and_residency_fold(rcluster):
    """/internal/query responses and /status probes feed the router:
    after traffic + one probe pass the coordinator holds per-peer load
    and residency summaries, and every surface exposes them."""
    servers = rcluster
    p0 = servers[0].port
    coord = servers[0].cluster
    query(p0, "sk", "Count(Row(a=1))")
    coord.probe_peers()
    snap = coord.router.snapshot()
    peers = snap["peers"]
    assert peers, "router never saw a peer"
    remotes = {nid: st for nid, st in peers.items() if nid != "node0"}
    assert remotes, "router never saw a remote peer"
    for nid, st in remotes.items():
        assert st["reportedInFlight"] >= 0
        assert st["residencyAgeS"] is not None, \
            f"{nid} never advertised residency"
    # the peers ran queries, so their summaries list resident shards
    assert any(st["residentShards"] for st in remotes.values())
    # /status carries the piggybacks
    st = _req(servers[1].port, "GET", "/status")
    assert "load" in st and "residency" in st and "overlayEpoch" in st
    # /debug/vars cluster.routing + /metrics cluster_peer_* gauges
    dv = _req(p0, "GET", "/debug/vars")
    assert dv["cluster"]["routing"]["policy"] == "loaded"
    assert set(dv["cluster"]["routing"]["peers"]) >= {"node1", "node2"}
    import urllib.request
    with urllib.request.urlopen(
            f"http://localhost:{p0}/metrics", timeout=30) as resp:
        text = resp.read().decode()
    assert "pilosa_tpu_cluster_peer_node1_ewma_rtt_ms" in text
    assert "pilosa_tpu_cluster_peer_node2_inflight" in text
    assert "pilosa_tpu_cluster_overlay_epoch" in text


def test_local_residency_summary_tiers(rcluster):
    """A node that just served a mesh query reports the shards
    HBM-resident (stacked blocks count as resident)."""
    servers = rcluster
    query(servers[0].port, "sk", "Count(Row(a=1))")
    for s in servers:
        # a probe may have cached the summary (2 s TTL) before the query
        s.cluster._residency_cache = None
    summaries = [s.cluster.residency_summary() for s in servers]
    assert any("sk" in s and s["sk"]["hbm"] for s in summaries), \
        f"no node reports sk resident: {summaries}"


# -- hot-shard balancer ------------------------------------------------------


def test_balancer_handoff_converges_with_oracle_answers(tmp_path):
    """End-to-end handoff: a hot shard with replica_n=1 gains an overlay
    owner (fragments copied via the resize-fetch machinery), every node
    adopts the overlay epoch, answers stay oracle-identical, writes fan
    to the overlay owner, and a restarted state-wiped node is
    reconciled by the probe's overlay-epoch re-push."""
    servers = make_routing_cluster(tmp_path, n=3, replica_n=1,
                                   hot_shard_threshold=2.0)
    try:
        p0 = _setup(servers, "hb2")
        rng = np.random.default_rng(5)
        cols = np.unique(rng.integers(0, 6 * SHARD_WIDTH, size=1200))
        rows = rng.integers(0, 6, size=cols.size)
        _req(p0, "POST", "/index/hb2/field/a/import",
             {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()})
        coord = servers[0].cluster
        # a shard whose single owner is REMOTE, so the overlay owner and
        # the restart victim below are both non-coordinator nodes
        hot = next(s for s in range(6)
                   if coord.placement.primary("hb2", s) != "node0")
        hot_q = "Count(Row(a=2))"
        want = query(p0, "hb2", hot_q)
        # skewed load: the tracker must rank shard `hot` hot
        for _ in range(40):
            coord.load_tracker.note("hb2", [hot],
                                    coord.placement.primary("hb2", hot))
        for s in range(6):
            coord.load_tracker.note("hb2", [s], "node0")

        owners0 = coord.shard_owner_nodes("hb2", hot)
        assert len(owners0) == 1
        assert coord.balancer.tick() == 1, coord.balancer.snapshot()
        owners1 = coord.shard_owner_nodes("hb2", hot)
        assert len(owners1) == 2 and owners1[:1] == owners0
        extra = owners1[1]
        # every node adopted the same overlay epoch + table
        for s in servers:
            assert s.cluster.overlay_epoch == coord.overlay_epoch
            assert s.cluster.shard_owner_nodes("hb2", hot) == owners1
        # the overlay owner holds a real copy
        extra_srv = next(s for s in servers
                         if s.cluster.node_id == extra)
        frag = extra_srv.holder.fragment("hb2", "a", "standard", hot)
        assert frag is not None and frag.n_rows > 0
        # answers unchanged, from any node
        for s in servers:
            assert query(s.port, "hb2", hot_q) == want
        # writes now fan to the overlay owner too
        wcol = hot * SHARD_WIDTH + 123
        query(p0, "hb2", f"Set({wcol}, a=2)")
        assert extra_srv.holder.fragment(
            "hb2", "a", "standard", hot).row(2)[123 // 32] >> (123 % 32) & 1
        [cnt] = query(p0, "hb2", hot_q)
        assert cnt == want[0] + 1
        # bounded: a second tick can widen by at most one more owner,
        # and a third finds no non-owner left — never loops
        coord.balancer.tick()
        assert len(coord.shard_owner_nodes("hb2", hot)) <= 3

        # restart the OVERLAY owner with WIPED cluster state (.topology
        # removed): the probe pass must re-push the overlay, epoch-gated
        victim = extra_srv
        vid, vcfg = victim.cluster.node_id, victim.config
        servers.remove(victim)
        victim.close()
        import os
        topo = os.path.join(os.path.expanduser(vcfg.data_dir),
                            ".topology")
        if os.path.exists(topo):
            os.remove(topo)
        restarted = Server(vcfg)
        restarted.open()
        servers.append(restarted)
        assert restarted.cluster.overlay_epoch == 0  # wiped
        coord.probe_peers()

        def wait_for(cond, timeout=10.0):
            t0 = time.monotonic()
            while time.monotonic() - t0 < timeout:
                if cond():
                    return True
                time.sleep(0.05)
            return False

        assert wait_for(lambda: restarted.cluster.overlay_epoch
                        == coord.overlay_epoch)
        assert restarted.cluster.shard_owner_nodes("hb2", hot) \
            == coord.shard_owner_nodes("hb2", hot)
        assert query(restarted.port, "hb2", hot_q) == [want[0] + 1]
        # balancer counters surfaced
        dv = _req(p0, "GET", "/debug/vars")
        assert dv["cluster"]["balancer"]["handoffs"] >= 1
        assert dv["cluster"]["overlay"]["epoch"] >= 1
    finally:
        close_all(servers)


def test_balancer_off_is_static_jump_hash(tmp_path):
    """balancer=off (the default): no balancer thread, empty overlay,
    and the primary policy reproduces the static grouping exactly."""
    servers = make_routing_cluster(tmp_path, n=2, replica_n=2,
                                   read_routing="primary")
    try:
        p0 = _setup(servers, "st")
        _req(p0, "POST", "/index/st/field/a/import",
             {"rowIDs": [1, 1, 1],
              "columnIDs": [5, SHARD_WIDTH + 5, 2 * SHARD_WIDTH + 5]})
        coord = servers[0].cluster
        assert not coord.balancer_on
        assert coord.overlay_snapshot() == {"epoch": 0, "entries": []}
        shards = [0, 1, 2]
        assert coord.router.group_shards("st", shards) == \
            legacy_group(coord, "st", shards)
        [cnt] = query(p0, "st", "Count(Row(a=1))")
        assert cnt == 3
    finally:
        close_all(servers)
