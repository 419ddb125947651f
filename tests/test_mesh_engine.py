"""MeshEngine end-to-end: raft groups whose replicas span the 8-device CPU
mesh, served through the real NodeHost client API (VERDICT round-2 item 3 —
the ICI mesh promoted from bench island to serving path).

Scenarios mirror test_nodehost.py / test_kernel_engine.py with
``Config.mesh_resident=True``: every NodeHost attaches to one shared
MeshEngine, replicas of a shard live on different devices along mesh axis
'r', and intra-group raft traffic rides the all_gather inside the jitted
step instead of the chan transport (parallel/ici.py:serve_body).
"""

import time

import pytest

from dragonboat_tpu.config import (
    Config,
    ExpertConfig,
    MeshSpec,
    NodeHostConfig,
)
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.request import RequestDroppedError, RequestTimeoutError

from test_nodehost import KVStateMachine, wait_leader


def propose_retry(nh, sess, cmd, timeout_s=5, deadline_s=30):
    end = time.time() + deadline_s
    while True:
        try:
            return nh.sync_propose(sess, cmd, timeout_s=timeout_s)
        except (RequestDroppedError, RequestTimeoutError):
            if time.time() > end:
                raise
            time.sleep(0.1)


def make_cluster(prefix, n=3, snapshot_entries=0, rtt_ms=5, shards=(1,),
                 node_host_dirs=None):
    """n NodeHosts sharing one (2, 3)-mesh: 6 of the 8 virtual devices."""
    spec = MeshSpec(name=prefix, g_size=2, replicas=3, n_local=4)
    addrs = {i: f"{prefix}-{i}" for i in range(1, n + 1)}
    hosts = {}
    for rid, addr in addrs.items():
        nh = NodeHost(NodeHostConfig(
            raft_address=addr, rtt_millisecond=rtt_ms,
            node_host_dir=(node_host_dirs or {}).get(rid, ""),
            expert=ExpertConfig(mesh=spec, kernel_log_cap=256,
                                kernel_apply_batch=16,
                                kernel_compaction_overhead=16)))
        for sid in shards:
            cfg = Config(shard_id=sid, replica_id=rid, election_rtt=10,
                         heartbeat_rtt=2, snapshot_entries=snapshot_entries,
                         compaction_overhead=5, mesh_resident=True)
            nh.start_replica(addrs, False, KVStateMachine, cfg)
        hosts[rid] = nh
    return hosts


def close_all(hosts):
    for nh in hosts.values():
        nh.close()


@pytest.fixture
def cluster():
    hosts = make_cluster(f"mshA{time.monotonic_ns()}")
    yield hosts
    close_all(hosts)


def test_mesh_shard_is_mesh_resident(cluster):
    hosts = cluster
    eng = hosts[1].mesh_engine
    assert eng is not None
    # one shared engine across the attached NodeHosts
    assert eng is hosts[2].mesh_engine is hosts[3].mesh_engine
    # replicas occupy distinct rows (distinct devices along axis 'r')
    rows = [eng.by_shard[(1, r)].lane for r in (1, 2, 3)]
    assert len(set(rows)) == 3
    # protocol state lives on the mesh, not in a pycore Peer
    assert all(hosts[r].nodes[1].peer is None for r in hosts)


def test_mesh_propose_and_read(cluster):
    hosts = cluster
    lid = wait_leader(hosts, timeout=60)
    nh = hosts[lid]
    sess = nh.get_noop_session(1)
    for i in range(10):
        propose_retry(nh, sess, f"k{i}=v{i}".encode())
    assert nh.sync_read(1, "k7", timeout_s=10) == "v7"
    deadline = time.time() + 10
    while time.time() < deadline:
        if all(h.stale_read(1, "k9") == "v9" for h in hosts.values()):
            break
        time.sleep(0.05)
    assert all(h.stale_read(1, "k9") == "v9" for h in hosts.values())


def test_mesh_propose_via_follower_host(cluster):
    """Follower-host proposals forward in-engine to the leader row (the
    reference forwards MsgProp through the raft core)."""
    hosts = cluster
    lid = wait_leader(hosts, timeout=60)
    frid = next(r for r in hosts if r != lid)
    fnh = hosts[frid]
    r = propose_retry(fnh, fnh.get_noop_session(1), b"fwd=yes")
    assert r.value >= 1
    assert hosts[lid].sync_read(1, "fwd", timeout_s=10) == "yes"


def test_mesh_read_from_follower_host(cluster):
    """ReadIndex forwarded over the host transport to the leader row."""
    hosts = cluster
    lid = wait_leader(hosts, timeout=60)
    propose_retry(hosts[lid], hosts[lid].get_noop_session(1), b"fr=ok")
    frid = next(r for r in hosts if r != lid)
    # one read under one limit: a forwarded read that is lost shows as a
    # timeout, not as a second try that happened to land
    assert hosts[frid].sync_read(1, "fr", timeout_s=10) == "ok"


def test_mesh_leader_transfer(cluster):
    hosts = cluster
    lid = wait_leader(hosts, timeout=60)
    target = next(r for r in hosts if r != lid)
    node = hosts[lid].nodes[1]
    rs = node.request_leader_transfer(target, 2000)
    hosts[lid]._work.set()
    r = rs.wait(20.0)
    assert r.code.name == "COMPLETED", r.code
    assert wait_leader(hosts, timeout=30) == target


def test_mesh_snapshot_and_compaction():
    hosts = make_cluster(f"mshS{time.monotonic_ns()}", snapshot_entries=12)
    try:
        lid = wait_leader(hosts, timeout=60)
        nh = hosts[lid]
        sess = nh.get_noop_session(1)
        for i in range(30):
            propose_retry(nh, sess, f"s{i}=v{i}".encode())
        deadline = time.time() + 15
        node = nh.nodes[1]
        while time.time() < deadline and node.compacted_to == 0:
            time.sleep(0.05)
        assert node.compacted_to > 0
        assert nh.sync_read(1, "s29", timeout_s=10) == "v29"
        idx = nh.sync_request_snapshot(1, timeout_s=10)
        assert idx > 0
    finally:
        close_all(hosts)


def test_mesh_partitioned_leader_deposed():
    """Device-side partition mask (monkey.go:170 on the mesh): cutting the
    leader's host re-elects among the remaining devices; healing rejoins."""
    hosts = make_cluster(f"mshP{time.monotonic_ns()}")
    try:
        lid = wait_leader(hosts, timeout=60)
        propose_retry(hosts[lid], hosts[lid].get_noop_session(1), b"pre=cut")
        hosts[lid].partition_node()
        others = {r: h for r, h in hosts.items() if r != lid}
        new_lid = None
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                new_lid = wait_leader(others, timeout=10)
                if new_lid != lid:
                    break
            except AssertionError:
                pass
        assert new_lid is not None and new_lid != lid
        propose_retry(others[new_lid], others[new_lid].get_noop_session(1),
                      b"during=cut")
        hosts[lid].restore_partitioned_node()
        # healed replica converges
        deadline = time.time() + 30
        while time.time() < deadline:
            if hosts[lid].stale_read(1, "during") == "cut":
                break
            time.sleep(0.05)
        assert hosts[lid].stale_read(1, "during") == "cut"
    finally:
        close_all(hosts)


def test_mesh_single_link_cut_falls_back_to_hub():
    """Round 17 per-LINK cut: severing ONE mesh link (leader <->
    follower) leaves the row serving — traffic for that link leaves the
    device fabric and rides the host hub instead, so the cut follower
    keeps replicating with zero acked loss; healing returns the link to
    the mesh and the hub gate closes behind it."""
    hosts = make_cluster(f"mshL{time.monotonic_ns()}")
    try:
        lid = wait_leader(hosts, timeout=60)
        nh = hosts[lid]
        propose_retry(nh, nh.get_noop_session(1), b"pre=cut")
        deadline = time.time() + 15
        while time.time() < deadline:
            if all(h.stale_read(1, "pre") == "cut" for h in hosts.values()):
                break
            time.sleep(0.05)
        assert all(h.stale_read(1, "pre") == "cut" for h in hosts.values())

        frid = next(r for r in hosts if r != lid)
        eng = nh.mesh_engine
        lnode = eng.by_shard[(1, lid)]
        fnode = eng.by_shard[(1, frid)]
        eng.set_link_hub_served(lnode, frid, True)
        # a link is cut at BOTH endpoints (asymmetric masks could leak
        # one direction across a link the host already re-routed)
        assert eng._dispatch.cut[lnode.lane, frid - 1]
        assert eng._dispatch.cut[fnode.lane, lid - 1]
        # the rows are NOT partitioned: only one link left the mesh
        assert not eng._dispatch.cut[lnode.lane].all()
        assert eng.link_hub_served(lnode, frid)
        assert eng.link_hub_served(fnode, lid)
        # the doctor's carrier classes track the cut: this link is now
        # hub-delivered (both directions), every other link resident
        from dragonboat_tpu import fabric as _fabric
        book = eng._link_class_book(lnode)
        la, fa = book[lid], book[frid]
        classes = _fabric.METER.snapshot()["link_classes"]
        assert classes[f"{la}->{fa}"] == "hub"
        assert classes[f"{fa}->{la}"] == "hub"

        # writes still commit, and the CUT follower still converges —
        # its replication stream now rides the host hub
        propose_retry(nh, nh.get_noop_session(1), b"during=cut")
        deadline = time.time() + 30
        while time.time() < deadline:
            if hosts[frid].stale_read(1, "during") == "cut":
                break
            time.sleep(0.05)
        assert hosts[frid].stale_read(1, "during") == "cut", (
            "cut link did not fall back to the hub")

        eng.set_link_hub_served(lnode, frid, False)
        assert not eng._dispatch.cut[lnode.lane].any()
        assert not eng._dispatch.cut[fnode.lane].any()
        classes = _fabric.METER.snapshot()["link_classes"]
        assert classes[f"{la}->{fa}"] == "resident"
        assert classes[f"{fa}->{la}"] == "resident"
        propose_retry(nh, nh.get_noop_session(1), b"post=heal")
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(h.stale_read(1, "post") == "heal"
                   for h in hosts.values()):
                break
            time.sleep(0.05)
        assert all(h.stale_read(1, "post") == "heal"
                   for h in hosts.values())
    finally:
        close_all(hosts)


def test_mesh_eviction_to_host_engines():
    """Whole-group escalation: after eviction every member continues as a
    host-resident Node on its own NodeHost over the chan transport."""
    hosts = make_cluster(f"mshE{time.monotonic_ns()}")
    try:
        lid = wait_leader(hosts, timeout=60)
        nh = hosts[lid]
        propose_retry(nh, nh.get_noop_session(1), b"pre=evict")
        # wait for the write to reach every replica's SM before evicting
        deadline = time.time() + 10
        while time.time() < deadline:
            if all(h.stale_read(1, "pre") == "evict" for h in hosts.values()):
                break
            time.sleep(0.05)
        eng = nh.mesh_engine
        knode = eng.by_shard[(1, lid)]
        with eng.mu:
            eng._evict(knode, reason="test")
        assert all((1, r) not in eng.by_shard for r in (1, 2, 3))
        for h in hosts.values():
            assert h.nodes[1].peer is not None  # host-resident now
        assert nh.stale_read(1, "pre") == "evict"
        # the group keeps serving over the regular transport
        deadline = time.time() + 40
        ok = False
        while time.time() < deadline and not ok:
            try:
                nh2 = hosts[wait_leader(hosts, timeout=10)]
                nh2.sync_propose(nh2.get_noop_session(1), b"post=evict",
                                 timeout_s=3)
                ok = nh2.sync_read(1, "post", timeout_s=3) == "evict"
            except Exception:
                time.sleep(0.2)
        assert ok
    finally:
        close_all(hosts)


def test_mesh_restart_from_disk(tmp_path):
    """Durable mesh shards: close every host, reopen, rows re-inject from
    tan state with data intact."""
    dirs = {r: str(tmp_path / f"nh{r}") for r in (1, 2, 3)}
    name = f"mshR{time.monotonic_ns()}"
    hosts = make_cluster(name, node_host_dirs=dirs)
    try:
        lid = wait_leader(hosts, timeout=60)
        sess = hosts[lid].get_noop_session(1)
        for i in range(8):
            propose_retry(hosts[lid], sess, f"d{i}=v{i}".encode())
        deadline = time.time() + 10
        while time.time() < deadline:
            if all(h.stale_read(1, "d7") == "v7" for h in hosts.values()):
                break
            time.sleep(0.05)
    finally:
        close_all(hosts)

    hosts = make_cluster(name, node_host_dirs=dirs)
    try:
        lid = wait_leader(hosts, timeout=60)
        deadline = time.time() + 15
        while time.time() < deadline:
            if hosts[lid].stale_read(1, "d7") == "v7":
                break
            time.sleep(0.05)
        for i in range(8):
            assert hosts[lid].stale_read(1, f"d{i}") == f"v{i}", i
        propose_retry(hosts[lid], hosts[lid].get_noop_session(1), b"dz=zz")
        assert hosts[lid].sync_read(1, "dz", timeout_s=10) == "zz"
    finally:
        close_all(hosts)


def test_mesh_group_with_witness_member_escalates_to_host(tmp_path):
    """Witness replicas are never mesh-resident, so a mesh group that
    gains a witness member must leave the mesh (host engines serve
    witnesses); staying would blackhole all witness-bound traffic.

    The mesh is (g=2, r=4) so witness id 4 is INSIDE mesh addressing —
    only the witness-specific guard can evict.  The restart then checks
    the admission-time twin: rebuilding from the durable membership must
    refuse the mesh and fall back host-side."""
    prefix = f"mshW{time.monotonic_ns()}"
    spec = MeshSpec(name=prefix, g_size=2, replicas=4, n_local=2)
    addrs = {i: f"{prefix}-{i}" for i in (1, 2, 3)}
    dirs = {i: str(tmp_path / f"nh{i}") for i in (1, 2, 3)}
    def mk(rid):
        nh = NodeHost(NodeHostConfig(
            raft_address=addrs[rid], rtt_millisecond=5,
            node_host_dir=dirs[rid],
            expert=ExpertConfig(mesh=spec, kernel_log_cap=256,
                                kernel_apply_batch=16,
                                kernel_compaction_overhead=16)))
        nh.start_replica(addrs, False, KVStateMachine, Config(
            shard_id=1, replica_id=rid, election_rtt=10, heartbeat_rtt=2,
            mesh_resident=True))
        return nh
    hosts = {rid: mk(rid) for rid in (1, 2, 3)}
    try:
        lid = wait_leader(hosts, timeout=60)
        nh = hosts[lid]
        assert (1, lid) in nh.mesh_engine.by_shard  # really on the mesh
        propose_retry(nh, nh.get_noop_session(1), b"pre=wit")
        waddr = f"{prefix}-w"
        deadline = time.time() + 30
        while True:
            try:
                nh.sync_request_add_witness(1, 4, waddr, 0, timeout_s=5)
                break
            except (RequestDroppedError, RequestTimeoutError):
                if time.time() > deadline:
                    raise
                time.sleep(0.2)
        deadline = time.time() + 20
        while time.time() < deadline:
            if all((1, r) not in nh.mesh_engine.by_shard for r in (1, 2, 3)):
                break
            time.sleep(0.05)
        assert all((1, r) not in nh.mesh_engine.by_shard for r in (1, 2, 3)), \
            "group with witness member stayed mesh-resident"
        # and it keeps serving from the host engines
        deadline = time.time() + 40
        ok = False
        while time.time() < deadline and not ok:
            try:
                nh2 = hosts[wait_leader(hosts, timeout=10)]
                nh2.sync_propose(nh2.get_noop_session(1), b"post=wit",
                                 timeout_s=3)
                ok = nh2.sync_read(1, "post", timeout_s=3) == "wit"
            except Exception:
                time.sleep(0.2)
        assert ok
    finally:
        close_all(hosts)

    # restart: the durable membership carries the witness.  If the
    # recovered snapshot captured it, admission refuses the mesh
    # outright; otherwise the witness CC replays through the lane apply
    # within the first steps and the update-time guard evicts.  Either
    # way the shard must settle host-side, not stay a mesh blackhole.
    nh3 = mk(1)
    try:
        deadline = time.time() + 20
        while time.time() < deadline:
            if (nh3.mesh_engine is None
                    or (1, 1) not in nh3.mesh_engine.by_shard) \
                    and nh3.nodes[1].peer is not None:
                break
            time.sleep(0.05)
        assert nh3.mesh_engine is None \
            or (1, 1) not in nh3.mesh_engine.by_shard, \
            "witness-bearing group stayed mesh-resident after restart"
        assert nh3.nodes[1].peer is not None  # host-resident
    finally:
        nh3.close()
