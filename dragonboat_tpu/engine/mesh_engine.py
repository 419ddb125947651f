"""MeshEngine — raft groups whose replicas span a multi-chip device mesh.

The reference scales by running one NodeHost per machine and moving every
inter-replica message through its TCP transport (transport.go:86-101,
engine.go:1230-1364).  Here the replicas of a mesh-resident shard are rows
of ONE sharded kernel state over a ``Mesh(('g','r'))``: replica ``i`` of a
group lives on a device along axis ``'r'``, and message exchange is the
``all_gather``+route inside the jitted step (parallel/ici.py) — the
transport seam collapses into an ICI collective while the host keeps the
same serving duties the single-device KernelEngine has:

  - client proposals / ReadIndex staged into StepInput lanes (with
    follower-host proposals forwarded in-engine to the leader row — the
    reference forwards MsgProp through the raft core);
  - ONE batched ``save_raft_state`` fsync per LogDB per step;
  - snapshots, log queries, eviction to host engines as the slow path.

Deployment note: in this process every attached NodeHost drives its own
replicas and ONE shared engine advances the mesh — the in-process form of
a jax multi-host SPMD program where each host owns a slice of the global
mesh.  Payload bytes live in a per-shard mirror shared by the replicas
(the in-process form of payload distribution; the device ring carries
terms, and ``KernelParams.inline_payloads`` carries values for the
device-native RSM).  Partition chaos (monkey.go:170) is a device-side
mask: a cut row neither sends nor receives on the mesh.

Escalation is whole-group: all state is durable through each replica's
LogDB, so on ``needs_host`` (or InstallSnapshot, or a membership the mesh
cannot address) every member is rebuilt as a host-resident pycore Node on
its own NodeHost and the group continues over the regular transport.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
from jax.sharding import Mesh

from dragonboat_tpu import fabric as _fabric
from dragonboat_tpu import raftpb as pb
from dragonboat_tpu import telemetry
from dragonboat_tpu.config import MeshSpec
from dragonboat_tpu.core import params as KP
from dragonboat_tpu.core.kstate import init_state
from dragonboat_tpu.engine.kernel_engine import (
    ADD_SHARD_LOCK_US,
    KernelEngine,
    KernelNode,
    _KERNEL_MTYPES,
    _LaneInit,
)
from dragonboat_tpu.logger import get_logger
from dragonboat_tpu.parallel.ici import IciCluster
from dragonboat_tpu.tracing import monotonic_us

_LOG = get_logger("mesh_engine")

MT = pb.MessageType

# what a mesh engine still moves over the host transport (always on; the
# benchmark's mesh_hub_msgs_per_step reads the family): with every link
# resident and writes sent to leaders all three stay 0, which is what says
# that the fabric, and not the hub, did the work
_HUB_MSGS = telemetry.GLOBAL.counter(
    "engine_mesh_hub_msgs",
    help="messages of a mesh engine's replicas that met the host "
         "transport: sent = kernel-family messages handed to it for cut "
         "or off-mesh links; read_forward = READ_INDEX / READ_INDEX_RESP "
         "forwarded host to host; stray_dropped = hub copies turned away "
         "at the inbound gate because the link is resident",
    labelnames=("way",))
_HUB_SENT = _HUB_MSGS.labels("sent")
_HUB_READ_FORWARD = _HUB_MSGS.labels("read_forward")
_HUB_STRAY_DROPPED = _HUB_MSGS.labels("stray_dropped")
_READ_FORWARDS = frozenset({MT.READ_INDEX, MT.READ_INDEX_RESP})


class MeshEngine(KernelEngine):
    """A KernelEngine whose rows span a device mesh.

    Row layout matches parallel/ici.py block-major addressing: row
    ``((ig * R) + ir) * n_local + n`` is replica ``ir + 1`` of group lane
    ``ig * n_local + n``; a flat ``P(('g','r'))`` sharding then gives
    device ``(ig, ir)`` the rows of its replica slot."""

    def __init__(self, kp: KP.KernelParams, spec: MeshSpec,
                 events=None, fleet_stats_every: int = 10,
                 pipeline_depth: int = 0,
                 health_top_k: int = 8,
                 health_thresholds=None,
                 invariant_probe: bool = True,
                 capacity_watermark_pct: float = 10.0,
                 capacity_budget_bytes: int = 0) -> None:
        devs = jax.devices()
        need = spec.g_size * spec.replicas
        if len(devs) < need:
            raise RuntimeError(
                f"mesh '{spec.name}' needs {need} devices, have {len(devs)}")
        mesh = Mesh(
            np.array(devs[:need]).reshape(spec.g_size, spec.replicas),
            ("g", "r"))
        self.spec = spec
        self.cluster = IciCluster(
            kp=kp, mesh=mesh, replicas=spec.replicas,
            n_local=spec.n_local, num_groups=spec.g_size * spec.n_local)
        total = self.cluster.total_rows
        # read by KernelEngine.__init__ below: hub-fallback deliveries
        # stage slot-exact against route()'s layout (_InboxBuilder)
        self._slot_exact_replicas = spec.replicas
        super().__init__(kp, total, send_message=None, events=events,
                         fleet_stats_every=fleet_stats_every,
                         pipeline_depth=pipeline_depth,
                         health_top_k=health_top_k,
                         health_thresholds=health_thresholds,
                         invariant_probe=invariant_probe,
                         capacity_watermark_pct=capacity_watermark_pct,
                         capacity_budget_bytes=capacity_budget_bytes,
                         label=f"mesh:{spec.name}")
        # replica ids are fixed by the mesh addressing (route() targets
        # rid 1..R); rows keep them even while ABSENT
        rids = np.empty((total,), np.int32)
        for ig in range(spec.g_size):
            for ir in range(spec.replicas):
                lo = (ig * spec.replicas + ir) * spec.n_local
                rids[lo:lo + spec.n_local] = ir + 1
        # (the setter packs it and places it along G)
        self.state = init_state(
            kp, total, replica_id=rids,
            peer_ids=np.zeros((total, kp.num_peers), np.int32))
        # group-lane bookkeeping
        self._lane_of: dict[int, int] = {}            # shard_id -> lane
        # newest membership ccid written to each group's shared peer
        # books (guards against lagging-member rollback)
        self._books_ccid: dict[int, int] = {}
        self._members: dict[int, dict[int, KernelNode]] = {}  # sid -> rid -> n
        self._mirrors: dict[int, dict[int, pb.Entry]] = {}    # sid -> mirror
        self._free_lanes = list(range(self.cluster.num_groups - 1, -1, -1))
        self._free = []   # base's row free-list is unused (rows are fixed)
        # a row no replica is placed in is cut from the mesh: route()
        # addresses rows by position, so an uncut empty row would receive
        # its group's votes and appends and answer them, a member with no
        # LogDB behind it (add_shard heals the row, remove_replica cuts it;
        # tests/test_mesh_cell.py test_empty_mesh_rows_take_no_part)
        self._dispatch.cut[:] = True
        self._refs = 0    # attached NodeHosts (registry lifecycle)

    # -- row addressing ----------------------------------------------------

    def _row(self, lane: int, replica_id: int) -> int:
        R, n_local = self.spec.replicas, self.spec.n_local
        ig, n = divmod(lane, n_local)
        return (ig * R + (replica_id - 1)) * n_local + n

    # -- lane lifecycle ----------------------------------------------------

    def add_shard(self, node: KernelNode, init: _LaneInit) -> None:
        """Place one REPLICA into its mesh row.  The first member of a
        shard allocates the group lane; later members (possibly attached
        by other NodeHosts, possibly after a restart) join it."""
        rids = [rid for rid, _ in init.peers]
        if any(not (1 <= r <= self.spec.replicas) for r in rids) or not (
                1 <= node.replica_id <= self.spec.replicas):
            raise ValueError(
                f"mesh-resident shard {node.shard_id}: replica ids {rids} "
                f"outside mesh addressing 1..{self.spec.replicas}")
        if any(kind == KP.K_WITNESS for _, kind in init.peers):
            # admission-time twin of the update_lane_membership guard: a
            # restart rebuilds init.peers from the durable membership, and
            # a witness member must keep the group on the host engines
            # (its mesh row would be ABSENT — traffic to it vanishes)
            raise ValueError(
                f"mesh-resident shard {node.shard_id}: witness members "
                f"are host-engine only")
        # as the base engine's: reserve the row and queue the replica
        # under the admission lock, which no round holds.  A group lane is
        # held while any replica of the shard is in ``by_shard``; the
        # group's books (``_members``, ``_mirrors``) and the row's place in
        # the mesh are the engine thread's, written when a round takes the
        # admission (``_register``): the row stays cut until the round
        # that injects it
        key = (node.shard_id, node.replica_id)
        t0 = monotonic_us()
        with self._admit_mu:
            ADD_SHARD_LOCK_US.observe(monotonic_us() - t0)
            if key in self.by_shard:
                raise RuntimeError(
                    f"replica {node.replica_id} of shard {node.shard_id} "
                    f"already mesh-resident")
            lane = self._lane_of.get(node.shard_id)
            if lane is None:
                if not self._free_lanes:
                    raise RuntimeError("mesh engine is at capacity")
                lane = self._free_lanes.pop()
                self._lane_of[node.shard_id] = lane
            row = self._row(lane, node.replica_id)
            node.lane = row
            node.engine = self
            self.by_shard[key] = node
            self._admitting[row] = (node, init)

    def _register(self, row: int, node: KernelNode) -> None:
        sid = node.shard_id
        node.mirror = self._mirrors.setdefault(sid, {})   # shared payloads
        self._members.setdefault(sid, {})[node.replica_id] = node
        self.nodes[row] = node
        self._dispatch.set_cut(row, False)
        self._note_link_classes(node)

    def remove_replica(self, node: KernelNode) -> KernelNode | None:
        """Detach one replica (stop_replica / NodeHost.close); the group
        lane lives on for the remaining members, queued ones included."""
        sid = node.shard_id
        with self.mu:
            with self._admit_mu:
                if self.by_shard.pop((sid, node.replica_id), None) is None:
                    return None
                queued = self._admitting.pop(node.lane, None) is not None
                last = not any((sid, r) in self.by_shard
                               for r in range(1, self.spec.replicas + 1))
                if last:
                    lane = self._lane_of.pop(sid, None)
                    if lane is not None:
                        self._free_lanes.append(lane)
            self._removed_nodes.append(node)
            if not queued:
                addr = self._link_class_book(node).get(node.replica_id)
                if addr:
                    _fabric.METER.drop_link_classes(addr)
                self._members.get(sid, {}).pop(node.replica_id, None)
                self.nodes.pop(node.lane, None)
                self._clear_lane(node.lane)
                self._dispatch.set_cut(node.lane, True)  # empty rows are cut
            if last:
                self._members.pop(sid, None)
                self._mirrors.pop(sid, None)
                self._books_ccid.pop(sid, None)
        return node

    def remove_shard(self, shard_id: int) -> KernelNode | None:
        raise NotImplementedError(
            "mesh engine removes per-replica: use remove_replica(node)")

    def _is_registered(self, n: KernelNode) -> bool:
        # identity, for the same reason as the base engine: a deferred
        # retire must not mistake a re-admitted replica for this node
        return self.by_shard.get((n.shard_id, n.replica_id)) is n

    def _mirror_floor(self, n: KernelNode) -> int:
        members = self._members.get(n.shard_id, {}).values()
        return min((m.sm.get_last_applied() for m in members),
                   default=n.sm.get_last_applied())

    # -- fabric link classes ----------------------------------------------

    @staticmethod
    def _link_class_book(node: KernelNode) -> dict:
        """rid -> raft address from the node's own durable membership —
        the same book update_lane_membership reads."""
        m = node.sm.get_membership()
        return {**m.addresses, **m.non_votings, **m.witnesses}

    def _note_link_classes(self, node: KernelNode) -> None:
        """Refresh the fabric meter's carrier class for every co-
        resident link of ``node`` from the live cut mask (resident =
        mesh-carried, hub = cut/partitioned), both directions.  Links
        to absent or off-mesh peers stay unregistered: they are hub
        links by construction and the meter already counts their
        traffic.  Caller holds self.mu; the meter takes only its own
        lock."""
        book = self._link_class_book(node)
        me = book.get(node.replica_id)
        if not me:
            return
        for rid, peer in self._members.get(node.shard_id, {}).items():
            if rid == node.replica_id:
                continue
            them = self._link_class_book(peer).get(rid) or book.get(rid)
            if not them:
                continue
            cls = (_fabric.LINK_CLASS_HUB
                   if bool(self._dispatch.cut[node.lane, rid - 1])
                   else _fabric.LINK_CLASS_RESIDENT)
            _fabric.METER.set_link_class(me, them, cls)
            _fabric.METER.set_link_class(them, me, cls)

    # -- chaos surface -----------------------------------------------------

    def set_partitioned(self, node: KernelNode, cut: bool) -> None:
        """Device-side partition mask for one replica row (every link)."""
        with self.mu:
            if self._is_registered(node):
                self._dispatch.set_cut(node.lane, cut)
                self._note_link_classes(node)

    def set_link_hub_served(self, node: KernelNode, peer_rid: int,
                            cut: bool) -> None:
        """Cut (or heal) ONE mesh link, symmetrically: traffic between
        ``node``'s row and its group peer ``peer_rid`` leaves the mesh
        and rides the host hub — where transport faults (drop/delay)
        apply to it like any other hub traffic.  Both endpoints are
        masked together: hub fallback relies on the peer's sender-side
        mask to emit its half over the host (MeshDispatch.set_link_cut)."""
        if not (1 <= peer_rid <= self.spec.replicas):
            return
        with self.mu:
            if not self._is_registered(node):
                return
            self._dispatch.set_link_cut(node.lane, peer_rid, cut)
            peer = self._members.get(node.shard_id, {}).get(peer_rid)
            if peer is not None:
                self._dispatch.set_link_cut(
                    peer.lane, node.replica_id, cut)
            self._note_link_classes(node)

    def hub_accepts(self, node: KernelNode, m: pb.Message) -> bool:
        """NodeHost inbound gate for a mesh-resident replica: kernel-
        family traffic lands only when the hub is that link's carrier
        (link_hub_served); host-mediated traffic (snapshot streams and
        the like) always lands."""
        if m.type not in _KERNEL_MTYPES:
            return True
        if self.link_hub_served(node, int(m.from_)):
            return True
        _HUB_STRAY_DROPPED.inc()
        return False

    def link_hub_served(self, node: KernelNode, from_rid: int) -> bool:
        """True when the hub must deliver ``from_rid`` -> ``node``: the
        link is cut, or the sender is off-mesh/absent.  Resident links
        return False — the mesh already carried the message, so the hub
        copy (if any) is a stray and the NodeHost drops it."""
        if not (1 <= from_rid <= self.spec.replicas):
            return True
        if self._members.get(node.shard_id, {}).get(from_rid) is None:
            return True
        return bool(self._dispatch.cut[node.lane, from_rid - 1])

    # -- the step ----------------------------------------------------------

    def _make_dispatch(self):
        """The mesh backend (engine/dispatch.py MeshDispatch): donated +
        depth-1-pipelined shard_map dispatch through parallel/ici.py,
        with the carried inbox, pending counter and partition mask owned
        by the backend.  The step loop itself stays KernelEngine's —
        this seam is the ONLY dispatch-level difference."""
        from dragonboat_tpu.engine.dispatch import MeshDispatch

        return MeshDispatch(self.cluster)

    def _link_mask(self, rows):
        # intra-group messages ride the mesh inside the step; the host
        # sends ONLY the hub-fallback traffic of cut links: EXACTLY the
        # messages the mesh exchange masked out (sender-side per-link
        # mask: parallel/ici.py _mask_outgoing reads the same unmasked
        # output fields the download carries).  READ_INDEX forwarding and
        # snapshot streams go through the per-node host path
        return self._dispatch.cut[rows]

    def _witness_snapshot(self, r, i: int, others: list) -> None:
        # a witness peer needing a snapshot CANNOT be served over the
        # mesh (witness replicas are host-resident, their mesh row is
        # absent): host-escalation, not link traffic — the group leaves
        # for the host engines
        if r.view()["s_wit_snap"][i].any() \
                and r.nodes[i] not in r.fallback:
            r.fallback.append(r.nodes[i])

    def _send(self, n: KernelNode, m: pb.Message) -> None:
        # everything a mesh engine hands to the host transport passes here:
        # the hub fallback of cut links and the reads a follower's host
        # forwards (and their answers)
        (_HUB_READ_FORWARD if m.type in _READ_FORWARDS else _HUB_SENT).inc()
        super()._send(n, m)

    def _send_all(self, pairs: list) -> None:
        for n, m in pairs:      # (cut links only: few, and counted above)
            self._send(n, m)

    def _prop_target(self, n: KernelNode):
        """Forward proposals to the group's leader row (any NodeHost is a
        valid entry point, like the reference's MsgProp forwarding). Falls
        back to the proposer's own row when no leader is known — the
        kernel then drops and the client retries."""
        lane_cut = self._dispatch.cut[n.lane]
        if lane_cut.all():
            # a fully partitioned host's proposals must not tunnel
            # through shared memory to the leader row — stage on the cut
            # row, where the kernel drops them (the client sees DROPPED,
            # as it would against the reference's silenced transport)
            return n.lane, n
        lid = n._leader_cache
        if lid and lid != n.replica_id:
            leader = self._members.get(n.shard_id, {}).get(lid)
            # per-link discipline: forwarding IS a proposer->leader send,
            # so a cut link (or a fully cut leader row) blocks it — the
            # proposal stays on the proposer's row, the kernel drops it
            # there and the client retries
            if (leader is not None
                    and not lane_cut[lid - 1]
                    and not self._dispatch.cut[leader.lane].all()):
                return leader.lane, leader
        return n.lane, n

    # -- membership / escalation ------------------------------------------

    def update_lane_membership(self, node: KernelNode) -> None:
        """Refresh the peer books of EVERY row of this group from the RSM
        membership.  A membership the mesh cannot address (ids outside
        1..R, or more members than peer slots) evicts the whole group."""
        m = node.sm.get_membership()
        kp = self.kp
        ids = (list(m.addresses) + list(m.non_votings) + list(m.witnesses))
        if (len(ids) > kp.num_peers
                or any(not (1 <= r <= self.spec.replicas) for r in ids)):
            self._evict(node, reason=f"membership {sorted(ids)} outside "
                                     f"mesh addressing")
            return
        if m.witnesses:
            # witness replicas are never mesh-resident (their row stays
            # ABSENT), so mesh-routed traffic to them would vanish and
            # the ring floor would wait on their match forever — the
            # group serves witnesses from the host engines instead
            self._evict(node, reason="witness member on a mesh group")
            return
        # the applied CC releases THIS replica's one-in-flight gate only
        # (pycore clears pending_config_change per replica at apply) — a
        # lagging follower's apply must not release the leader row's
        # gate while a newer CC is still uncommitted there
        writes = [(node.lane, "pending_cc", False)]
        # shared peer books: members apply the same CCs at different
        # steps, so only the NEWEST applied membership may write them —
        # a lagging member's view would roll the group's books back
        # (config_change_id is monotonic, membership.go ccid)
        last_ccid = self._books_ccid.get(node.shard_id, -1)
        if m.config_change_id >= last_ccid:
            self._books_ccid[node.shard_id] = m.config_change_id
            pids = np.zeros((kp.num_peers,), np.int32)
            kinds = np.zeros((kp.num_peers,), np.int32)
            i = 0
            for rid in sorted(m.addresses):
                pids[i], kinds[i] = rid, KP.K_VOTER
                i += 1
            for rid in sorted(m.non_votings):
                pids[i], kinds[i] = rid, KP.K_NON_VOTING
                i += 1
            for member in list(self._members.get(node.shard_id, {}).values()):
                writes += [(member.lane, "pid", pids),
                           (member.lane, "kind", kinds)]
                self._kind_np[member.lane] = kinds
                self._pid_np[member.lane] = pids
        self._held_cells += writes

    def _evict(self, n: KernelNode, reason: str, carry=None) -> None:
        """Whole-group escalation: every member leaves the mesh and is
        rebuilt host-side by ITS OWN NodeHost; the group continues over
        the regular transport (all state is already durable)."""
        members = list(self._members.get(n.shard_id, {}).values())
        with self._admit_mu:    # and those no round has taken yet
            members += [q[0] for q in self._admitting.values()
                        if q[0].shard_id == n.shard_id]
        if not members:
            return
        _LOG.info("shard %d: leaving the mesh (%s)", n.shard_id, reason)
        for member in members:
            if self.remove_replica(member) is None:
                continue
            cb = getattr(member, "on_evict_cb", None)
            if cb is not None:
                cb(member, (carry or []) if member is n else [])


# ---------------------------------------------------------------------------
# process-wide registry: NodeHosts sharing a MeshSpec.name share one engine
# (the in-process form of hosts jointly executing one SPMD program)
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, MeshEngine] = {}
_REG_MU = threading.Lock()


def attach_mesh_engine(kp: KP.KernelParams, spec: MeshSpec,
                       events=None, fleet_stats_every: int = 10,
                       pipeline_depth: int = 0,
                       health_top_k: int = 8,
                       health_thresholds=None,
                       invariant_probe: bool = True,
                       capacity_watermark_pct: float = 10.0,
                       capacity_budget_bytes: int = 0) -> MeshEngine:
    with _REG_MU:
        eng = _REGISTRY.get(spec.name)
        if eng is None:
            # the first attaching host's pipeline depth wins (the engine
            # is process-wide; geometry/kp mismatches raise below)
            eng = MeshEngine(kp, spec, events=events,
                             fleet_stats_every=fleet_stats_every,
                             pipeline_depth=pipeline_depth,
                             health_top_k=health_top_k,
                             health_thresholds=health_thresholds,
                             invariant_probe=invariant_probe,
                             capacity_watermark_pct=capacity_watermark_pct,
                             capacity_budget_bytes=capacity_budget_bytes)
            _REGISTRY[spec.name] = eng
        else:
            if eng.spec != spec:
                raise RuntimeError(
                    f"mesh '{spec.name}' geometry mismatch: engine has "
                    f"{eng.spec}, caller wants {spec}")
            if eng.kp != kp:
                raise RuntimeError(
                    f"mesh '{spec.name}' kernel params mismatch")
        eng._refs += 1
        return eng


def detach_mesh_engine(eng: MeshEngine) -> None:
    with _REG_MU:
        eng._refs -= 1
        if eng._refs <= 0:
            _REGISTRY.pop(eng.spec.name, None)
            # last host off the mesh: flush an env-armed profiler
            # capture now (KernelEngine.close semantics — the engine is
            # shared, so only full detach may stop it)
            eng.close()
