"""KernelEngine — device-resident shards behind the real client API.

The reference advances each shard with per-shard goroutine work queues
(engine.go:1107-1364: step workers → one batched fsync → send → apply).
Here every device-resident shard is one lane of a batched ``[G]`` kernel
state (core/kernel.py) and ONE jitted vmapped step advances all of them;
the host's job per step is pure marshaling:

  1. drain client/transport queues into ``StepInput`` lanes + ``Inbox``
     slots (payloads stay in a host-side mirror — the device ring holds
     terms only, kstate.py:59);
  2. run the jitted step;
  3. assemble one ``pb.Update`` batch and call ``save_raft_state`` once
     (THE fsync — raftio/logdb.go:78-83), sending Replicates before it
     (thesis §10.2.1, engine.go:1332-1343) and everything else after;
  4. release committed entries to the RSMs, complete request futures,
     and fire events.

Shards escalate out of the kernel (``needs_host``: a peer needs an
InstallSnapshot stream, the ring overflowed, a restore arrived) by
EVICTION: all state is already durable through the shared LogDB, so the
host builds a regular pycore ``Node`` from the persisted state and the
shard continues on the loopback engine.  That is the slow path the
VERDICT's round-1 review found missing — produced but never consumed.

ReadIndex across hosts: a follower-host read forwards a READ_INDEX
message to the leader host (raft.go:1296 leader-forwarding), the leader
feeds it to its kernel lane as a batched-read ctx and answers with
READ_INDEX_RESP — the kernel itself only ever sees leader-local reads.

Pipelining (``pipeline_depth``): at depth 0 each ``step_all`` runs the
serial loop — stage, dispatch, fetch, process — and is the differential
oracle.  At depth 1 the loop is software-pipelined: staging for step N
builds into the ALTERNATE of two staging buffers while the device still
executes step N-1; step N-1's outputs are then retired (its download is
consumed one step late) BEFORE step N is dispatched through the donating
jit entry (core/round.py ``step_donated``) — the retire-before-dispatch
order is the donation contract: dispatch hands the state's buffers to
XLA, so every read of the previous state (the wit-snap compaction floor,
a whole ring row where a save window overflowed) must complete first.
The download itself is an output of the step's program, carried on the
step's ctx: retiring it reads nothing a donation has handed over.

The device boundary (engine/dispatch.py): a round crosses it three times
whatever it carries — one packed upload, one jitted entry, one packed
download of fixed shape — and nothing in a round compiles after the
first.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field, replace as _dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from dragonboat_tpu import capacity as _capacity
from dragonboat_tpu import lifecycle
from dragonboat_tpu import raftpb as pb
from dragonboat_tpu import telemetry
from dragonboat_tpu.tracing import (
    RoundTimer,
    maybe_start_from_env,
    monotonic_us,
    stop_env_trace,
)
from dragonboat_tpu.config import Config
from dragonboat_tpu.core import params as KP
from dragonboat_tpu.core.kernel import step as kernel_step
from dragonboat_tpu.core import router as _router
from dragonboat_tpu.core.kstate import (
    ACTIVE_TRIPLE,
    FLAG_CLASSES,
    ShardState,
    column_value,
    column_views,
    init_state,
    inject_program,
    pack_program,
    resident_program,
    round_columns,
    state_columns,
    view_program,
    write_cells_program,
)
from dragonboat_tpu.core.round import ring_row, state_cell
from dragonboat_tpu.events import EventHub
from dragonboat_tpu.logger import get_logger
from dragonboat_tpu.node import Node, _SnapshotRequest
from dragonboat_tpu.raftio import LeaderInfo
from dragonboat_tpu.request import RequestResultCode
from dragonboat_tpu.statemachine import Result

_LOG = get_logger("engine")

MT = pb.MessageType

# message types a kernel lane consumes directly (core/kernel.py
# _process_family dispatch set)
_KERNEL_MTYPES = frozenset({
    MT.REPLICATE, MT.REPLICATE_RESP, MT.HEARTBEAT, MT.HEARTBEAT_RESP,
    MT.REQUEST_VOTE, MT.REQUEST_VOTE_RESP, MT.REQUEST_PREVOTE,
    MT.REQUEST_PREVOTE_RESP, MT.TIMEOUT_NOW, MT.UNREACHABLE,
    MT.SNAPSHOT_STATUS,
    # a peer's word that it entered quiesce: no raft message, read by the
    # step's quiesce block alone (core/kernel.py 0b), in a heartbeat slot
    MT.QUIESCE,
})

# column per message class in the download's [G, C] activity flags
# (kstate.FLAG_CLASSES order)
_F = {c: i for i, c in enumerate(FLAG_CLASSES)}
_F_RESP, _F_REP, _F_HB, _F_VOTE = _F["resp"], _F["rep"], _F["hb"], _F["vote"]
_F_TIMEOUT, _F_WITSNAP, _F_RTR = _F["timeout_now"], _F["wit_snap"], _F["rtr"]

# admission at the staging boundary, all engines of the process
# (telemetry.GLOBAL, where the round timer's histograms live)
_PROPS_STAGED = telemetry.GLOBAL.counter(
    "engine_props_staged",
    help="proposals (config changes included) given a prop slot")
_PROPS_DEFERRED = telemetry.GLOBAL.counter(
    "engine_props_deferred",
    help="proposals a staging pass put back for want of a prop slot")
_PROP_SLOTS_OFFERED = telemetry.GLOBAL.counter(
    "engine_prop_slots_offered",
    help="prop slots (proposal_cap) of every row that staged at least "
         "one proposal in a round")
# The engine's logical clock: one tick a step (the kernel ticks a lane at
# most once a step), and no faster than the engine's own recent rounds
# take (``KernelEngine._tick_floor_us``: it follows the longest recent
# rounds, decays by a sixteenth a round, and is at most this cap).  A tick
# stands for a message round trip (``rtt_millisecond``): election and
# check-quorum windows are ~10 ticks and a heartbeat is due every 1-2,
# which holds only while a tick is no shorter than a real round trip
# between hosts, and a round trip through two engines' step loops takes
# several of their rounds.  While a round cost 120-290 ms whatever it
# carried, the step rate itself kept the clock that slow on every engine
# alike; with rounds of 20-140 ms an engine between bursts of work ran its
# clock 5-10x faster than a loaded one's: its followers deposed live
# leaders, and its leaders flooded the slower engines with heartbeats
# (PERF.md section 6, PR 26).
_MAX_TICK_FLOOR_US = 100_000

_SAVE_WINDOW_OVERFLOW = telemetry.GLOBAL.counter(
    "engine_save_window_overflow",
    help="lanes whose save window was wider than the download's S ring "
         "entries and took the whole-ring-row fetch (none expected)")
_READ_STAGE_WAIT_US = telemetry.GLOBAL.histogram(
    "read_stage_wait_us",
    help="one observation per ReadIndex context staged: its wait for "
         "the staging, from the enqueue of its batch's first read (a "
         "forwarded one: from its arrival at this host)")
# lane admission, beside nodehost_start_replica_us: the caller's wait for
# the admission lock in add_shard (no round holds it; part of
# start_replica's ``stage``), one _flush_injections batch on the engine
# thread (inside a round's ``stage``) and the replicas it wrote
ADD_SHARD_LOCK_US = telemetry.GLOBAL.histogram(
    "engine_add_shard_lock_us",
    help="add_shard's wait for the admission lock, per call")
_INJECT_BATCH = 8       # least rows of one flush's program
_CELL_BATCH = 16        # least cells of one write_cells_program


def _cell_batch(rows, cols, values) -> np.ndarray:
    """The ``[3, N] int32`` host array ``write_cells_program`` takes: cell
    ``i`` is ``(rows[i], cols[i], values[i])``; the batch is padded with
    copies of its last cell to a power of two, so the program compiles once
    per size class."""
    n = len(rows)
    size = max(_CELL_BATCH, 1 << (n - 1).bit_length())
    cells = np.empty((3, size), np.int32)
    cells[:, :n] = (rows, cols, values)
    cells[:, n:] = cells[:, n - 1:n]
    return cells
_INJECT_FLUSH_US = telemetry.GLOBAL.histogram(
    "engine_inject_flush_us",
    help="one batch of queued lane injections written into the device "
         "state, on the engine thread")
_INJECT_ROWS = telemetry.GLOBAL.counter(
    "engine_inject_rows",
    help="replicas written into the device state by _flush_injections "
         "(engine_inject_flush_us counts the batches)")
# the width of a round, beside the round timer's histograms: per
# committed round the lanes _process_outputs retired (its candidate rows;
# the round record holds the lanes step_all staged too)
_LANES_PROCESSED = telemetry.GLOBAL.counter(
    "engine_round_lanes",
    help="lanes of committed rounds that the output pass processed (its "
         "candidate rows)",
    labelnames=("what",)).labels("processed")
# how the output pass retired them: by columns of the download, or through
# the per-lane handler of a rare class (a witness snapshot, a ReadIndex
# completion or drop, a config change, an escalation, a save window wider
# than the download's)
_RETIRE_LANES = telemetry.GLOBAL.counter(
    "engine_retire_lanes",
    help="lanes the output pass processed, by the path a round took "
         "them: columnar, or per_lane where a rare class's handler ran",
    labelnames=("path",))
_RETIRED_COLUMNAR = _RETIRE_LANES.labels("columnar")
_RETIRED_PER_LANE = _RETIRE_LANES.labels("per_lane")
# and who named them: the download's ``active`` column, or only the host
# (a row that staged proposals and came back with nothing else to do)
_RETIRE_NAMED = telemetry.GLOBAL.counter(
    "engine_retire_named",
    help="lanes the output pass processed, by who named them: device "
         "(the download's active column) or host (staged rows the column "
         "left out); a falling device share says the host guesses again",
    labelnames=("by",))
_NAMED_BY_DEVICE = _RETIRE_NAMED.labels("device")
_NAMED_BY_HOST = _RETIRE_NAMED.labels("host")
# what the kernel's quiesce did, read off the fleet digest (every
# ``fleet_stats_every`` rounds; the counts are the digest's, the growths
# are since the engine's last one)
_FLEET_LANES = telemetry.GLOBAL.counter(
    "engine_fleet_lanes",
    help="at every fleet digest, the lanes it counted: occupied, and of "
         "them quiesced (a window's ratio is the share of held lanes "
         "that were asleep while it ran)",
    labelnames=("what",))
# the rows an engine's programs run over, whatever they hold: the step,
# the round's upload and download and the sweeps are priced by it (an
# engine states its own under its label and takes it back when it closes)
_ENGINE_LANES = telemetry.GLOBAL.gauge(
    "engine_lanes",
    help="lanes of an engine's batched state by what they are: capacity "
         "(the rows every device program of that engine runs over)",
    labelnames=("what", "engine"))
# what a round's reset of its staging array costs follows this, not the
# capacity: the rows the round before it wrote (PR 43)
_SWEPT_ROWS = telemetry.GLOBAL.counter(
    "engine_round_swept_rows",
    help="rows of its staging array an engine's rounds zeroed as they "
         "began: the rows the rounds before them wrote (staged lanes and "
         "forwarded proposals' target rows), whatever the engine holds",
    labelnames=("engine",))
_FLEET_OCCUPIED = _FLEET_LANES.labels("occupied")
_FLEET_QUIESCED = _FLEET_LANES.labels("quiesced")
_QUIESCE_WAKES = telemetry.GLOBAL.counter(
    "engine_quiesce_wakes",
    help="lanes that left quiesce: at every fleet digest, the growth of "
         "the resident quiesce_epoch column summed over occupied lanes")
_QUIESCE_ENTERS = telemetry.GLOBAL.counter(
    "engine_quiesce_enters",
    help="lanes a fleet digest newly counted quiesced, by how they "
         "entered: on their own idle clock, or on a peer's word (a lane "
         "that entered and woke between two digests is not counted)",
    labelnames=("how",))
_ENTERED_OWN_CLOCK = _QUIESCE_ENTERS.labels("own_clock")
_ENTERED_ON_WORD = _QUIESCE_ENTERS.labels("peer")


class _RoundDown:
    """Host view of rows of a round's packed download (kstate.py's column
    table): ``o["term"][g]``, ``o["prop_index"][g, slot]``,
    ``o["s_ent_term"][g, p, j]`` read a [N, Wd] int32 host array (the
    download, or rows gathered from it), each field with its StepOutput
    shape (a bool field is compared ``!= 0`` once); ``o["flags"]`` is the
    [N, C] activity matrix and ``o["save_terms"][g, j]`` the term of ring
    entry ``save_first[g] + j``.  Nothing here touches the device.  The
    output pass reads lists (``_Retiring``); this is what the handlers of
    its rare classes read."""

    __slots__ = ("_host", "_cols", "_np")

    def __init__(self, host: np.ndarray, cols: dict) -> None:
        self._host = host
        self._cols = cols           # field -> kstate.Column
        self._np: dict[str, np.ndarray] = {}

    def __getitem__(self, f: str) -> np.ndarray:
        v = self._np.get(f)
        if v is None:
            v = self._np[f] = column_value(self._cols[f], self._host)
        return v


class _Retiring:
    """The candidate rows of the round being retired, read ONCE: one gather
    of those rows from the download and one ``tolist`` make ``cells``,
    plain lists of Python ints, and everything the pass does to a lane
    indexes ``cells[i]`` at a column's offset (``KernelEngine._at``): row
    ``i`` is lane ``lanes[i]``'s and ``nodes[i]``'s.  A numpy call a field
    or a class costs more than its work: each may let the interpreter go,
    and beside other runnable threads the engine thread then waits for it
    back (PERF.md section 6, PR 32).  ``view()`` is the ``_RoundDown`` of
    the same rows for the rare classes' handlers; ``per_lane`` collects
    the rows such a handler took, ``fallback`` the nodes whose witness
    snapshot has to go the eviction way."""

    __slots__ = ("lanes", "nodes", "cells", "per_lane", "fallback",
                 "_rows", "_cols", "_view")

    def __init__(self, lanes: list, nodes: list, host: np.ndarray,
                 cols: dict) -> None:
        self.lanes = lanes
        self.nodes = nodes
        self._rows = host[lanes]
        self._cols = cols
        self._view: _RoundDown | None = None
        self.cells: list = self._rows.tolist()
        self.per_lane: set[int] = set()
        self.fallback: list = []

    def view(self) -> _RoundDown:
        if self._view is None:
            self._view = _RoundDown(self._rows, self._cols)
        return self._view


# what the output pass builds by the hundred a round is built with every
# field given: a default factory of ``pb.Message`` / ``pb.Update`` makes
# an empty Snapshot (2.7 us of a message's 4.5), UpdateCommit or
# LogQueryResult and throws it away.  All three are frozen.
_NO_SNAPSHOT = pb.Snapshot()
_NO_COMMIT = pb.UpdateCommit()
_NO_LOG_QUERY = pb.LogQueryResult()
_MT_OF = {int(t): t for t in pb.MessageType}


def _message(mtype, to: int, n, term: int = 0, log_term: int = 0,
             log_index: int = 0, commit: int = 0, reject: bool = False,
             hint: int = 0, hint_high: int = 0, entries: tuple = ()):
    """A message of node ``n``'s lane (positional: ``pb.Message``'s
    field order)."""
    return pb.Message(mtype, to, n.replica_id, n.shard_id, term, log_term,
                      log_index, commit, reject, hint, hint_high, entries,
                      _NO_SNAPSHOT)


def _update(n, state: pb.State, entries: list):
    """What node ``n``'s lane persists this round (positional:
    ``pb.Update``'s field order)."""
    return pb.Update(n.shard_id, n.replica_id, state, False, tuple(entries),
                     (), False, _NO_SNAPSHOT, (), (), 0, _NO_COMMIT, (), (),
                     _NO_LOG_QUERY, None)


def _entry_at(e: pb.Entry, index: int, term: int) -> pb.Entry:
    """``e`` at ``index`` and ``term`` (the constructor:
    ``dataclasses.replace`` takes half as long again)."""
    return pb.Entry(term, index, e.type, e.key, e.client_id, e.series_id,
                    e.responded_to, e.cmd)


def _replicate_entries(mirror: dict, prev: int, terms: list,
                       witness: bool) -> tuple:
    """The entries after ``prev`` a REPLICATE carries, with the terms the
    kernel read from its ring: payloads from the mirror, and for a
    witness peer none (raft.go:770 makeMetadataEntries; config changes
    ship in full)."""
    entries = []
    idx = prev
    for term in terms:
        idx += 1
        e = mirror.get(idx)
        if e is None:
            e = pb.Entry(term, idx)
        elif e.term != term:
            e = _entry_at(e, e.index, term)
        if witness and not e.is_config_change():
            e = pb.Entry(term, idx, pb.EntryType.METADATA)
        entries.append(e)
    return tuple(entries)


@dataclass
class _StepCtx:
    """Everything the deferred output pass of ONE dispatched step needs,
    captured at dispatch time: staging for the NEXT step rebinds
    ``n._staged_props`` / ``n._staged_ri`` before a pipelined step's
    outputs are retired, so fates and read ctxs must ride the ctx, not
    the node."""

    nodes: dict[int, "KernelNode"]
    fates: dict[int, list]                  # row -> [(entry, origin), ...]
    staged_ri: dict[int, pb.SystemCtx]      # row -> staged ReadIndex ctx
    staged_rows: set[int]
    out: object = None                      # packed download, on device (async)
    dead: set[int] = field(default_factory=set)   # rows removed in flight
    # rows placed at a term > 0 since the step before (``_injected``)
    injected: set[int] = field(default_factory=set)
    # lifecycle-sampled proposal keys riding this step (dispatch/retire
    # stamps); keys of rows scrubbed in flight stay here harmlessly —
    # stamp() is a no-op once the book's dropped() scrubbed the span
    traced: list = field(default_factory=list)


class KernelNode(Node):
    """A device-resident shard: client surface + books + RSM live on the
    host exactly like ``Node``; the raft state machine lives in a kernel
    lane and is advanced by the owning ``KernelEngine``."""

    engine_driven = True

    def __init__(self, *args, **kw) -> None:
        super().__init__(*args, **kw)
        self.lane: int = -1
        self.engine: KernelEngine | None = None
        # set (under self.mu) when the shard is evicted: every later
        # ingress mutation is redirected to the host-resident successor
        self._moved: Node | None = None
        # payload mirror: log index -> full pb.Entry (device holds terms).
        # On a mesh engine all replicas of a shard share one dict (the
        # in-process form of payload distribution).
        self.mirror: dict[int, pb.Entry] = {}
        # (entry, origin_node) staged into prop lanes this step, by slot —
        # origin tracks whose books own the future (mesh engines forward
        # follower-host proposals onto the leader row)
        self._staged_props: list[tuple[pb.Entry, "KernelNode"]] = []
        self._staged_ri: pb.SystemCtx | None = None
        # remote ReadIndex ctxs forwarded from follower hosts, FIFO
        # (sender, ctx, arrival at this host on tracing.monotonic_us)
        self._remote_reads: list[tuple[int, pb.SystemCtx, int]] = []
        # ctx.low -> requesting replica, for remote reads riding the
        # quorum path (answered when the rtr lane lands, steps later)
        self._remote_ri_inflight: dict[int, int] = {}
        self._local_ri_pending: dict[int, pb.SystemCtx] = {}
        self._tick_pending = 0
        self._leader_cache = 0
        self._leader_term_cache = 0
        self._staged_ri_from = 0
        self._committed_cache = 0
        self.applied_since_snapshot = 0

    # the engine drives everything; the loopback step must not touch peer
    def step(self) -> bool:  # pragma: no cover - engine-driven
        return False

    def _post(self, mutate) -> None:
        """Ingress choke point: after eviction, redirect atomically to the
        successor Node so nothing lands in a dead queue (the drain in
        _on_kernel_evict runs under self.mu after _moved is set).  Every
        ingress dirties the lane so the engine's staging pass visits it
        (mark_dirty is lock-free — taking engine.mu here would invert
        the step path's engine.mu -> node.mu order)."""
        with self.mu:
            if self._moved is None:
                mutate(self)
                eng, lane = self.engine, self.lane
                if eng is not None and lane >= 0:
                    eng.mark_dirty(lane)
                return
            target = self._moved
        target._post(mutate)

    def leader_id(self) -> int:
        return self._leader_cache

    def node_term(self) -> int:
        return self._leader_term_cache

    def is_leader(self) -> bool:
        return self._leader_cache == self.replica_id

    def read(self, timeout_ticks: int):
        """Reads enqueue into the book WITHOUT the _post choke point
        (no node-state mutation), so the lane must be dirtied here or
        the staging pass would never pick the batch up — before the
        engine-wide tick broadcast (r5), the per-tick dirty-marking of
        every lane masked this."""
        rs = super().read(timeout_ticks)
        eng, lane = self.engine, self.lane
        if eng is not None and lane >= 0:
            eng.mark_dirty(lane)
        return rs

    def tick(self) -> None:
        """Direct per-lane tick (tests / pre-injection): the NodeHost
        ticker never calls this for engine-registered lanes — it hands
        the whole round to the engine as one pending broadcast
        (KernelEngine.tick_round)."""
        self._tick_pending += 1
        eng, lane = self.engine, self.lane
        if eng is not None and lane >= 0:
            eng.mark_dirty(lane)
        if self._owns_clock:
            self._clock.advance()
        self.gc_books()

    def _take_snapshot(self, req: _SnapshotRequest) -> None:
        """Snapshot for a device-resident shard: the device compacts its
        term ring itself (kernel.py device-side compaction), so the host
        only persists the RSM image + snapshot record and truncates the
        durable log (node.go:739 doSave without the logreader cache)."""
        import os as _os

        from dragonboat_tpu.raftio import EntryInfo, SnapshotInfo  # noqa: F401

        index0 = self.sm.get_last_applied()
        if index0 == 0:
            if req.key:
                self.pending_snapshot.done(req.key,
                                           RequestResultCode.REJECTED)
            return
        path = req.path if req.exported else self._snapshot_path(index0)
        self.fs.makedirs(_os.path.dirname(path) or ".")
        index, term, membership, files = \
            self.sm.save_snapshot_with_files(path)
        ss = pb.Snapshot(
            filepath=path, file_size=self.fs.getsize(path),
            index=index, term=term, membership=membership,
            shard_id=self.shard_id, type=self.sm.sm_type, files=files,
        )
        if req.exported:
            from dragonboat_tpu.tools import write_export_metadata

            write_export_metadata(path, ss, fs=self.fs)
        else:
            self.logdb.save_snapshots([pb.Update(
                shard_id=self.shard_id, replica_id=self.replica_id,
                snapshot=ss)])
            self.events.snapshot_created(SnapshotInfo(
                shard_id=self.shard_id, replica_id=self.replica_id,
                from_=self.replica_id, index=index, term=term))
            overhead = (req.compaction_overhead if req.override_compaction
                        else self.cfg.compaction_overhead)
            compact_to = max(0, index - overhead)
            if compact_to > 0 and not self.cfg.disable_auto_compaction:
                self.logdb.remove_entries_to(
                    self.shard_id, self.replica_id, compact_to)
                self.compacted_to = compact_to
                self.events.log_compacted(EntryInfo(
                    shard_id=self.shard_id, replica_id=self.replica_id,
                    index=compact_to))
        self.applied_since_snapshot = 0
        if req.key:
            self.pending_snapshot.done(
                req.key, RequestResultCode.COMPLETED, snapshot_index=index)

    def _on_config_change_applied(self, entry: pb.Entry, r) -> None:
        """CC apply for a lane: the RSM's membership store is the truth
        and the engine refreshes the device peer book after the apply
        batch; there is no pycore Peer to notify."""
        cc = pb.decode_config_change(entry.cmd)
        if not r.rejected:
            self.membership_changed_cb(cc)
        code = (RequestResultCode.REJECTED if r.rejected
                else RequestResultCode.COMPLETED)
        self.pending_config_change.done(
            entry.key, code, Result(value=entry.index))


@dataclass
class _LaneInit:
    """State captured from a bootstrapped pycore Peer for lane injection."""

    term: int
    vote: int
    committed: int
    applied: int
    snap_index: int
    snap_term: int
    entries: list[pb.Entry]
    peers: list[tuple[int, int]]   # (replica_id, kind)


class KernelEngine:
    """Owns one batched kernel state and every KernelNode mapped onto it."""

    # class-wide: serializes the FIRST jit compile across engines (see
    # step_all; concurrent engine-thread compiles segfaulted XLA:CPU)
    _first_compile_mu = threading.Lock()

    def __init__(self, kp: KP.KernelParams, capacity: int,
                 send_message, events: EventHub | None = None,
                 election_rtt: int = 10, heartbeat_rtt: int = 1,
                 fleet_stats_every: int = 10,
                 pipeline_depth: int = 0,
                 health_top_k: int = 8,
                 health_thresholds=None,
                 invariant_probe: bool = True,
                 capacity_watermark_pct: float = 10.0,
                 capacity_budget_bytes: int = 0,
                 label: str = "") -> None:
        self.kp = kp
        self.capacity = capacity
        self.label = label or f"engine-{id(self):x}"
        _ENGINE_LANES.labels("capacity", self.label).set(capacity)
        self._swept_rows = _SWEPT_ROWS.labels(self.label)
        self.send_message = send_message
        self.events = events or EventHub()
        self.mu = threading.RLock()
        self.nodes: dict[int, KernelNode] = {}     # lane -> node
        self.by_shard: dict[int, KernelNode] = {}
        self._free = list(range(capacity - 1, -1, -1))
        # lanes with possibly-pending host work (see mark_dirty); its
        # own tiny lock — NOT engine.mu (ingress holds node.mu and the
        # documented order is engine.mu -> node.mu)
        self._dirty: set[int] = set()
        self._dirty_mu = threading.Lock()
        # the applied cursor each lane's device state has been sent: the
        # device gates campaigns and compaction on it, so a lane whose RSM
        # has applied further is work for a round even when nothing else is
        self._applied_sent_np = np.zeros((capacity,), np.int64)
        # rows that received staged proposals this step (bounds the
        # fate-reset and fate-processing loops)
        self._staged_rows: set[int] = set()
        # nodes removed since the last step (same-thread evictions during
        # staging land here); step_all drains it instead of sweeping all
        # [capacity] rows for vanished registrations
        self._removed_nodes: list[KernelNode] = []
        # first-call guard for the cross-engine compile serialization in
        # step_all (the class-wide _first_compile_mu)
        self._compiled_once = False
        # host mirrors of the device peer books: pids/kinds only change
        # on injection/membership updates, so the output path must not
        # pay a device->host transfer for them every step
        self._kind_np = np.zeros((capacity, kp.num_peers), np.int32)
        self._pid_np = np.zeros((capacity, kp.num_peers), np.int32)
        # admission, in two steps.  ``add_shard`` reserves a lane and
        # queues the replica under ``_admit_mu``, a lock NO round holds
        # (lane -> (node, init, the call's start)); it guards the free
        # list, ``by_shard`` and this queue, and is taken after ``mu``
        # where both are held.  ``step_all`` takes the queue as it begins:
        # only then does a round see the node (``self.nodes``), and that
        # same round injects it
        self._admit_mu = threading.Lock()
        self._admitting: dict[int, tuple] = {}
        # taken admissions awaiting this step's batched injection
        # (lane -> (node, init, pids, kinds)); see _flush_injections
        self._pending_inject: dict[int, tuple] = {}
        # rows written into the state at a term > 0 (a founder's bootstrap
        # term is 1; a replica that starts again has the term it saved)
        # since the last dispatch: candidates of that step's output pass
        # whatever its ``active`` column says, for the leader edge
        # (0 -> term) that no step moves
        self._injected: set[int] = set()
        self._inject_fn = None      # inject_rows jitted for this state
        # peer-book writes (``update_lane_membership``) of the rows one
        # ``_finish`` retires, held for ONE upload and one program behind
        # its loop: 4,096 groups elected at once apply their bootstrap
        # config changes in a few rounds, and a program, an upload and two
        # arrays let go of for each replica was most of those rounds
        self._held_cells: list = []
        # whole-engine tick rounds queued by the host ticker; each step
        # consumes ONE round as a vectorized [G]-bool broadcast (the
        # per-lane Python tick walk was ~25 s/round at 100k lanes).
        # Capped so a long no-node idle cannot bank a burst of rounds
        # that would fast-forward election timers on the first admission
        self._tick_rounds_pending = 0
        self._last_tick_us = 0
        self._tick_floor_us = 0     # see _MAX_TICK_FLOOR_US
        self._round_t0_us = 0       # start of the pass being timed for it
        self._tick_mu = threading.Lock()
        # persistent staging buffers, zeroed per step (the jitted step
        # needs fixed [capacity] shapes anyway; reallocating every engine
        # iteration would cost ~G*K*E ints of fresh numpy per step).
        # A slot is a pair (``_RoundStaging``: one for the rounds no tick
        # is due in, one for the tick rounds), and at pipeline depth 1
        # there are TWO slots: staging for step N writes the alternate
        # one while step N-1 (whose device upload may alias its numpy
        # staging on CPU backends) is still in flight; a buffer is only
        # rewritten after the step that used it has retired
        # mesh subclasses set _slot_exact_replicas BEFORE super().__init__
        # so hub-fallback staging lands at route()'s exact slot layout
        mesh_r = getattr(self, "_slot_exact_replicas", None)
        # both crossings' layouts at this geometry (kstate.py's table)
        self._cols = round_columns(kp)
        self._down_cols = {c.field: c for c in self._cols.down}
        # where each field of the download starts in a row (the output
        # pass reads a candidate row as a list, at these offsets)
        self._at = {c.field: c.start for c in self._cols.down}
        # software pipeline: 0 = serial oracle (stage, dispatch, fetch,
        # process in one pass), 1 = retire step N-1 while N is staged,
        # dispatching N through the donating jit entry
        self.pipeline_depth = max(0, min(1, int(pipeline_depth)))
        self._bufs = tuple(
            tuple(_RoundStaging(kp, capacity, mesh_replicas=mesh_r)
                  for _tick_due in (False, True))
            for _ in range(self.pipeline_depth + 1))
        self._buf_idx = 0
        # aliases to the builders of the most recent dispatch (fleet
        # stats and tests read the staged inbox through these)
        self._inbox_buf = self._bufs[0][0].inbox
        self._input_buf = self._bufs[0][0].inp
        self._pending_ctx: _StepCtx | None = None
        # pipeline occupancy accounting: a dispatch is "overlapped" when
        # a previous step was still unretired at its staging
        self._pipe_steps = 0
        self._pipe_overlapped = 0
        # the round timer (tracing.RoundTimer; ``label`` names this
        # engine in its records and annotations: the owning host's id) +
        # opt-in jax.profiler capture
        self._round = RoundTimer(self.events.metrics, "engine.kernel_step",
                                 engine=self.label)
        # staging counts of the round being staged (the round's record),
        # and the lanes its output pass took into the per-lane loops
        self._props_staged = 0
        self._props_deferred = 0
        self._reads_staged = 0
        self._lanes_processed = 0
        # what one ``_finish`` spent applying and acknowledging (ns; the
        # timer's ``finish.apply`` and ``finish.ack``)
        self._apply_ns = self._ack_ns = 0
        maybe_start_from_env()
        self.events.metrics.set("engine.pipeline.depth", self.pipeline_depth)
        # the decimated collection (core/digest.py): every N steps ONE
        # jitted program over the resident state runs the fleet statistics
        # (core/fleet.py), the anomaly classification (core/health.py;
        # health_top_k=0 leaves it out) and the protocol-invariant probe
        # (core/invariants.py, the runtime leg of the safety verifier;
        # invariant_probe off leaves it out), and ONE flat int32 vector
        # crosses to the host; 0 disables.  Their per-group digests stay
        # device resident between collections as ONE [G, 17] array
        self.fleet_stats_every = max(0, int(fleet_stats_every))
        self._fleet_countdown = self.fleet_stats_every
        self._digest = None             # built lazily, at the first tick
        #: (in, out) device arrays of the collection's program, from its
        #: first call (like the dispatch backend's ``entry_arrays``)
        self.digest_arrays: tuple[int, int] | None = None
        self.last_fleet: dict | None = None
        # standalone engines (no NodeHost) still expose the device-only
        # view; a NodeHost registers its merged host+device view over the
        # same names FIRST in its __init__, so this is a no-op there
        from dragonboat_tpu.core import fleet as _fleet

        _fleet.register_exposition(self.events.metrics.registry,
                                   lambda: self.last_fleet)
        from dragonboat_tpu.core import health as _health

        self.health_top_k = max(0, int(health_top_k))
        self.health_thresholds = (
            _health.HealthThresholds(*health_thresholds)
            if health_thresholds is not None
            else _health.DEFAULT_THRESHOLDS)
        self.last_health: dict | None = None
        self._health_seq = 0            # health ticks taken (flight stamp)
        _health.register_exposition(self.events.metrics.registry,
                                    lambda: self.last_health)
        # A violation is ALWAYS a bug, so sightings are sticky
        # (violations_seen) — a transient step-scope violation must not
        # vanish from /healthz at the next clean window
        from dragonboat_tpu.core import invariants as _invariants

        self.invariant_probe = bool(invariant_probe)
        self.last_invariants: dict | None = None
        self._inv_seq = 0               # probe ticks taken (flight stamp)
        self._inv_violations_seen = 0   # sticky cumulative violation total
        # lanes injected/cleared since the last probe tick: their digest
        # prev-columns describe a DIFFERENT occupant, so the probe must
        # re-seed them (ticks=0) or a fresh shard's lower term would
        # read as a bogus term_monotone violation
        self._inv_dirty: set[int] = set()
        _invariants.register_exposition(self.events.metrics.registry,
                                        lambda: self.last_invariants)
        # capacity rail (dragonboat_tpu/capacity.py): compile telemetry
        # wrappers around every jit entry this engine dispatches, plus
        # decimated device-memory accounting on the fleet cadence
        from dragonboat_tpu import capacity as _capacity

        self.capacity_watermark_pct = float(capacity_watermark_pct)
        self.capacity_budget_bytes = max(0, int(capacity_budget_bytes))
        # the ONE dispatch backend (engine/dispatch.py): subclasses pick
        # a backend through the _make_dispatch seam instead of overriding
        # step-loop internals — the engine-unity lint pass enforces it
        self._dispatch = self._make_dispatch()
        # the device state between rounds, in its resident form (kstate.py
        # ResidentState: three arrays, placed by the backend); ``state``
        # below is the ShardState view of it for callers outside a round.
        # All lanes start ABSENT: no peers -> non-single, no campaigns
        # (a lane with kind all K_ABSENT and tick never set is inert)
        self._state_cols = {c.field: c for c in state_columns(kp)[0]}
        self.state = init_state(
            kp, capacity,
            replica_id=np.ones((capacity,), np.int32),
            peer_ids=np.zeros((capacity, kp.num_peers), np.int32),
            election_timeout=election_rtt,
            heartbeat_timeout=heartbeat_rtt,
        )
        self._cap_entries = self._capacity_entries()
        self.last_capacity: dict | None = None
        self._capacity_seq = 0          # capacity ticks (flight stamp)
        self._capacity_peak = 0         # high-water live tree bytes
        _capacity.register_exposition(self.events.metrics.registry,
                                      lambda: self.last_capacity)

    # -- lane lifecycle ---------------------------------------------------

    def add_shard(self, node: KernelNode, init: _LaneInit) -> None:
        """Admit a bootstrapped shard: reserve a free lane and queue the
        replica for the next round, without waiting for the round that
        may be running (a round holds ``mu`` from end to end; 768
        admissions each waited one out).  A concurrent step must never
        run between a node becoming visible to a round and its injection
        (it would write back a stepped pre-injection state, clobbering
        the lane): ``step_all`` makes the node visible itself
        (``_take_admissions``) and injects it before it stages anything.
        What arrives for the node meanwhile waits in its own queues; the
        take dirties the lane, so the injecting round stages it."""
        t0 = monotonic_us()
        with self._admit_mu:
            ADD_SHARD_LOCK_US.observe(monotonic_us() - t0)
            if not self._free:
                raise RuntimeError(
                    f"kernel engine is at capacity ({self.capacity} "
                    "lanes, ExpertConfig.kernel_capacity)")
            lane = self._free.pop()
            node.lane = lane
            node.engine = self
            self.by_shard[node.shard_id] = node
            self._admitting[lane] = (node, init)

    def _take_admissions(self) -> None:
        """On the engine thread, holding ``mu``, as a round begins: every
        admission queued since the last one becomes visible to this round
        and is queued for its one ``inject_rows`` program."""
        if not self._admitting:
            return
        with self._admit_mu:
            taken, self._admitting = self._admitting, {}
        for lane, (node, init) in taken.items():
            self._register(lane, node)
            self._inject(lane, node, init)

    def _register(self, lane: int, node: KernelNode) -> None:
        """Make an admitted node visible to rounds (a seam: the mesh
        engine also joins it to its group and heals its row)."""
        self.nodes[lane] = node

    def remove_shard(self, shard_id: int) -> KernelNode | None:
        with self.mu:
            with self._admit_mu:
                node = self.by_shard.pop(shard_id, None)
                if node is None:
                    return None
                queued = self._admitting.pop(node.lane, None) is not None
                self._free.append(node.lane)
            if not queued:
                # (an admission no round took was never written anywhere)
                self.nodes.pop(node.lane, None)
                self._clear_lane(node.lane)
            self._removed_nodes.append(node)
        return node

    def close(self) -> None:
        """Engine teardown.  Flushes a DRAGONBOAT_TPU_TRACE_DIR-armed
        profiler capture while the JAX backend is unambiguously alive —
        relying on atexit for it races interpreter/backend shutdown and
        can leave the trace dir empty (a user-started ``start_trace``
        capture is deliberately left to its owner)."""
        _ENGINE_LANES.labels("capacity", self.label).set(0)
        stop_env_trace()

    @property
    def state(self) -> ShardState:
        """The device state as a ShardState of device arrays, unpacked on
        demand by one jitted program and placed like the resident arrays
        (``state.lt`` / ``.lcc`` / ``.lv`` ARE the resident rings).  For
        callers outside a round: device checks, differentials, tests.
        Nothing inside ``step_all`` reads it (a ShardState is 45 arrays to
        let go of; tests/test_round_budget.py holds that)."""
        r = self._resident
        view = view_program(self.kp, self._dispatch.placement())(r.cols)
        return view._replace(lt=r.lt, lcc=r.lcc, lv=r.lv)

    @state.setter
    def state(self, s: ShardState) -> None:
        self._resident = pack_program(
            self.kp, self._dispatch.placement())(s)

    def _write_cells(self, writes, tag: str) -> None:
        """Set ``field[lane] = value`` on the device for every ``(lane,
        field, value)`` of ``writes`` (a [G] or [G, P] field of the
        packed columns), by ONE small jitted program and one upload
        (``_cell_batch``)."""
        r, c, v = [], [], []
        for lane, field, value in writes:
            col = self._state_cols[field]
            vals = np.broadcast_to(np.asarray(value, np.int32), col.shape)
            r += [lane] * col.width
            c += range(col.start, col.start + col.width)
            v += vals.ravel().tolist()
        with _capacity.METER.sanctioned(tag) as crossing:
            up = jnp.asarray(_cell_batch(r, c, v))
            crossing.moved(up)
        res = self._resident
        self._resident = res._replace(cols=write_cells_program(
            self._dispatch.placement())(res.cols, up))

    def _write_held_cells(self) -> None:
        """The peer-book writes a ``_finish`` held back, as ONE batch.  A
        cell's last write wins, as it did at one program a replica (a mesh
        group's members write its shared books in turn); a lane vacated
        since (an eviction clears it at once) is left alone."""
        held, self._held_cells = self._held_cells, []
        last = {(lane, field): (lane, field, value)
                for lane, field, value in held if lane in self.nodes}
        if last:
            self._write_cells(last.values(), "membership_up")

    def _inject(self, lane: int, node: KernelNode, init: _LaneInit
                ) -> None:
        """Queue one lane injection; this ``step_all`` flushes every
        queued lane in ONE vectorized state update.  The eager form was
        ~30 full-[capacity] array copies PER admission — O(n·capacity)
        total, the first structure to fall over at 100k groups.  Host
        bookkeeping (kind cache, payload mirror) is done here so non-state
        readers see the shard immediately."""
        kp = self.kp
        pids = np.zeros((kp.num_peers,), np.int32)
        kinds = np.zeros((kp.num_peers,), np.int32)
        for i, (rid, kind) in enumerate(init.peers[:kp.num_peers]):
            pids[i], kinds[i] = rid, kind
        self._kind_np[lane] = kinds
        self._pid_np[lane] = pids
        for e in init.entries:
            node.mirror[e.index] = e
        self._applied_sent_np[lane] = init.applied
        self._pending_inject[lane] = (node, init, pids, kinds)
        self._inv_dirty.add(lane)
        self.mark_dirty(lane)

    def _flush_injections(self) -> None:
        """Every admission queued since the last step, written into the
        state by ONE jitted program (kstate.py ``inject_rows``): O(capacity + n)
        instead of O(n·capacity), and one dispatch instead of one per
        state field."""
        if not self._pending_inject:
            return
        t0 = monotonic_us()
        kp = self.kp
        items = sorted(self._pending_inject.items())
        self._pending_inject = {}
        n = len(items)
        lanes_np = np.array([g for g, _ in items], np.int32)
        rows = {k: np.zeros((n,), np.int32) for k in (
            "replica_id", "seed", "rand_timeout", "e_timeout", "h_timeout",
            "role", "term", "vote", "applied", "snap_index", "snap_term",
            "last", "committed")}
        rows.update({k: np.zeros((n,), bool) for k in (
            "check_quorum", "pre_vote", "quiesce_on")})
        rows["pid"] = np.zeros((n, kp.num_peers), np.int32)
        rows["kind"] = np.zeros((n, kp.num_peers), np.int32)
        rows["lt"] = np.zeros((n, kp.log_cap), np.int32)
        rows["lcc"] = np.zeros((n, kp.log_cap), bool)
        for j, (lane, (node, init, pids, kinds)) in enumerate(items):
            rows["pid"][j], rows["kind"][j] = pids, kinds
            for e in init.entries:
                rows["lt"][j, e.index & (kp.log_cap - 1)] = e.term
                rows["lcc"][j, e.index & (kp.log_cap - 1)] = \
                    e.is_config_change()
            last = init.entries[-1].index if init.entries \
                else init.snap_index
            role = KP.FOLLOWER
            my_kind = dict(init.peers).get(node.replica_id, KP.K_VOTER)
            if my_kind == KP.K_NON_VOTING:
                role = KP.NON_VOTING
            elif my_kind == KP.K_WITNESS:
                role = KP.WITNESS
            cfg = node.cfg
            # per-(shard, replica) PRNG stream: lanes injected on
            # different hosts must NOT share election-timeout sequences
            # or symmetric campaigns livelock (randomizedElectionTimeout,
            # raft.go:659)
            seed = int(KP.splitmix32(
                (node.shard_id * 2654435761 + node.replica_id * 40503)
                & 0xFFFFFFFF)) & 0x7FFFFFFF
            rows["replica_id"][j] = node.replica_id
            rows["seed"][j] = seed
            rows["rand_timeout"][j] = KP.randomized_timeout(
                seed, 0, cfg.election_rtt)
            rows["e_timeout"][j] = cfg.election_rtt
            rows["h_timeout"][j] = max(1, cfg.heartbeat_rtt)
            rows["check_quorum"][j] = cfg.check_quorum
            rows["pre_vote"][j] = cfg.pre_vote
            rows["quiesce_on"][j] = cfg.quiesce
            rows["role"][j] = role
            rows["term"][j] = init.term
            rows["vote"][j] = init.vote
            rows["applied"][j] = init.applied
            rows["snap_index"][j] = init.snap_index
            rows["snap_term"][j] = init.snap_term
            rows["last"][j] = last
            rows["committed"][j] = init.committed
            if init.term:
                self._injected.add(lane)
        # The batch is padded with copies of its last lane (a copy writes
        # the same values to the same row) to a power of two and at least
        # _INJECT_BATCH, so the program compiles once per size class and an
        # engine that admits a few lanes at a time has one.  (45 eager
        # scatters took 0.5-1 s a batch, and every admission waited for
        # one under the engine's lock.)
        size = max(_INJECT_BATCH, 1 << (n - 1).bit_length())
        if size > n:
            lanes_np = np.concatenate(
                [lanes_np, np.repeat(lanes_np[-1:], size - n)])
            rows = {k: np.concatenate(
                        [v, np.repeat(v[-1:], size - n, axis=0)])
                    for k, v in rows.items()}
        if self._inject_fn is None:
            # the rows come back placed as the backend keeps its state (a
            # mesh engine's stay sharded as its serve entry was compiled)
            self._inject_fn = _capacity.TRACKER.wrap(
                "inject_rows", inject_program(
                    self.kp, self._dispatch.placement()))
        with _capacity.METER.sanctioned("inject_up") as crossing:
            crossing.moved(lanes_np, *rows.values())
            self._resident = self._inject_fn(
                self._resident, jnp.asarray(lanes_np),
                {k: jnp.asarray(v) for k, v in rows.items()})
        _INJECT_FLUSH_US.observe(monotonic_us() - t0)
        _INJECT_ROWS.inc(n)

    def _clear_lane(self, lane: int) -> None:
        self._inv_dirty.add(lane)
        self._injected.discard(lane)
        if self._pending_inject.pop(lane, None) is not None:
            # evicted before its injection ever flushed: the lane state
            # was never written, so there is nothing to clear on device
            self._kind_np[lane] = KP.K_ABSENT
            self._pid_np[lane] = 0
            return
        self._write_cells((
            (lane, "kind", KP.K_ABSENT), (lane, "pid", 0),
            (lane, "needs_host", False),
            # a vacated lane must not linger in the fleet quiesced count
            (lane, "quiesce_on", False), (lane, "quiesced", False),
        ), "lane_clear_up")
        self._kind_np[lane] = KP.K_ABSENT
        self._pid_np[lane] = 0

    def update_lane_membership(self, node: KernelNode) -> None:
        """Re-derive the lane's peer book from the RSM membership (host
        applies config changes; the device book follows).  A membership
        larger than the fixed [P] peer book cannot be modeled on device —
        quorum over a truncated book would be unsafe — so the shard is
        evicted to the host engine instead.  The book's cells are held
        (``_held_cells``) and go up behind the ``_finish`` that applied the
        change, with those of every other row it retired."""
        m = node.sm.get_membership()
        kp = self.kp
        total = len(m.addresses) + len(m.non_votings) + len(m.witnesses)
        if total > kp.num_peers:
            self._evict(node, reason=f"membership {total} > "
                                     f"kernel peer book {kp.num_peers}")
            return
        pids = np.zeros((kp.num_peers,), np.int32)
        kinds = np.zeros((kp.num_peers,), np.int32)
        i = 0
        for rid in sorted(m.addresses):
            if i < kp.num_peers:
                pids[i], kinds[i] = rid, KP.K_VOTER
                i += 1
        for rid in sorted(m.non_votings):
            if i < kp.num_peers:
                pids[i], kinds[i] = rid, KP.K_NON_VOTING
                i += 1
        for rid in sorted(m.witnesses):
            if i < kp.num_peers:
                pids[i], kinds[i] = rid, KP.K_WITNESS
                i += 1
        g = node.lane
        self._held_cells += (
            (g, "pid", pids), (g, "kind", kinds),
            # the applied CC releases the one-in-flight gate (pycore
            # add_node/add_non_voting/... clear pending_config_change on
            # apply; without this a lane accepts exactly ONE config
            # change in its lifetime and drops every later one)
            (g, "pending_cc", False),
        )
        self._kind_np[g] = kinds
        self._pid_np[g] = pids

    # -- the step ---------------------------------------------------------

    def tick_round(self) -> None:
        """Queue one tick round for EVERY registered lane (called once
        per host tick interval; consumed in step_all as one vectorized
        broadcast)."""
        with self._tick_mu:
            if self._tick_rounds_pending < 8:
                self._tick_rounds_pending += 1

    def mark_dirty(self, lane: int) -> None:
        """Flag a lane for the next staging pass.  Guarded by its own
        lock rather than engine.mu (ingress already holds node.mu, and
        the step path's order is engine.mu -> node.mu): a bare set.add
        could land in a set the step thread just swapped out and be
        silently dropped."""
        with self._dirty_mu:
            self._dirty.add(lane)

    def step_all(self) -> bool:
        """One engine iteration; returns True if any lane had work
        (messages, ticks, proposals, reads) or an in-flight pipelined
        step was retired.  Only DIRTY lanes stage — the full-scan form
        cost 16 µs/lane of Python per step (1.6 s at 100k lanes) whether
        or not anything was pending.  Runs under the engine lock: lane
        injection/eviction and the device state update must not
        interleave with a step.

        Pipeline order at depth 1 (every part of it is load-bearing):
        (1) stage step N into the alternate buffer pair — host marshaling
        overlaps the device compute of step N-1; (2) retire step N-1's
        deferred outputs — this is the first point the host blocks on
        the device, and it must run BEFORE (3) dispatches step N with
        donated buffers, because retiring reads previous-state leaves
        (lt rows, the wit-snap floor) that donation hands to XLA."""
        # the round timer (tracing.RoundTimer): ``stage`` starts once the
        # lock is held; a pass that returns False, or raises, leaves the
        # block with its round uncommitted and records nothing
        with self.mu, self._round as rt:
            self._round_t0_us = monotonic_us()
            self._props_staged = self._props_deferred = 0
            self._reads_staged = self._lanes_processed = 0
            self._take_admissions()
            nodes = dict(self.nodes)
            if not nodes:
                if self._pending_ctx is not None:
                    # every lane vanished with a step in flight: fail the
                    # removed nodes' staged futures, then retire the step
                    # so nothing hangs on an answer that cannot land
                    removed, self._removed_nodes = self._removed_nodes, []
                    for n in removed:
                        if not self._is_registered(n):
                            self._scrub_pending_ctx(n)
                            self._drop_staged_fates(n)
                    ctx, self._pending_ctx = self._pending_ctx, None
                    with rt.within("kernel_engine.process_outputs"):
                        self._process_outputs(ctx)
                    self._commit_round(0, ())
                    return True
                return False
            # between ticks the worker's passes mostly find nothing: leave
            # before the staging buffer is cleared (an ingress that lands
            # now has its own wake-up behind it)
            tick_due = (self._tick_rounds_pending > 0
                        and self._round_t0_us - self._last_tick_us
                        >= self._tick_floor_us)
            if not (tick_due or self._dirty or self._pending_inject
                    or self._removed_nodes or self._pending_ctx is not None
                    or self._device_pending()):
                return False
            self._flush_injections()
            staging = self._bufs[self._buf_idx][tick_due]
            inbox, inp = staging.inbox, staging.inp
            self._inbox_buf, self._input_buf = inbox, inp
            with rt.part("stage.reset"):
                self._swept_rows.inc(staging.reset())
            had_work = False

            # swap out the dirty set; arrivals during this step land in
            # the fresh set and stage next iteration
            with self._dirty_mu:
                dirty, self._dirty = self._dirty, set()
            staged = [(g, nodes[g]) for g in sorted(dirty) if g in nodes]
            # staging may target OTHER rows' prop slots (mesh engines
            # forward follower-host proposals to the leader row); only
            # rows recorded as prop targets can hold stale fates.  The
            # pending ctx (if any) captured the OLD list objects, so the
            # rebind here cannot lose in-flight fates
            self._slot_cursor: dict[int, int] = {}
            for g in self._staged_rows:
                n = nodes.get(g)
                if n is not None:
                    n._staged_props = []
            self._staged_rows = set()
            for g, n in staged:
                if self._stage_lane(g, n, inbox, inp):
                    had_work = True
            # consume one queued engine-wide tick round: every
            # registered lane ticks via ONE vectorized bool write —
            # no per-lane Python, no dirty-marking the whole batch
            # (no faster than the tick floor: _MAX_TICK_FLOOR_US)
            tick_round = tick_due
            if tick_round:
                with self._tick_mu:
                    self._tick_rounds_pending -= 1
                self._last_tick_us = monotonic_us()
            if tick_round:
                with rt.part("stage.tick"):
                    staging.tick_all(nodes)
                had_work = True
            # an eviction while staging (InstallSnapshot; whole-GROUP on a
            # mesh engine) may remove rows staged EARLIER in this loop —
            # drop them, failing any proposals forwarded onto them so the
            # origin futures fail fast instead of timing out.  Removals
            # are drained from the explicit log remove_shard keeps (the
            # full [capacity] registration sweep this replaces was a fixed
            # ~16 µs/lane of Python per step at 100k lanes).  An in-flight
            # pipelined step is scrubbed FIRST: its captured fates are the
            # removed node's un-reset lists, and the scrub empties them so
            # _drop_staged_fates cannot fail the same futures twice
            removed, self._removed_nodes = self._removed_nodes, []
            for n in removed:
                if self._is_registered(n):
                    continue  # re-admitted since removal
                self._scrub_pending_ctx(n)
                self._drop_staged_fates(n)
                if nodes.get(n.lane) is n:
                    nodes.pop(n.lane)
            if not (had_work or self._device_pending()):
                if self._pending_ctx is not None:
                    # nothing new to dispatch — drain the pipeline: the
                    # in-flight step's outputs still owe applies, futures
                    # and events, and retiring re-dirties its lanes so
                    # follow-on work stages next iteration
                    ctx, self._pending_ctx = self._pending_ctx, None
                    with rt.within("kernel_engine.process_outputs"):
                        self._process_outputs(ctx)
                    self._commit_round(len(staged), ())
                    return True
                return False

            ctx = _StepCtx(
                nodes=nodes,
                fates={g: nodes[g]._staged_props
                       for g in self._staged_rows if g in nodes},
                staged_ri={g: n._staged_ri for g, n in staged
                           if n._staged_ri is not None},
                staged_rows=set(self._staged_rows),
                injected=self._injected,
            )
            self._injected = set()
            if lifecycle.TRACER.enabled:
                ctx.traced = [e.key for fl in ctx.fates.values()
                              for e, _origin in fl
                              if e.key and lifecycle.TRACER.sampled(e.key)]
            # ``stage`` runs on to the upload; ``within`` ends its
            # annotation before either of the two older ones opens
            overlapped = self._pending_ctx is not None
            if overlapped:
                # retire step N-1 BEFORE the donating dispatch of N
                pending, self._pending_ctx = self._pending_ctx, None
                with rt.within("kernel_engine.process_outputs"):
                    self._process_outputs(pending)
            with rt.within("kernel_engine.step"):
                rt.enter("upload")
                if not self._compiled_once:
                    # serialize FIRST calls across engines (incl. the
                    # mesh override): concurrent jit compiles from
                    # several engine threads have segfaulted XLA:CPU
                    # (2026-07-31); once the executable is cached the
                    # lock is never touched again
                    with KernelEngine._first_compile_mu:
                        resident, out = self._kernel_call(staging)
                    self._compiled_once = True
                else:
                    resident, out = self._kernel_call(staging)
            # the previous resident arrays die here, three of them: each
            # one let go is a wait for the interpreter (kstate.py)
            with rt.part("upload.release"):
                self._resident = resident
            ctx.out = out
            with rt.part("upload.applied"):
                sent, applied = self._applied_sent_np, inp._applied
                for g in staging.rows:
                    if applied[g] > sent[g]:
                        sent[g] = applied[g]
            for k in ctx.traced:
                lifecycle.TRACER.stamp(k, lifecycle.STAGE_DISPATCH)
            self._pipe_steps += 1
            if self.pipeline_depth > 0:
                # defer the fetch: the outputs are consumed one step
                # late, overlapping device step N+1 with this retire
                self._pending_ctx = ctx
                self._buf_idx ^= 1
                if overlapped:
                    self._pipe_overlapped += 1
                m = self.events.metrics
                m.inc("engine.pipeline.steps")
                if overlapped:
                    m.inc("engine.pipeline.overlapped")
                m.set("engine.pipeline.occupancy_pct",
                      100 * self._pipe_overlapped
                      // max(1, self._pipe_steps))
            else:
                with rt.within("kernel_engine.process_outputs"):
                    self._process_outputs(ctx)
            if self.fleet_stats_every > 0:
                self._fleet_countdown -= 1
                if self._fleet_countdown <= 0:
                    self._fleet_countdown = self.fleet_stats_every
                    rt.enter("finish")
                    with rt.part("finish.collect"):
                        self._collect_digest()
                        self._collect_capacity()
            self._commit_round(len(staged), ctx.traced)
            return True

    def _commit_round(self, lanes_staged: int, keys) -> None:
        """Close the round timer's round: the staging counts and the
        sampled lifecycle keys the round dispatched (``ctx.traced``; none
        where it only retired a step) ride its record, which is how a
        write's span names its round."""
        # the tick floor: up towards this round's length by at most a
        # doubling (a lone long round, a compile or a stalled flush, moves
        # it little), down by a sixteenth
        floor = self._tick_floor_us
        self._tick_floor_us = min(
            _MAX_TICK_FLOOR_US,
            max(min(monotonic_us() - self._round_t0_us, 2 * floor + 5_000),
                floor * 15 // 16))
        _LANES_PROCESSED.inc(self._lanes_processed)
        self._round.commit(
            props_staged=self._props_staged,
            props_deferred=self._props_deferred,
            reads_staged=self._reads_staged,
            lanes_staged=lanes_staged,
            lanes_processed=self._lanes_processed,
            lanes_held=len(self.nodes),
            lanes_quiesced=(self.last_fleet or {}).get("quiesced", 0),
            keys=list(keys))

    def _is_registered(self, n: KernelNode) -> bool:
        # identity, not membership: with a deferred (pipelined) output
        # pass the same shard id can be re-admitted as a NEW node while
        # the old one's step is still in flight
        return self.by_shard.get(n.shard_id) is n

    @staticmethod
    def _fail_fates(fates) -> None:
        for entry, origin in fates:
            if entry.is_config_change():
                origin.pending_config_change.done(
                    entry.key, RequestResultCode.DROPPED)
            else:
                origin._rl_release(entry.key)
                origin.pending_proposals.dropped(entry.key)

    def _drop_staged_fates(self, n: KernelNode) -> None:
        self._fail_fates(n._staged_props)
        n._staged_props = []

    def _scrub_pending_ctx(self, n: KernelNode) -> None:
        """Remove a dead node's rows from the in-flight step ctx: fail
        its staged-proposal futures now (the retire pass will skip the
        row) rather than letting them time out against a node whose
        books no longer exist."""
        ctx = self._pending_ctx
        if ctx is None or ctx.nodes.get(n.lane) is not n:
            return
        fates = ctx.fates.pop(n.lane, None)
        if fates:
            if n._staged_props is fates:
                n._staged_props = []
            self._fail_fates(fates)
        ctx.staged_ri.pop(n.lane, None)
        ctx.dead.add(n.lane)

    def _make_dispatch(self):
        """Dispatch-backend factory — the sanctioned seam an engine
        subclass uses to change WHERE the step runs (serial jit vs the
        parallel/ici.py shard_map path) without growing a second step
        loop.  Called once at the end of __init__."""
        from dragonboat_tpu.engine.dispatch import SerialDispatch

        # bind THIS module's global at construction: chaos tests swap
        # kernel_step for a mutated kernel here
        return SerialDispatch(self.kp, kernel_step)

    def _device_pending(self) -> bool:
        """True while the dispatch backend carries undelivered messages
        between steps (the mesh backend's device-resident inbox); the
        serial backend re-stages from host queues and never does."""
        return self._dispatch.pending()

    def _fleet_inbox_from(self):
        """[G, K] sender ids feeding the inbox-occupancy histogram, for
        callers outside a round (a lane's health row, the chaos oracle):
        the backend picks the host-staged builder or its carried box."""
        return self._dispatch.inbox_from(self._inbox_buf)

    def _make_digest(self):
        """Fresh all-zero ``[G, 17]`` carry of the collection (the health
        digest's ten columns and the invariant digest's seven,
        core/digest.py) at the engine's lane geometry, placed by the
        dispatch backend (the mesh backend shards it along G like the
        state it derives from)."""
        from dragonboat_tpu.core import digest as _digest

        return self._dispatch.shard(_digest.empty_carry(self.capacity))

    def _digest_views(self):
        """``(HealthDigest, InvariantDigest)`` views of the carried array's
        columns, unpacked on demand by one jitted program: for readers
        outside a round.  Nothing inside ``step_all`` reads them (17
        arrays to let go of)."""
        from dragonboat_tpu.core import digest as _digest

        with self.mu:
            if self._digest is None:
                self._digest = self._make_digest()
            return _digest.carry_view_program(
                self._dispatch.placement())(self._digest)

    @property
    def _health_digest(self):
        return self._digest_views()[0]

    @property
    def _inv_digest(self):
        return self._digest_views()[1]

    def _collect_digest(self) -> None:
        """The decimated collection (core/digest.py): ONE jitted program
        over the resident state runs the fleet statistics, the anomaly
        classification (where ``health_top_k`` > 0) and the invariant
        probe (where ``invariant_probe``); ONE flat int32 vector is
        fetched and read by one ``tolist``; the per-group digests stay on
        the device as ONE carried array the program rewrites.  The sender
        ids go in once: the serial backend's host array as one upload,
        the mesh backend's carried box sliced inside the program.  Runs
        under engine.mu right after a step, so the state it reads is
        exactly the state the step produced, and blocks for its download,
        so a reader of ``last_*`` sees this round's.  Lanes whose occupant
        changed since the last collection have the invariant digest's age
        zeroed first (one column of the carried array, by the program a
        lane's clearing runs), so step-scoped invariants never compare
        across occupants."""
        from dragonboat_tpu.core import digest as _digest

        with _capacity.METER.sanctioned("digest_down") as crossing:
            carry = self._digest if self._digest is not None \
                else self._make_digest()
            if self.invariant_probe and self._inv_dirty:
                lanes = sorted(self._inv_dirty)
                self._inv_dirty.clear()
                cells = jnp.asarray(_cell_batch(
                    lanes, [_digest.INV_TICKS_COL] * len(lanes),
                    [0] * len(lanes)))
                crossing.moved(cells)
                carry = write_cells_program(self._dispatch.placement())(
                    carry, cells)
            inbox = self._dispatch.digest_inbox(self._inbox_buf)
            if "Inbox" not in self._dispatch.resident_classes():
                crossing.moved(inbox)       # host-staged: it went up
            args = (self._resident, inbox, carry)
            res = self._cap_entries["fleet_digest"](*args)
            if self.digest_arrays is None:
                self.digest_arrays = (len(jax.tree.leaves(args)),
                                      len(jax.tree.leaves(res)))
            vec, self._digest = res
            crossing.moved(vec)
            ints = np.asarray(vec).tolist()
        fleet, health, invariants = _digest.decode(
            ints, self.capacity, self.health_top_k, self.invariant_probe)
        self._note_fleet(fleet)
        if health is not None:
            self._note_health(health)
        if invariants is not None:
            self._note_invariants(invariants)

    def _note_fleet(self, now: dict) -> None:
        """``last_fleet`` and the counters fed from it."""
        was, self.last_fleet = self.last_fleet or {}, now
        _FLEET_OCCUPIED.inc(now["occupied"])
        _FLEET_QUIESCED.inc(now["quiesced"])

        def tallies(d):
            on_word = d.get("quiesced_by_word", 0)
            return (d.get("quiesce_wakes", 0), on_word,
                    d.get("quiesced", 0) - on_word)

        # the growth since this engine's last digest; a lane vacated
        # takes its counts with it: never count down
        for counter, count, before in zip(
                (_QUIESCE_WAKES, _ENTERED_ON_WORD, _ENTERED_OWN_CLOCK),
                tallies(now), tallies(was)):
            counter.inc(max(0, count - before))

    def _note_health(self, cur: dict) -> None:
        """``last_health``; class-count edges (0 -> nonzero and back) are
        recorded as flight-recorder anomaly_raised/anomaly_cleared events
        stamped with the engine's health-tick sequence — never the wall
        clock."""
        from dragonboat_tpu import flight

        prev = self.last_health
        self._health_seq += 1
        self.last_health = cur
        prev_counts = prev["class_count"] if prev else {}
        for cls, n in cur["class_count"].items():
            was = prev_counts.get(cls, 0)
            if n > 0 and was == 0:
                flight.record(flight.ANOMALY_RAISED, cls=cls, count=n,
                              tick=self._health_seq)
            elif n == 0 and was > 0:
                flight.record(flight.ANOMALY_CLEARED, cls=cls,
                              tick=self._health_seq)

    def _note_invariants(self, cur: dict) -> None:
        """``last_invariants``; a 0 -> nonzero violation edge is recorded
        as an ``invariant_violation`` flight event stamped with the
        probe-tick sequence — never the wall clock."""
        from dragonboat_tpu import flight

        prev = self.last_invariants
        self._inv_seq += 1
        self._inv_violations_seen += cur["total"]
        cur["violations_seen"] = self._inv_violations_seen
        self.last_invariants = cur
        was = prev["total"] if prev else 0
        if cur["total"] > 0 and was == 0:
            first = cur["first"] or {}
            flight.record(flight.INVARIANT_VIOLATION,
                          total=cur["total"],
                          lane=first.get("lane", -1),
                          invariants=first.get("invariants", []),
                          tick=self._inv_seq)

    def _capacity_entries(self) -> dict:
        """Compile-telemetry wrappers for every jit entry this engine
        dispatches: the backend's step entries (serial step/step_donated
        or the mesh serve pair) plus the collection's one program.
        Each engine wraps independently (own counters): a first compile
        at THIS engine's geometry is never mistaken for a retrace of
        another engine sharing the same jitted function."""
        from dragonboat_tpu import capacity as _capacity
        from dragonboat_tpu.core import digest as _digest

        # the collection takes the resident form and unpacks it inside
        # its own program (core/digest.py digest_program): one for this
        # engine's thresholds, top-K and which of its parts are on; a
        # backend that carries its Inbox hands that over (``digest_inbox``)
        # and the program slices the sender ids out
        entries = dict(self._dispatch.entries)
        entries["fleet_digest"] = _capacity.TRACKER.wrap(
            "fleet_digest", _digest.digest_program(
                self.kp, self.health_thresholds, self.health_top_k,
                self.invariant_probe,
                "Inbox" in self._dispatch.resident_classes(),
                self._dispatch.placement()))
        return entries

    def _capacity_trees(self) -> tuple:
        """Device-resident trees this engine keeps alive between steps
        (the mesh backend adds its carried inbox)."""
        return (self._resident, self._digest) \
            + self._dispatch.resident_trees()

    def _capacity_model_classes(self) -> tuple:
        """Contract classes resident on device for this engine's
        geometry: the serial backend re-stages its inbox from host each
        step, so only state + the carried digests (one [G, 17] array,
        both classes' columns) persist; the mesh backend carries its
        Inbox."""
        return ("ShardState", "HealthDigest", "InvariantDigest") \
            + self._dispatch.resident_classes()

    def _collect_capacity(self) -> None:
        """Decimated capacity accounting, riding the fleet cadence under
        the same engine.mu post-step window: live bytes of the resident
        trees (shape-derived — no device sync), allocator stats where
        the backend reports them, the contracts capacity model at this
        geometry (a constant, walked once a process:
        capacity.resident_bytes_per_group), and the compile counters.
        The memory_pressure
        watermark crossing is recorded as an edge-triggered flight event
        stamped with the capacity tick — never the wall clock."""
        from dragonboat_tpu import capacity as _capacity
        from dragonboat_tpu import flight

        live = _capacity.measure_tree_bytes(*self._capacity_trees())
        self._capacity_seq += 1
        self._capacity_peak = max(self._capacity_peak, live)
        prev = self.last_capacity
        cur = _capacity.engine_snapshot(
            self.kp, self.capacity, live, self._capacity_peak,
            {name: w.stats() for name, w in self._cap_entries.items()},
            budget_bytes=self.capacity_budget_bytes,
            watermark_pct=self.capacity_watermark_pct,
            ticks=self._capacity_seq,
            classes=self._capacity_model_classes())
        self.last_capacity = cur
        was = bool(prev and prev["memory_pressure"])
        if cur["memory_pressure"] and not was:
            flight.record(flight.MEMORY_PRESSURE,
                          bytes_in_use=cur["bytes_in_use"],
                          budget_bytes=cur["budget_bytes"],
                          headroom_pct=cur["headroom_pct"],
                          tick=self._capacity_seq)

    def health_row(self, lane: int) -> dict:
        """One lane's drill-down row (NodeHost.shard_info): an O(1)
        dynamic_index fetch of device scalars — the full ShardState is
        never materialized on host."""
        from dragonboat_tpu.core import health as _health

        with self.mu, _capacity.METER.sanctioned("health_row") as crossing:
            row = resident_program(
                self.kp, _health.shard_row, ("thresholds",))(
                self._resident, self._fleet_inbox_from(),
                self._health_digest, np.int32(lane),
                thresholds=self.health_thresholds)
            crossing.moved(*jax.tree.leaves(row))
            return _health.row_to_dict(row)

    def _kernel_call(self, staging: _RoundStaging):
        # the round's device work, one upload and one jitted entry:
        # -> (state, the packed download, still on the device).
        # depth > 0 routes through the backend's donating entry: XLA
        # reuses the state's buffers in place of per-step fresh
        # allocations.  After a donating dispatch the host must not read
        # the passed-in state again — step_all's retire-before-dispatch
        # order upholds that on BOTH backends
        return self._dispatch.dispatch(
            self._resident, staging, donate=self.pipeline_depth > 0)

    # -- staging ----------------------------------------------------------

    def _stage_lane(self, g: int, n: KernelNode, inbox: _InboxBuilder,
                    inp: _InputBuilder) -> bool:
        work = False
        with n.mu:
            msgs, n.incoming_msgs = n.incoming_msgs, []
            props, n.incoming_proposals = n.incoming_proposals, []
            cc_entry, n.config_change_entry = n.config_change_entry, None
            transfer, n.transfer_target = n.transfer_target, None
            ss_req, n.snapshot_request = n.snapshot_request, None
            lq, n.log_query_range = n.log_query_range, None
            compact_key, n.compaction_request_key = (
                n.compaction_request_key, None)
            ticks, n._tick_pending = n._tick_pending, 0
            # sticky transfer lease: the kernel aborts an armed transfer
            # at its next check-quorum round (core/kernel.py abort_tr),
            # which under apply backpressure fires before the transferee
            # can catch up — a one-shot staging then loses the request
            # forever.  Re-arm every step while the transfer future is
            # live; the re-arm is a no-op while ltt is set, and the book
            # timeout (pending_transfer.gc) bounds the lease
            if transfer is None and n._transfer_awaiting is not None:
                if n.pending_transfer.outstanding is not None:
                    transfer = n._transfer_awaiting[0]
                else:
                    n._transfer_awaiting = None    # timed out: lease over

        if len(msgs) > 1:
            msgs = _newest_heartbeats(msgs)

        # an InstallSnapshot forces eviction — restore everything drained
        # so the successor Node inherits it intact
        if any(m.type == MT.INSTALL_SNAPSHOT for m in msgs):
            with n.mu:
                n.incoming_msgs = (
                    [m for m in msgs if m.type != MT.INSTALL_SNAPSHOT]
                    + n.incoming_msgs)
                n.incoming_proposals = props + n.incoming_proposals
                n.config_change_entry = n.config_change_entry or cc_entry
                n.transfer_target = n.transfer_target or transfer
                n.snapshot_request = n.snapshot_request or ss_req
                n.log_query_range = n.log_query_range or lq
                n.compaction_request_key = (n.compaction_request_key
                                            or compact_key)
            self._evict(n, reason="install-snapshot",
                        carry=[m for m in msgs
                               if m.type == MT.INSTALL_SNAPSHOT])
            return True

        # host-side ops that never touch the device
        if lq is not None:
            self._answer_log_query(n, lq)
        if compact_key is not None:
            n._process_compaction(compact_key)

        requeue: list[pb.Message] = []
        arrived_us = None
        for m in msgs:
            if m.type == MT.LOCAL_TICK:
                ticks += 1
            elif m.type == MT.READ_INDEX:
                # a follower host forwarded a read (hint carries its ctx)
                if arrived_us is None:
                    arrived_us = monotonic_us()    # once per staging pass
                n._remote_reads.append(
                    (m.from_, pb.SystemCtx(low=m.hint, high=m.hint_high),
                     arrived_us))
            elif m.type == MT.READ_INDEX_RESP:
                n._local_ri_pending.pop(m.hint, None)
                n.pending_reads.add_ready(
                    pb.SystemCtx(low=m.hint, high=m.hint_high), m.log_index)
                n.pending_reads.applied(n.sm.get_last_applied())
            elif m.type in _KERNEL_MTYPES:
                if not inbox.add(g, m, n):
                    requeue.append(m)
                work = True
            # other local messages: ignored on the kernel path (a peer's
            # QUIESCE word is a kernel type: the lane follows it on the
            # device, as Node does through QuiesceState)
        if requeue:
            with n.mu:
                n.incoming_msgs = requeue + n.incoming_msgs

        # proposals -> prop lanes (payload staged by slot, fate correlated
        # in _process_outputs)
        if cc_entry is not None or props:
            self._stage_props(g, n, inp, cc_entry, props)
            work = True

        # one batched ReadIndex ctx per step: prefer a forwarded remote
        # read, else the local batch (node.go:1296)
        n._staged_ri = None
        ri_from = 0
        ri_since_us = 0         # when the staged ctx began to wait
        if n._remote_reads:
            ri_from, ctx, ri_since_us = n._remote_reads.pop(0)
            n._staged_ri = ctx
            n._remote_ri_inflight[ctx.low] = ri_from
            inp.read(g, ctx)
            work = True
        else:
            ctx = n.pending_reads.peep()
            if ctx is not None:
                if n.is_leader() or len(self._peers_of(n)) == 1:
                    n._staged_ri = ctx
                    n._local_ri_pending[ctx.low] = ctx
                    inp.read(g, ctx)
                    ri_since_us = n.pending_reads.peeped_since_us
                elif n._leader_cache != 0:
                    # forward to the leader host (raft.go ReadIndex
                    # leader forwarding)
                    n._local_ri_pending[ctx.low] = ctx
                    self._send(n, pb.Message(
                        type=MT.READ_INDEX, from_=n.replica_id,
                        to=n._leader_cache, shard_id=n.shard_id,
                        hint=ctx.low, hint_high=ctx.high))
                else:
                    n.pending_reads.dropped(ctx)
                work = True
        n._staged_ri_from = ri_from
        if n._staged_ri is not None:
            self._reads_staged += 1
            _READ_STAGE_WAIT_US.observe(monotonic_us() - ri_since_us)

        if transfer is not None:
            inp.transfer(g, transfer)
            work = True
        if ss_req is not None:
            self._take_lane_snapshot(n, ss_req)
        if ticks:
            inp.tick(g)
            work = True
        applied = n.sm.get_last_applied()
        inp.applied(g, applied)
        if applied > self._applied_sent_np[g]:
            work = True
        # anything left queued (inbox overflow requeues, extra remote
        # reads, an unserved local read batch) re-stages next step
        with n.mu:
            residual = bool(n.incoming_msgs or n.incoming_proposals
                            or n._remote_reads
                            or n.config_change_entry is not None
                            or n.transfer_target is not None
                            or n._transfer_awaiting is not None
                            or n.snapshot_request is not None
                            or n.log_query_range is not None
                            or n.compaction_request_key is not None
                            or n._tick_pending)
        # non-destructive batch probe: peep() here would move the batch
        # under a fresh ctx that nothing ever stages — its readers would
        # sit in pending until the timeout GC fires
        if residual or n.pending_reads.batching:
            self._dirty.add(g)
        return work

    def _prop_target(self, n: KernelNode) -> tuple[int, KernelNode]:
        """(row, node) whose prop lanes this node's proposals stage into.
        The single-device engine always proposes on its own lane (the
        kernel drops non-leader proposals and the client retries); mesh
        engines override to forward to the group's leader row."""
        return n.lane, n

    def _stage_props(self, g: int, n: KernelNode, inp: _InputBuilder,
                     cc_entry, props) -> None:
        """Stage cc + proposals into prop slots, remembering the origin
        node per slot so fates (drop/mirror) land on the right books."""
        tg, tn = self._prop_target(n)
        self._staged_rows.add(tg)
        slot = first = self._slot_cursor.get(tg, 0)
        deferred = 0
        if cc_entry is not None:
            if slot < inp.B:
                inp.prop(tg, slot, True)
                tn._staged_props.append((cc_entry, n))
                slot += 1
            else:
                deferred += 1
                with n.mu:
                    n.config_change_entry = n.config_change_entry or cc_entry
        for e in props:
            if slot >= inp.B:
                deferred += 1
                with n.mu:
                    n.incoming_proposals.append(e)
                continue
            inp.prop(tg, slot, False)
            tn._staged_props.append((e, n))
            if e.key:
                lifecycle.TRACER.stamp(e.key, lifecycle.STAGE_STAGE)
            slot += 1
        self._slot_cursor[tg] = slot
        # admission counters: what this pass admitted and turned away,
        # and the row's slots the first time the round fills one
        self._props_staged += slot - first
        self._props_deferred += deferred
        _PROPS_STAGED.inc(slot - first)
        _PROPS_DEFERRED.inc(deferred)
        if first == 0 and slot > 0:
            _PROP_SLOTS_OFFERED.inc(inp.B)

    def _peers_of(self, n: KernelNode) -> dict[int, str]:
        m = n.sm.get_membership()
        return {**m.addresses, **m.non_votings, **m.witnesses}

    # -- output processing -------------------------------------------------

    def _process_outputs(self, ctx: _StepCtx) -> None:
        """Retire one dispatched step: resolve proposal fates, emit
        messages, persist, apply, complete reads, fire events.  Serial
        mode calls this inline; pipelined mode one step late (the ctx
        carries the fates/read ctxs that staging has since rebound).

        The fetch is ONE download: the [G, Wd] int32 array the step's
        program ended by writing (core/round.py ``pack_round``: activity
        flags, the ``active`` column, every StepOutput field, the save
        window's terms).  Everything below is host work on that array,
        and it reads the array ONCE after the ``active`` column: the
        candidate rows are gathered and turned into lists of Python ints
        (``_Retiring``), and what follows indexes those lists at the
        column table's offsets and does for a lane only what its row says
        happened (a class of message whose flag is set, a save or apply
        window that is not empty, a leader that moved).  Read a cell at a
        time, a round of 220 lanes made 9,000 numpy scalar reads and built
        430 messages a field at a time; read a numpy call a field, a round
        let the interpreter go at every call (PERF.md section 6, PR 32).  The
        rare classes (witness snapshots, ReadIndex completions and drops,
        config changes, escalation, a save window past ``S``) keep
        per-lane handlers on the numpy view of the same rows: a lane one
        of them took counts ``per_lane`` in ``engine_retire_lanes``."""
        nodes = ctx.nodes
        rt = self._round
        for k in ctx.traced:
            lifecycle.TRACER.stamp(k, lifecycle.STAGE_RETIRE)
        # fetch: the download (where the host waits for the device) and
        # the rows it names
        rt.enter("fetch")
        with _capacity.METER.sanctioned("round_down") as crossing:
            host = np.asarray(ctx.out)
            crossing.moved(host)
        # lanes with anything to process are the rows whose ``active``
        # cell is not 0.  The round's program computed it (core/round.py
        # ``row_activity``) and it covers every consumer below: emitted
        # messages and snapshot needs (all eight flag columns), dropped
        # reads (_complete_reads) and escalation flags, save/apply windows
        # and quiet term/vote/commit changes (_build_updates persists a
        # bump even when no message went out), leader moves
        # (_leader_edge); staged proposal fates ride ctx.staged_rows
        # below, and a replica placed at a term > 0 its first pass
        # (ctx.injected: its leader edge is 0 -> that term, and no step
        # moved it).
        # ONE ``tolist`` of one column, the interpreter held: the host
        # built this mask itself once, a dozen numpy calls over every row
        # it held, each a point where the engine thread lost its turn
        # (27 ms of a round that retired 79 of 1,024 rows: PERF.md
        # section 6, PR 36)
        active_at = self._at["active"]
        cand_ids = {g for g, a in enumerate(host[:, active_at].tolist())
                    if a}
        cand_ids.update(ctx.staged_rows)
        cand_ids.update(ctx.injected)
        cand_ids.difference_update(ctx.dead)
        # identity check, not membership: a row whose node was removed
        # (and possibly re-admitted) while the step was in flight must
        # not have stale outputs applied to the successor's books
        lanes = [g for g in sorted(cand_ids)
                 if g in nodes and self.nodes.get(g) is nodes[g]]
        # every processed lane re-stages once next step: multi-window
        # pipelines (apply batches, read books, ring compaction) advance
        # by re-examination, exactly as the full scan did
        self._dirty.update(lanes)
        self._lanes_processed += len(lanes)
        # the candidate rows of the download, read once: everything below
        # indexes these lists (never all [G] rows, never a numpy cell)
        r = _Retiring(lanes, [nodes[g] for g in lanes], host,
                      self._down_cols)
        named = sum(1 for row in r.cells if row[active_at])
        _NAMED_BY_DEVICE.inc(named)
        _NAMED_BY_HOST.inc(len(lanes) - named)
        # the dispatch backend derives drain-pending from the rows' flags
        # (a row that is no candidate has none set)
        self._dispatch.note_output_flags(r.cells)

        rt.enter("resolve")
        # 1. proposal fates
        self._resolve_fates(r, ctx.fates)
        # 2. outgoing messages, of the classes a row's flags name
        replicates: list = []
        others: list = []
        self._emit_messages(r, replicates, others)
        # 3. persistence batch
        updates = self._build_updates(r)

        # replicate-before-fsync (engine.go:1332-1343).  The timer's
        # ``resolve.send`` is both sends as the sender pays them (the
        # receivers' registries, ``_dirty_mu``, ``node.mu``); its two marks
        # are when a round's messages have left, in a round that sent any
        with rt.part("resolve.send"):
            self._send_all(replicates)
        if replicates:
            rt.mark("replicates_out")
        if updates:
            rt.enter("save")
            # one batched fsync per LogDB (nodes of a shared mesh engine
            # belong to different NodeHosts, each with its own LogDB)
            by_db: dict[int, tuple[object, list]] = {}
            for n, ud in updates:
                by_db.setdefault(id(n.logdb), (n.logdb, []))[1].append(ud)
                lifecycle.TRACER.stamp_all(
                    [e.key for e in ud.entries_to_save],
                    lifecycle.STAGE_SAVE)
            for db, uds in by_db.values():
                db.save_raft_state(uds, worker_id=0)
            rt.enter("resolve")
        with rt.part("resolve.send"):
            self._send_all(others)
        if others:
            rt.mark("responses_out")

        rt.enter("finish")
        self._finish(r, ctx.staged_ri)
        self._write_held_cells()
        _RETIRED_PER_LANE.inc(len(r.per_lane))
        _RETIRED_COLUMNAR.inc(len(lanes) - len(r.per_lane))

    def _resolve_fates(self, r: _Retiring, fates_of: dict) -> None:
        """What became of the proposals staged into this step (the origin
        holds the future's books: on a mesh engine forwarded proposals
        stage on the leader row): an accepted one enters the payload
        mirror at the index and term the kernel gave it, a refused one
        fails its future now."""
        if not fates_of:
            return
        at = self._at
        accepted, index_at, term_at = (
            at["prop_accepted"], at["prop_index"], at["prop_term"])
        where = {g: i for i, g in enumerate(r.lanes)}
        for g, fates in fates_of.items():
            i = where.get(g)
            if i is None:
                continue
            row, n = r.cells[i], r.nodes[i]
            mirror = n.mirror
            for slot, fate in enumerate(fates):
                if row[accepted + slot]:
                    index = row[index_at + slot]
                    mirror[index] = _entry_at(
                        fate[0], index, row[term_at + slot])
                else:
                    if fate[0].is_config_change():
                        r.per_lane.add(i)
                    self._fail_fates((fate,))
            if n._staged_props is fates:
                # serial mode retires before the next staging rebinds
                # the list; pipelined mode's rebind already happened
                n._staged_props = []

    def _link_mask(self, rows: list):
        """Which links of the lanes ``rows`` the host transport carries,
        as a ``[len(rows), R] bool`` array indexed by the target's replica
        id less one; None where it carries them all (a seam: the mesh
        engine answers with its cut mask, the rest rides the mesh)."""
        return None

    def _emit_messages(self, r: _Retiring, replicates: list,
                       others: list) -> None:
        """This round's outgoing messages, for the rows whose flags say
        they have some: a class whose flag is clear is never looked at.
        Replicates go to ``replicates`` (sent before the save), the rest
        to ``others``; a (shard, target) pair gets them in the order
        responses, witness snapshot, heartbeat, vote, timeout-now."""
        links = self._link_mask(r.lanes)
        # (a mask with no link in it: nothing is the host's to send but
        # witness snapshots)
        hushed = links is not None and not links.any()
        links = None if links is None or hushed else links.tolist()
        at = self._at
        K, E = self.kp.inbox_cap, self.kp.msg_entries
        term_at = at["term"]
        r_type, r_to, r_term, r_index, r_reject, r_hint, r_high = (
            at[f] for f in ("r_type", "r_to", "r_term", "r_log_index",
                            "r_reject", "r_hint", "r_hint_high"))
        s_rep, s_prev, s_prev_term, s_commit, s_count, s_terms = (
            at[f] for f in ("s_rep", "s_prev_index", "s_prev_term",
                            "s_commit", "s_n_ent", "s_ent_term"))
        s_hb, s_hb_commit, s_hb_low, s_hb_high = (
            at[f] for f in ("s_hb", "s_hb_commit", "s_hb_low", "s_hb_high"))
        s_vote, s_vote_term, s_vote_index, s_vote_lterm, s_vote_hint = (
            at[f] for f in ("s_vote", "s_vote_term", "s_vote_lindex",
                            "s_vote_lterm", "s_vote_hint"))
        s_timeout = at["s_timeout_now"]
        pids = kinds = None         # the rows' peer books, once needed
        for i, row in enumerate(r.cells):
            resp, rep, hb, vote, timeout, wit_snap = (
                row[_F_RESP], row[_F_REP], row[_F_HB], row[_F_VOTE],
                row[_F_TIMEOUT], row[_F_WITSNAP])
            if not (resp or rep or hb or vote or timeout or wit_snap):
                continue
            n = r.nodes[i]
            if hushed:
                resp = rep = hb = vote = timeout = 0
            linked = None if links is None else links[i]
            if resp:
                for k in range(K):
                    mt = row[r_type + k]
                    if not mt:
                        continue
                    to = row[r_to + k]
                    if linked is not None and not (
                            1 <= to <= len(linked) and linked[to - 1]):
                        continue
                    others.append((n, _message(
                        _MT_OF[mt], to, n, term=row[r_term + k],
                        log_index=row[r_index + k],
                        reject=bool(row[r_reject + k]),
                        hint=row[r_hint + k], hint_high=row[r_high + k])))
            if wit_snap:
                r.per_lane.add(i)
                self._witness_snapshot(r, i, others)
            if not (rep or hb or vote or timeout):
                continue
            if pids is None:
                pids = self._pid_np[r.lanes].tolist()
            term = row[term_at]
            # a leader's entries are built once per (prev, count) and
            # shared by the peers they fit (a witness peer's are
            # stripped: a form of its own)
            built: dict = {}
            for p, to in enumerate(pids[i]):
                if to == 0 or to == n.replica_id:
                    continue
                if linked is not None and not (
                        1 <= to <= len(linked) and linked[to - 1]):
                    continue
                if rep and row[s_rep + p]:
                    if kinds is None:
                        kinds = self._kind_np[r.lanes].tolist()
                    prev, count = row[s_prev + p], row[s_count + p]
                    key = (prev, count, kinds[i][p] == KP.K_WITNESS)
                    entries = built.get(key)
                    if entries is None:
                        first = s_terms + p * E
                        entries = built[key] = _replicate_entries(
                            n.mirror, prev, row[first:first + count], key[2])
                    replicates.append((n, _message(
                        MT.REPLICATE, to, n, term=term,
                        log_term=row[s_prev_term + p], log_index=prev,
                        commit=row[s_commit + p], entries=entries)))
                if hb and row[s_hb + p]:
                    if row[s_hb_commit + p] == KP.QUIESCE_WORD:
                        # the lane entered quiesce on its own idle clock
                        # and tells its peers (node.go
                        # sendEnterQuiesceMessages)
                        others.append((n, _message(MT.QUIESCE, to, n)))
                    else:
                        others.append((n, _message(
                            MT.HEARTBEAT, to, n, term=term,
                            commit=row[s_hb_commit + p],
                            hint=row[s_hb_low + p],
                            hint_high=row[s_hb_high + p])))
                if vote and row[s_vote + p]:
                    others.append((n, _message(
                        MT.REQUEST_VOTE if row[s_vote + p] == 1
                        else MT.REQUEST_PREVOTE, to, n,
                        term=row[s_vote_term + p],
                        log_term=row[s_vote_lterm + p],
                        log_index=row[s_vote_index + p],
                        hint=row[s_vote_hint + p])))
                if timeout and row[s_timeout + p]:
                    others.append((n, _message(
                        MT.TIMEOUT_NOW, to, n, term=term)))

    def _witness_snapshot(self, r: _Retiring, i: int, others: list) -> None:
        """A witness peer of row ``i`` fell behind compaction: answer
        with the stripped file-less snapshot built from the recorded
        snapshot (raft.go:713-735) — no stream, no eviction.  The record
        must cover the DEVICE compaction floor: the device paused the
        peer at psnap = snap_index, and a stale older record would leave
        a gap the witness can never bridge (re-sent forever) — the lane
        takes the regular eviction slow path instead (a seam: on a mesh
        engine it always does)."""
        g, n = r.lanes[i], r.nodes[i]
        for p in np.nonzero(r.view()["s_wit_snap"][i])[0].tolist():
            to = int(self._pid_np[g, p])
            if to == 0 or to == n.replica_id:
                continue
            ss = n.logdb.get_snapshot(n.shard_id, n.replica_id)
            with _capacity.METER.sanctioned("wit_snap_floor") as crossing:
                cell = state_cell(                # wit_snap only
                    self._resident.cols, np.int32(g), np.int32(
                        self._state_cols["snap_index"].start))
                crossing.moved(cell)
                floor = int(cell)
            if ss is not None and not ss.is_empty() and ss.index >= floor:
                others.append((n, pb.Message(
                    type=MT.INSTALL_SNAPSHOT, to=to, from_=n.replica_id,
                    shard_id=n.shard_id, term=r.cells[i][self._at["term"]],
                    snapshot=_dc_replace(
                        ss, filepath="", file_size=0, files=(),
                        witness=True, dummy=False),
                )))
            elif n not in r.fallback:
                # no record, or one below the device floor — the
                # regular escalation path recovers the shard
                r.fallback.append(n)

    def _save_terms(self, g: int, first: int, last: int) -> list:
        """Terms of the ring entries ``first..last`` of a lane whose save
        window is wider than the download's ``S`` entries (none expected;
        counted): one fixed-shape fetch of the lane's whole ring row from
        the state that step returned (still the resident one: a retire
        runs before the next dispatch)."""
        _SAVE_WINDOW_OVERFLOW.inc()
        with _capacity.METER.sanctioned("save_window_row") as crossing:
            row = np.asarray(ring_row(self._resident.lt, np.int32(g)))
            crossing.moved(row)
        return row[(first + np.arange(last - first + 1))
                   & (self.kp.log_cap - 1)].tolist()

    def _build_updates(self, r: _Retiring) -> list:
        """-> [(node, pb.Update)] of the rows with entries to save or a
        (term, vote, commit) that moved in this step (a quiet bump is
        persisted too: the row's ``active`` cell says so, and every step
        that moves a triple is retired, so what a step was given is what
        was persisted last)."""
        at = self._at
        term_at, vote_at, commit_at = at["term"], at["vote"], at["commit"]
        first_at, last_at, terms_at = (
            at["save_first"], at["save_last"], at["save_terms"])
        active_at = at["active"]
        S = self._cols.save_window
        updates = []
        for i, row in enumerate(r.cells):
            lo, hi = row[first_at], row[last_at]
            if hi < lo and not row[active_at] & ACTIVE_TRIPLE:
                continue
            n = r.nodes[i]
            entries = []
            if hi >= lo:
                if hi - lo < S:
                    terms = row[terms_at:terms_at + hi - lo + 1]
                else:
                    r.per_lane.add(i)
                    terms = self._save_terms(r.lanes[i], lo, hi)
                mirror = n.mirror
                idx = lo
                for t in terms:
                    e = mirror.get(idx)
                    if e is None:
                        e = mirror[idx] = pb.Entry(t, idx)
                    elif e.term != t:
                        e = mirror[idx] = _entry_at(e, e.index, t)
                    entries.append(e)
                    idx += 1
            updates.append((n, _update(n, pb.State(
                row[term_at], row[vote_at], row[commit_at]), entries)))
        return updates

    def _finish(self, r: _Retiring, staged_ri: dict) -> None:
        """After the save, per row and only where it happened: complete
        reads, apply released entries, fire the leader edge, escalate."""
        at = self._at
        commit_at, term_at = at["commit"], at["term"]
        first_at, last_at = at["apply_first"], at["apply_last"]
        leader_at, leader_term_at = at["leader"], at["leader_term"]
        dropped_at, needs_host_at = at["ri_dropped"], at["needs_host"]
        removed = len(self._removed_nodes)
        # what ``_apply`` spends applying and acknowledging, summed over
        # the rows and handed to the round timer once each
        self._apply_ns = self._ack_ns = 0
        for i, row in enumerate(r.cells):
            n = r.nodes[i]
            # a whole-group eviction earlier in THIS loop (mesh engine)
            # already handed the sibling rows to host-resident successor
            # nodes — touching their SMs/books here would race them
            # (a removal is logged: only then is a row checked again)
            if len(self._removed_nodes) != removed \
                    and not self._is_registered(n):
                continue
            n._committed_cache = row[commit_at]
            # 4. ReadIndex results
            if row[_F_RTR] or row[dropped_at]:
                r.per_lane.add(i)
                view = r.view()
                self._complete_reads(i, n, view, view["flags"][i],
                                     staged_ri.get(r.lanes[i]))
            # 5. apply released entries
            if row[last_at] >= row[first_at] and self._apply(
                    n, row[first_at], row[last_at], row[term_at]):
                r.per_lane.add(i)
            # 6. leader edges
            leader, term = row[leader_at], row[leader_term_at]
            if leader != n._leader_cache or term != n._leader_term_cache:
                self._leader_edge(n, leader, term)
            # 7. escalation
            if row[needs_host_at]:
                r.per_lane.add(i)
                self._evict(n, reason="kernel escalation")
        for n in r.fallback:
            if self._is_registered(n):
                self._evict(n, reason="witness snapshot without record")
        rt = self._round
        rt.add("finish.apply", self._apply_ns)
        rt.add("finish.ack", self._ack_ns)

    def _complete_reads(self, g, n, o, fl, staged_ri) -> None:
        """ReadIndex results of row ``g`` of ``o`` (``fl``: its flag
        row).  ``staged_ri`` is the ReadIndex ctx staged into THIS step
        (from the step ctx — staging for the next step rebinds
        ``n._staged_ri`` before a pipelined retire runs)."""
        if fl[_F_RTR]:
            rtr = o["rtr_valid"][g]
            for j in range(rtr.shape[0]):
                if not rtr[j]:
                    continue
                low = int(o["rtr_low"][g, j])
                high = int(o["rtr_high"][g, j])
                index = int(o["rtr_index"][g, j])
                ctx = pb.SystemCtx(low=low, high=high)
                if low in n._local_ri_pending:
                    n._local_ri_pending.pop(low)
                    n.pending_reads.add_ready(ctx, index)
                elif low in n._remote_ri_inflight:
                    # remote read answered: respond to the requester
                    self._send(n, pb.Message(
                        type=MT.READ_INDEX_RESP,
                        to=n._remote_ri_inflight.pop(low),
                        from_=n.replica_id, shard_id=n.shard_id,
                        log_index=index, hint=low, hint_high=high))
        if o["ri_dropped"][g] and staged_ri is not None:
            low = staged_ri.low
            if low in n._local_ri_pending:
                n._local_ri_pending.pop(low)
                n.pending_reads.dropped(staged_ri)
            sender = n._remote_ri_inflight.pop(low, None)
            if sender is not None and n.is_leader():
                # a forwarded read this leader's kernel turned away (its
                # ReadIndex book is full): the requester hears of nothing
                # but an index, so the read waits its turn here instead of
                # timing out over there.  One no longer led from here is
                # let go, as before: the requester's timeout retries it
                n._remote_reads.insert(0, (sender, staged_ri, monotonic_us()))
        n.pending_reads.applied(n.sm.get_last_applied())

    def _apply(self, n: KernelNode, first: int, last: int,
               term: int) -> bool:
        """Hand the committed entries ``first..last`` to the RSM and
        complete what waited on them; -> whether a config change was
        among them (the rare class of this pass).

        The round timer's two parts of ``finish`` are told apart here, a
        row costing two or three reads of the host clock and no histogram:
        ``finish.apply`` is the mirror walk and the state machine's
        ``handle`` with its apply stamps; ``finish.ack`` the two loops that
        answer the proposals' futures, each answer waking a client
        thread, on a replica that holds any."""
        clock = self._round.clock_ns
        t_apply = clock()
        ack_ns = 0
        mirror = n.mirror
        entries = []
        for idx in range(first, last + 1):
            e = mirror.get(idx)
            if e is None:
                e = mirror[idx] = pb.Entry(term, idx)
            entries.append(e)
        # a future lives only where its proposal entered: a replica whose
        # book is empty (two of three, under writes to leaders) has none
        # to commit or complete
        book = n.pending_proposals
        waited = not book.idle()
        if n.rate_limiter.enabled():
            for e in entries:
                if e.key:
                    n._rl_release(e.key)
        if n.notify_commit and waited:
            t = clock()
            for e in entries:
                if e.key:
                    book.committed(e.key)
            ack_ns = clock() - t
        results = n.sm.handle(entries)
        lifecycle.TRACER.stamp_all(
            [e.key for e in entries], lifecycle.STAGE_APPLY)
        t_ack = clock()
        self._apply_ns += t_ack - t_apply - ack_ns
        cc_applied = False
        # ``handle`` answers for a subsequence of the entries, in order
        # (it skips what an on-disk state machine already replayed): one
        # forward cursor matches them
        cursor = iter(entries)
        for res in results:
            for entry in cursor:
                if entry.index == res.index:
                    break
            if entry.is_config_change():
                n._on_config_change_applied(entry, res)
                cc_applied = True
            elif res.key and waited:
                book.applied(res.key, res.client_id, res.series_id,
                             res.result, res.rejected)
        if waited:
            self._ack_ns += ack_ns + clock() - t_ack
        if cc_applied:
            self.update_lane_membership(n)
        n.applied_since_snapshot += len(results)
        if n.pending_reads.waiting:
            n.pending_reads.applied(n.sm.get_last_applied())
        # auto snapshot + mirror pruning (node.go:694 saveSnapshotRequired)
        if (n.cfg.snapshot_entries > 0
                and n.applied_since_snapshot >= n.cfg.snapshot_entries):
            self._take_lane_snapshot(n, _SnapshotRequest())
        self._prune_mirror(n)
        return cc_applied

    def _mirror_floor(self, n: KernelNode) -> int:
        """Lowest applied cursor that still needs mirror payloads.  On a
        shared mesh mirror this is the MINIMUM across the shard's
        replicas (a lagging/cut member must still find its entries)."""
        return n.sm.get_last_applied()

    def _prune_mirror(self, n: KernelNode) -> None:
        floor = self._mirror_floor(n) - self.kp.compaction_overhead
        if floor <= 0 or len(n.mirror) <= self.kp.log_cap:
            return
        for idx in [i for i in n.mirror if i < floor]:
            del n.mirror[idx]

    def _take_lane_snapshot(self, n: KernelNode,
                            req: _SnapshotRequest) -> None:
        """Host-side RSM snapshot for a kernel shard (the device compacts
        its ring itself; this makes restart/install possible)."""
        n._take_snapshot(req)

    def _answer_log_query(self, n: KernelNode,
                          lq: tuple[int, int, int]) -> None:
        """QueryRaftLog for a device shard, answered host-side from the
        durable log (every committed entry is persisted before release,
        so the LogDB is authoritative up to the lane's commit cursor)."""
        first, last, max_size = lq
        committed = n._committed_cache
        rs = n.logdb.read_raft_state(n.shard_id, n.replica_id, 0)
        avail_first = rs.first_index if rs is not None else 1
        if first < avail_first:
            n._on_log_query_result(pb.LogQueryResult(
                error=1, first_index=avail_first,
                last_index=committed + 1))
            return
        hi = min(last, committed + 1)
        entries = tuple(n.logdb.iterate_entries(
            n.shard_id, n.replica_id, first, hi, max_size)) if hi > first \
            else ()
        n._on_log_query_result(pb.LogQueryResult(
            error=0, first_index=avail_first, last_index=committed + 1,
            entries=entries))

    def _leader_edge(self, n: KernelNode, leader: int, term: int) -> None:
        if (leader, term) == (n._leader_cache, n._leader_term_cache):
            return
        n._leader_cache, n._leader_term_cache = leader, term
        n._last_leader = (leader, term)
        # the node's OWN hub: on a shared mesh engine each replica's
        # listeners live on its attaching NodeHost, not the engine's
        n.events.leader_updated(LeaderInfo(
            shard_id=n.shard_id, replica_id=n.replica_id,
            term=term, leader_id=leader))
        with n.mu:
            awaiting = n._transfer_awaiting
        if awaiting is not None and leader == awaiting[0]:
            n._finish_transfer(RequestResultCode.COMPLETED, leader)

    # -- escalation --------------------------------------------------------

    def _evict(self, n: KernelNode, reason: str,
               carry: list[pb.Message] | None = None) -> None:
        """Move a shard from the kernel to the loopback engine: state is
        already durable via the shared LogDB, so the host rebuilds a
        pycore Node from disk and the shard continues there."""
        if self.remove_shard(n.shard_id) is None:
            return  # already evicted/stopped concurrently
        _LOG.info("shard %d: leaving the kernel (%s)", n.shard_id, reason)
        if self.on_evict is not None:
            self.on_evict(n, carry or [])

    on_evict = None  # set by NodeHost

    def _send(self, n: KernelNode, m: pb.Message) -> None:
        # local delivery between lanes of this engine happens through the
        # sending node's NodeHost dispatch (same path as remote; on a
        # shared mesh engine each node routes via its own host)
        n.send_message(m)

    def _send_all(self, pairs: list) -> None:
        """What a round sends ([(node, message)]), as one call a sending
        host: its transport makes ONE batch a target of them.  Sent one
        by one, 256 lanes' ~430 messages a round took the process-wide
        locks of the send path (the fabric meter's, the receiving
        registry's and engine's) ~10 times each, and three engines whose
        rounds resolved at the same time queued on them behind the
        interpreter's switch interval: rounds of 150-250 ms became
        600-900 ms for seconds on end (PERF.md, PR 31)."""
        by_host: dict = {}
        for n, m in pairs:
            by_host.setdefault(n.send_messages, []).append(m)
        for send, msgs in by_host.items():
            send(msgs)


# ---------------------------------------------------------------------------
# staging buffers (numpy first, ONE device transfer per step)
# ---------------------------------------------------------------------------


_FAMILY_OF_TYPE = {
    int(pb.MessageType.REPLICATE): "rep",
    int(pb.MessageType.HEARTBEAT): "hb",
    int(pb.MessageType.QUIESCE): "hb",
    int(pb.MessageType.REQUEST_VOTE): "vote",
    int(pb.MessageType.REQUEST_PREVOTE): "vote",
    int(pb.MessageType.TIMEOUT_NOW): "vote",
}
# everything else (responses, NOOP, UNREACHABLE, SNAPSHOT_STATUS) -> "resp"


def _newest_heartbeats(msgs: list) -> list:
    """``msgs`` without the heartbeats a later one supersedes.  A lane
    stages a few messages of a kind a step, so a follower whose engine
    falls behind its leader's queues heartbeats faster than it takes
    them, and its answers (a ReadIndex confirmation among them) come
    ever later.  A heartbeat from the same sender in the same term
    carries everything an earlier one did: its commit is monotone, and
    its ReadIndex ctx is the newest pending one, whose ack confirms the
    older."""
    last: dict = {}
    beats = 0
    for i, m in enumerate(msgs):
        if m.type == MT.HEARTBEAT:
            last[(m.from_, m.term)] = i
            beats += 1
    if beats == len(last):
        return msgs
    return [m for i, m in enumerate(msgs)
            if m.type != MT.HEARTBEAT or last[(m.from_, m.term)] == i]


class _RoundStaging:
    """One round's upload: a host array ``[G, Wu] int32`` laid out by
    kstate.py's column table, written through the two builders (whose
    fields are views of its columns) and sent up in ONE transfer.

    What a round costs here follows the rows it STAGED, not the lanes the
    engine holds: the builders note every row they write (``rows``) and
    ``reset`` clears those alone; the full zero-fill it replaces was a
    pass over ``[capacity]`` a round, i.e. one more place where the engine
    thread gave the interpreter up (PERF.md section 6, PR 43).  An engine
    keeps a pair of these a buffer slot: one serves the rounds every lane
    ticks in and keeps its tick column set for the registered lanes
    between them (``tick_all``), the other serves the rest and is never
    asked to, so neither a tick round nor the one after it writes a
    column whole."""

    def __init__(self, kp: KP.KernelParams, G: int,
                 mesh_replicas: int | None = None) -> None:
        cols = round_columns(kp)
        self.up = np.zeros((G, cols.up_width), np.int32)
        views = column_views(cols.up, self.up)
        #: rows written since the last ``reset`` (the builders add)
        self.rows: set[int] = set()
        self.inbox = _InboxBuilder(views, kp.inbox_cap, kp.msg_entries,
                                   self.rows, mesh_replicas=mesh_replicas)
        self.inp = _InputBuilder(views, kp.proposal_cap, self.rows)
        # the lanes whose tick cell stays set, as the keys ``tick_all``
        # was last handed and as a [G] mask (none until it is called)
        self._ticked: set[int] = set()
        self._ticked_np = np.zeros((G,), bool)

    def reset(self) -> int:
        """Zero the rows written since the last reset (every column of
        them; their tick cells back to what ``tick_all`` keeps set) ->
        how many rows that was.  A row at a time, by plain indexing: one
        assignment through an index ARRAY gives the interpreter up inside
        numpy whatever its size (0.17-0.5 ms a call in a served engine
        where a row's plain write takes microseconds: PERF.md, PR 43)."""
        n = len(self.rows)
        if n:
            up, tick, kept = self.up, self.inp._tick, self._ticked_np
            for g in self.rows:
                up[g] = 0
                if kept[g]:
                    tick[g] = 1
            self.rows.clear()
        return n

    def tick_all(self, nodes: dict) -> None:
        """Every lane of ``nodes`` ticks this round: the column is
        rewritten where the registered lanes moved since this array's last
        tick round and is left as it stands otherwise."""
        if nodes.keys() != self._ticked:
            self._ticked = set(nodes)
            self._ticked_np.fill(False)
            self._ticked_np[np.fromiter(nodes, np.intp, len(nodes))] = True
            self.inp._tick[:] = self._ticked_np

    def to_device(self, sharding=None):
        """The round's one upload (``sharding``: the mesh backend's
        placement along G)."""
        with _capacity.METER.sanctioned("round_up") as crossing:
            crossing.moved(self.up)
            return jax.device_put(self.up, sharding)


class _InboxBuilder:
    """Writes the Inbox columns of a round's upload (``views``:
    ``_RoundStaging``'s field -> [G, ...] int32 view; a bool field is a
    0/1 column)."""

    def __init__(self, views: dict, K: int, E: int, rows: set,
                 mesh_replicas: int | None = None) -> None:
        self.K, self.E = K, E
        self._rows = rows       # the staging's: every row written here
        # typed slot layout (params.slot_families): a message may only be
        # staged into a slot whose family accepts its type ('any' accepts
        # all) — the kernel compiles family-specialized handlers per slot
        fams = KP.slot_families(K)
        self._slots_for = {}
        for fam in ("rep", "hb", "vote", "resp"):
            self._slots_for[fam] = tuple(
                k for k, f in enumerate(fams) if f in (fam, "any"))
        # slot-exact mode (mesh engines): hub-fallback deliveries must
        # land at the SAME route() slot the mesh exchange would have
        # used, so the merged carried inbox is bit-identical to a fully
        # resident exchange (core/router.py slot_candidates)
        self._mesh_R = mesh_replicas
        self.mtype = views["mtype"]
        self.from_ = views["from_"]
        self.term = views["term"]
        self.log_term = views["log_term"]
        self.log_index = views["log_index"]
        self.commit = views["commit"]
        self.reject = views["reject"]
        self.hint = views["hint"]
        self.hint_high = views["hint_high"]
        self.n_ent = views["n_ent"]
        self.ent_term = views["ent_term"]
        self.ent_cc = views["ent_cc"]

    def add(self, g: int, m: pb.Message, n: KernelNode) -> bool:
        if self._mesh_R is not None:
            R = self._mesh_R
            if m.from_ == n.replica_id or not (1 <= m.from_ <= R):
                # unroutable on the mesh layout: a stray delivery, not a
                # full inbox — swallow it (True = no requeue) like the
                # pre-round-17 hub drop did
                return True
            cands = _router.slot_candidates(
                n.replica_id, m.from_, R, int(m.type))
        else:
            cands = self._slots_for[_FAMILY_OF_TYPE.get(int(m.type), "resp")]
        k = -1
        for cand in cands:
            if self.mtype[g, cand] == 0:
                k = cand
                break
        if k < 0:
            return False  # family full this step; host requeues the message
        self._rows.add(g)
        self.mtype[g, k] = int(m.type)
        self.from_[g, k] = m.from_
        self.term[g, k] = m.term
        self.log_term[g, k] = m.log_term
        self.log_index[g, k] = m.log_index
        self.commit[g, k] = m.commit
        self.reject[g, k] = m.reject
        self.hint[g, k] = m.hint
        self.hint_high[g, k] = m.hint_high
        ents = m.entries[:self.E]
        self.n_ent[g, k] = len(ents)
        for j, e in enumerate(ents):
            self.ent_term[g, k, j] = e.term
            self.ent_cc[g, k, j] = e.is_config_change()
            # stage payloads; the kernel decides acceptance, and content
            # at-or-below commit is invariant so overwrites are safe
            n.mirror[e.index] = e
        return True


class _InputBuilder:
    """Writes the StepInput columns of a round's upload (``quiesced``
    stays 0: the device quiesces itself)."""

    def __init__(self, views: dict, B: int, rows: set) -> None:
        self.B = B
        self._rows = rows       # the staging's: every row written here
        self.prop_valid = views["prop_valid"]
        self.prop_cc = views["prop_cc"]
        self.ri_valid = views["ri_valid"]
        self.ri_low = views["ri_low"]
        self.ri_high = views["ri_high"]
        self.transfer_to = views["transfer_to"]
        self._tick = views["tick"]
        self._applied = views["applied"]

    def prop(self, g: int, slot: int, is_cc: bool) -> None:
        self._rows.add(g)
        self.prop_valid[g, slot] = True
        self.prop_cc[g, slot] = is_cc

    def read(self, g: int, ctx: pb.SystemCtx) -> None:
        self._rows.add(g)
        self.ri_valid[g] = True
        self.ri_low[g] = ctx.low & 0x7FFFFFFF
        self.ri_high[g] = ctx.high & 0x7FFFFFFF

    def transfer(self, g: int, target: int) -> None:
        self._rows.add(g)
        self.transfer_to[g] = target

    def tick(self, g: int) -> None:
        self._rows.add(g)
        self._tick[g] = True

    def applied(self, g: int, v: int) -> None:
        self._rows.add(g)
        self._applied[g] = v
