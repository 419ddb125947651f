"""Unified engine dispatch: ONE step loop, two jit backends.

``KernelEngine.step_all`` is the ONLY step loop; the only thing a backend
contributes is a ``dispatch()`` — the serial round (core/round.py
``step``/``step_donated``) or the shard_map round over a device mesh
(parallel/round.py) — each a donated + non-donated pair behind
CompileTracker telemetry, so the pipelined retire-before-dispatch
protocol works identically on both paths (the engine-unity lint pass,
analysis/engine_unity.py EU001–EU006, keeps it so).

A round crosses the device boundary three times whatever it carries: ONE
upload (the staged Inbox and StepInput, packed into one [G, Wu] int32
array by ``_RoundStaging``), ONE call of the backend's jitted entry, and
ONE download (the [G, Wd] int32 array that entry ends by writing: the
activity flags, every StepOutput field and the save window's terms).
Between rounds the state stays on the device in its resident form
(kstate.py ``ResidentState``: three arrays; the mesh backend's carried
inbox one more), which the entry takes and returns: a round lets go of
4-6 device arrays, not 46-60, and each one let go is a wait for the
interpreter (the gauge ``engine_entry_arrays`` counts them).  All layouts
are kstate.py's column table; pack and unpack are generic over the
leading [G] axis, so the backends differ only in ``dispatch()``.

The module-level tuples/dicts below are the MACHINE-READ contract the
engine-unity pass enforces (pure literals, parsed with
``ast.literal_eval`` — like kstate's CONTRACTS/DONATION tables):

- ``STEP_LOOP_METHODS``: step-loop internals only ``STEP_LOOP_OWNER``
  may define — a subclass override is a second step loop (EU001);
- ``DISPATCH_SEAMS``: the sanctioned subclass seams (addressing,
  membership, escalation, the link mask, and ``_make_dispatch``);
- ``ENGINE_FEATURE_KNOBS`` / ``ENGINE_FEATURE_CALLS``: dispatch
  features that must be reachable from ``step_all`` on every engine
  path (EU002/EU004);
- ``DISPATCH_ENTRIES``: every jit entry a dispatch backend may call —
  donated ones must carry a kstate.DONATION declaration (EU003,
  composing with KC008/PS004), non-donated ones a waiver naming why.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dragonboat_tpu import capacity as _capacity
from dragonboat_tpu import telemetry
from dragonboat_tpu.core import params as KP
from dragonboat_tpu.core.kernel import step as kernel_step
from dragonboat_tpu.core.kstate import (
    FLAG_CLASSES,
    box_view_program,
    inbox_columns,
)
from dragonboat_tpu.core.round import (
    step as round_step,
    step_donated as round_step_donated,
)
from dragonboat_tpu.parallel.ici import IciCluster
from dragonboat_tpu.parallel.round import (
    box_from,
    jit_serve_step,
    jit_serve_step_donated,
)

#: the one class allowed to define step-loop internals
STEP_LOOP_OWNER = "KernelEngine"

#: step-loop internals: defining any of these in a subclass of the owner
#: is a second step-loop implementation (EU001)
STEP_LOOP_METHODS = (
    "step_all",
    "_take_admissions",
    "_flush_injections",
    "_stage_lane",
    "_stage_props",
    "_process_outputs",
    "_resolve_fates",
    "_emit_messages",
    "_build_updates",
    "_save_terms",
    "_finish",
    "_kernel_call",
    "_capacity_entries",
    "_device_pending",
    "_fleet_inbox_from",
    "_capacity_trees",
    "_capacity_model_classes",
    "_make_digest",
    "_collect_digest",
)

#: sanctioned subclass seams: addressing, membership, escalation,
#: which links the host carries, and the dispatch-backend factory
DISPATCH_SEAMS = (
    "_make_dispatch",
    "_link_mask",
    "_witness_snapshot",
    "_send",
    "_send_all",
    "_prop_target",
    "_mirror_floor",
    "_is_registered",
    "_evict",
    "add_shard",
    "_register",
    "remove_shard",
    "update_lane_membership",
)

#: ExpertConfig-fed engine attributes gating dispatch features; every
#: one must be read on a path reachable from step_all in EVERY concrete
#: engine (EU002 flags per-path drift)
ENGINE_FEATURE_KNOBS = (
    "pipeline_depth",
    "fleet_stats_every",
    "health_top_k",
    "invariant_probe",
)

#: feature calls (not attributes) that must stay reachable from the
#: step loop on every path — the retired round's activity flags feed the
#: backend's drain-pending (the mesh backend carries its inbox on device)
ENGINE_FEATURE_CALLS = ("note_output_flags",)

#: every jit entry a dispatch backend may call.  ``donated`` entries
#: must be kstate.DONATION-declared (EU003 cross-checks via KC008);
#: non-donated entries carry a waiver naming why donation is out.
DISPATCH_ENTRIES = {
    "step": {
        "module": "dragonboat_tpu/core/round.py",
        "function": "step",
        "donated": False,
        "waiver": "depth-0 serial oracle: the differential reference "
                  "entry must leave its inputs readable",
    },
    "step_donated": {
        "module": "dragonboat_tpu/core/round.py",
        "function": "step_donated",
        "donated": True,
        "waiver": "",
    },
    "serve_step": {
        "module": "dragonboat_tpu/parallel/round.py",
        "function": "jit_serve_step",
        "donated": False,
        "waiver": "depth-0 mesh oracle: the differential reference "
                  "entry must leave its inputs readable",
    },
    "serve_step_donated": {
        "module": "dragonboat_tpu/parallel/round.py",
        "function": "jit_serve_step_donated",
        "donated": True,
        "waiver": "",
    },
}

#: every sanctioned device->host SYNC site in the engine layer, keyed by
#: the host-side qualname whose body may force a device value
#: (``int()`` / ``.item()`` / ``np.asarray`` / ``block_until_ready``).
#: The transfer pass (analysis/transfer.py TB005 — the engine-scope
#: sharpening of PS006) fails any other engine-layer sync; the runtime
#: leg counts each under ``tag`` via capacity.METER.  Declaring a site
#: here is a REVIEWED claim that the sync is the round's one download or
#: off the per-step critical path.
SYNC_POINTS = {
    "KernelEngine._process_outputs": {
        "tag": "round_down",
        "why": "the round's ONE download: the packed [G, Wd] array the "
               "jitted entry wrote (flags, StepOutput, save-window terms)",
    },
    "KernelEngine._collect_digest": {
        "tag": "digest_down",
        "why": "the every-Nth-round collection's ONE download: the flat "
               "int32 vector of the fleet, health and invariant reports "
               "(core/digest.py), read by one tolist",
    },
    "KernelEngine._save_terms": {
        "tag": "save_window_row",
        "why": "whole-ring-row fallback of a lane whose save window is "
               "wider than the download's S entries (none expected; "
               "counted in engine_save_window_overflow)",
    },
    "KernelEngine._witness_snapshot": {
        "tag": "wit_snap_floor",
        "why": "witness-snapshot floor probe (snap_index scalar) on the "
               "rare wit_snap retire path only",
    },
}

#: the machine-read transfer contract: every value crossing the
#: device<->host boundary through the dispatch seam, per jit entry
#: (analysis/transfer.py sizes each row in closed form from the
#: CONTRACTS grammar and gates the per-step totals against
#: analysis/transfer_budget.json).  Row schema:
#:   value    contract class name or inline contract string — or, on a
#:            ``packed`` row, the tuple of them that rides one array
#:   packed   the row is ONE int32 array laid out by kstate.py's column
#:            table (every element 4 bytes, a bool a 0/1 column)
#:   param    entry parameter the upload binds (classification cross-check)
#:   site     host qualname performing the crossing
#:   tag      capacity.METER tag the site counts under
#:   per_step crossing happens on EVERY step of this entry's profile
#:   masked   download is lane-masked (only the lanes that need it)
#:   cached   upload is memoized until invalidated (not per-step)
#: ``resident`` names the contract classes an entry takes from the device
#: and never from the host: the engine keeps them between rounds in
#: their resident form (kstate.py ``ResidentState``; the mesh backend's
#: carried inbox one [G, Wi] array) and every program that reads or
#: writes them converts inside itself.
#: ``fleet_digest`` is the every-tenth-round collection (core/digest.py):
#: the three reports ride ONE int32 vector down, the two digests stay
#: resident as the columns of one carried [G, 17] array; its second upload
#: is the rare reset of the lanes whose occupant changed (``[3, N]`` cells
#: of that array, the invariant digest's age).
#: ``_control`` rows are step-loop control-plane crossings (admissions,
#: membership, lane clearing, telemetry) that belong to no single entry;
#: a ``[3, N] i32`` value is ``_write_cells``' one upload (row, column,
#: value of N cells of the packed columns, at its least size class).
TRANSFER_LEDGER = {
    "step": {
        "resident": ("ShardState",),
        "up": (
            {"value": ("Inbox", "StepInput"), "packed": True,
             "param": "up", "site": "_RoundStaging.to_device",
             "tag": "round_up", "per_step": True},
        ),
        "down": (
            {"value": ("[G, 8] bool", "StepOutput", "[G, S] i32"),
             "packed": True, "site": "KernelEngine._process_outputs",
             "tag": "round_down", "per_step": True},
            {"value": "[1, CAP] i32",
             "site": "KernelEngine._save_terms",
             "tag": "save_window_row", "per_step": False, "masked": True},
        ),
    },
    "step_donated": {
        "resident": ("ShardState",),
        "up": (
            {"value": ("Inbox", "StepInput"), "packed": True,
             "param": "up", "site": "_RoundStaging.to_device",
             "tag": "round_up", "per_step": True},
        ),
        "down": (
            {"value": ("[G, 8] bool", "StepOutput", "[G, S] i32"),
             "packed": True, "site": "KernelEngine._process_outputs",
             "tag": "round_down", "per_step": True},
            {"value": "[1, CAP] i32",
             "site": "KernelEngine._save_terms",
             "tag": "save_window_row", "per_step": False, "masked": True},
        ),
    },
    "serve_step": {
        "resident": ("ShardState", "Inbox"),
        "up": (
            {"value": ("Inbox", "StepInput"), "packed": True,
             "param": "up", "site": "_RoundStaging.to_device",
             "tag": "round_up", "per_step": True},
            {"value": "[G, P] bool", "param": "cut",
             "site": "MeshDispatch.dispatch", "tag": "cut_up",
             "per_step": False, "cached": True},
        ),
        "down": (
            {"value": ("[G, 8] bool", "StepOutput", "[G, S] i32"),
             "packed": True, "site": "KernelEngine._process_outputs",
             "tag": "round_down", "per_step": True},
            {"value": "[1, CAP] i32",
             "site": "KernelEngine._save_terms",
             "tag": "save_window_row", "per_step": False, "masked": True},
        ),
    },
    "serve_step_donated": {
        "resident": ("ShardState", "Inbox"),
        "up": (
            {"value": ("Inbox", "StepInput"), "packed": True,
             "param": "up", "site": "_RoundStaging.to_device",
             "tag": "round_up", "per_step": True},
            {"value": "[G, P] bool", "param": "cut",
             "site": "MeshDispatch.dispatch", "tag": "cut_up",
             "per_step": False, "cached": True},
        ),
        "down": (
            {"value": ("[G, 8] bool", "StepOutput", "[G, S] i32"),
             "packed": True, "site": "KernelEngine._process_outputs",
             "tag": "round_down", "per_step": True},
            {"value": "[1, CAP] i32",
             "site": "KernelEngine._save_terms",
             "tag": "save_window_row", "per_step": False, "masked": True},
        ),
    },
    "fleet_digest": {
        "resident": ("ShardState", "HealthDigest", "InvariantDigest"),
        "up": (
            {"value": "[G, K] i32", "param": "inbox_from",
             "site": "SerialDispatch.digest_inbox",
             "tag": "digest_down", "per_step": False},
            {"value": "[3, 16] i32",
             "site": "KernelEngine._collect_digest",
             "tag": "digest_down", "per_step": False},
        ),
        "down": (
            {"value": ("FleetStats", "HealthReport", "InvariantReport"),
             "packed": True, "site": "KernelEngine._collect_digest",
             "tag": "digest_down", "per_step": False},
        ),
    },
    "_control": (
        {"value": "ShardState", "dir": "up",
         "site": "KernelEngine._flush_injections", "tag": "inject_up",
         "per_step": False},
        {"value": "[3, 32] i32", "dir": "up",
         "site": "KernelEngine._write_cells",
         "tag": "membership_up", "per_step": False},
        {"value": "[3, 16] i32", "dir": "up",
         "site": "KernelEngine._write_cells",
         "tag": "lane_clear_up", "per_step": False},
        {"value": "ShardRow", "dir": "down",
         "site": "KernelEngine.health_row", "tag": "health_row",
         "per_step": False},
        {"value": "[G] i32", "dir": "down",
         "site": "KernelEngine._witness_snapshot", "tag": "wit_snap_floor",
         "per_step": False},
    ),
}


_ENTRY_ARRAYS = telemetry.GLOBAL.gauge(
    "engine_entry_arrays",
    help="device arrays the round's jitted entry takes (in) and returns "
         "(out), flattened: set by each dispatch backend at its first "
         "call.  What a round lets go of costs its thread one wait for "
         "the interpreter per array",
    labelnames=("dir",))


def _note_entry_arrays(args, results) -> tuple[int, int]:
    n = (len(jax.tree.leaves(args)), len(jax.tree.leaves(results)))
    _ENTRY_ARRAYS.labels("in").set(n[0])
    _ENTRY_ARRAYS.labels("out").set(n[1])
    return n


class SerialDispatch:
    """Single-device backend: the whole inbox re-staged from host every
    round, inside the one packed upload."""

    def __init__(self, kp: KP.KernelParams, step_fn=None) -> None:
        self.kp = kp
        # the kernel step the round's program wraps: the engine binds ITS
        # module global (chaos tests swap a mutated kernel in there)
        self._step_fn = step_fn if step_fn is not None else kernel_step
        # per-instance telemetry wrappers (own counters): a first
        # compile at THIS engine's geometry is never mistaken for a
        # retrace of another engine sharing the jitted function
        self.entries = {
            "step": _capacity.TRACKER.wrap("step", round_step),
            "step_donated": _capacity.TRACKER.wrap(
                "step_donated", round_step_donated),
        }
        #: (in, out) device arrays of the entry, from its first call
        self.entry_arrays: tuple[int, int] | None = None

    def dispatch(self, state, staging, donate: bool):
        """One round's device work: upload ``staging``, run the jitted
        entry on the resident ``state``; returns ``(state, down)`` with
        ``down`` the packed download, still on the device.
        ``donate=True`` routes through the donating entry (core/round.py
        ``step_donated``): XLA reuses the state's buffers, so after this
        call the host must not read the passed-in state again —
        step_all's retire-before-dispatch order upholds that."""
        entry = self.entries["step_donated" if donate else "step"]
        args = (state, staging.to_device())
        res = entry(self.kp, self._step_fn, *args)
        if self.entry_arrays is None:
            self.entry_arrays = _note_entry_arrays(args, res)
        return res

    def pending(self) -> bool:
        """No device-resident inbox: nothing carries between steps."""
        return False

    def note_output_flags(self, rows) -> None:
        """No carried inbox, so retired activity flags carry no drain
        information here; MeshDispatch derives pending() from them."""

    def inbox_from(self, inbox_buf):
        """[G, K] sender ids for the inbox-occupancy histogram — the
        host-staged builder is the inbox here."""
        return inbox_buf.from_

    def digest_inbox(self, inbox_buf):
        """The collection's inbox argument: the host-staged sender ids as
        ONE explicit upload (handed over as a numpy array they went up
        once for every program that took them)."""
        return jnp.asarray(inbox_buf.from_)

    def shard(self, tree):
        """Single device: placement is a no-op."""
        return tree

    def placement(self):
        """The sharding a program that rewrites resident arrays asks for
        its results: none on a single device."""
        return None

    def resident_trees(self) -> tuple:
        return ()

    def resident_classes(self) -> tuple:
        return ()


#: FLAG_CLASSES columns that carry inter-replica messages — the classes
#: whose routed traffic keeps the mesh draining (need_snapshot/wit_snap/
#: rtr are host-escalation signals, not inbox content)
_MSG_FLAG_COLS = [FLAG_CLASSES.index(c)
                  for c in ("resp", "rep", "hb", "vote", "timeout_now")]


class MeshDispatch:
    """shard_map backend over a ``Mesh(('g','r'))``: messages ride the
    mesh inside the step (parallel/ici.py), the inbox is device-resident
    between steps, and a per-link cut mask decides which links the mesh
    serves — traffic for cut links (and off-mesh peers) rides the host
    hub and is merged back into the carried inbox at its route() slot
    (parallel/round.py)."""

    def __init__(self, cluster: IciCluster) -> None:
        self.cluster = cluster
        total = cluster.total_rows
        # device-resident inbox carried between steps (messages ride
        # the mesh, not the host queues): ONE [G, Wi] int32 array in the
        # upload's own inbox-column layout
        self._box = cluster.shard(
            np.zeros((total, inbox_columns(cluster.kp)[1]), np.int32))
        # drain-pending, derived host-side from the [G, C] activity
        # flags the step loop already fetches every step — the round-16
        # per-step pending-scalar download is gone
        self._pending_msgs = False
        # per-link cut mask [rows, num_peers]: cut[row, p] severs the
        # mesh link between the row and its group peer rid p+1 (mesh
        # addressing pins peer slot p to rid p+1).  Device copy cached
        # until the mask changes.
        self.cut = np.zeros((total, cluster.kp.num_peers), bool)
        self._cut_dev = None
        self.entries = {
            "serve_step": _capacity.TRACKER.wrap(
                "serve_step", jit_serve_step),
            "serve_step_donated": _capacity.TRACKER.wrap(
                "serve_step_donated", jit_serve_step_donated),
        }
        self.entry_arrays: tuple[int, int] | None = None

    def dispatch(self, state, staging, donate: bool):
        """Advance the mesh: host-staged inputs, device-routed messages.
        Kernel-family traffic between mesh rows rides the exchange
        inside the step; the upload's inbox columns carry ONLY
        hub-fallback deliveries (cut links, off-mesh senders), staged
        slot-exact by _InboxBuilder and merged into the carried inbox
        inside the entry, before the step.  ``donate=True`` hands state
        and the carried inbox to XLA (kstate.DONATION
        ``round_serve_step_donated``); the cached cut mask is never
        donated.  Returns ``(state, down)`` like the serial backend."""
        cl = self.cluster
        up = staging.to_device(cl.sharding())
        if self._cut_dev is None:
            with _capacity.METER.sanctioned("cut_up") as crossing:
                crossing.moved(self.cut)
                self._cut_dev = jax.device_put(self.cut, cl.sharding())
        entry = self.entries["serve_step_donated" if donate
                             else "serve_step"]
        args = (state, self._box, up, self._cut_dev)
        res = entry(cl.kp, cl, *args)
        if self.entry_arrays is None:
            self.entry_arrays = _note_entry_arrays(args, res)
        state, self._box, down = res
        return state, down

    @property
    def box(self):
        """The carried inbox as an Inbox of device arrays, unpacked on
        demand and placed like the carried array (for callers outside a
        round, like ``engine.state``)."""
        return box_view_program(
            self.cluster.kp, self.cluster.sharding())(self._box)

    def pending(self) -> bool:
        return self._pending_msgs

    def note_output_flags(self, rows) -> None:
        """Derive drain-pending from the retired step's candidate rows
        (``_Retiring.cells``: lists, already host-side, whose leading
        cells are the activity flags; a row that is no candidate has no
        flag set): any messaging class set means the exchange routed
        traffic into the carried inbox (or the hub is about to carry it),
        so the next step has work.  Conservative under cut links — flags
        are computed from the unmasked output, so a fully-cut row costs
        at most one idle step — and never an undercount: the carried
        inbox only ever holds routed copies of flagged output lanes."""
        self._pending_msgs = any(
            row[c] for row in rows for c in _MSG_FLAG_COLS)

    def inbox_from(self, inbox_buf):
        # the mesh inbox is device-resident between steps; no host copy
        # (one slice of the carried array, for callers outside a round)
        return box_from(self.cluster.kp, self._box)

    def digest_inbox(self, inbox_buf):
        """The collection's inbox argument: the carried [G, Wi] array as
        it is; the program slices the sender ids out itself."""
        return self._box

    def shard(self, tree):
        """Place a [G]-leading pytree onto the mesh (digests and the
        like shard along G exactly like the state they derive from)."""
        return self.cluster.shard(tree)

    def placement(self):
        """The sharding a program that rewrites resident arrays asks for
        its results: every [G]-leading array stays sharded as the serve
        entry takes and returns it."""
        return self.cluster.sharding()

    def set_cut(self, lane: int, cut: bool) -> None:
        """Flip one row's WHOLE partition mask (every link of the row)
        and invalidate the cached device copy (next dispatch re-stages
        it).  This is the chaos PartitionNode surface: the row neither
        sends nor receives on the mesh."""
        self.cut[lane, :] = cut
        self._cut_dev = None

    def set_link_cut(self, lane: int, peer_rid: int, cut: bool) -> None:
        """Flip ONE directed half-link: row ``lane`` stops exchanging
        with group peer rid ``peer_rid`` over the mesh.  Callers must
        cut links symmetrically (both endpoints) — hub fallback relies
        on the peer's sender-side mask to emit its half over the host."""
        self.cut[lane, peer_rid - 1] = cut
        self._cut_dev = None

    def resident_trees(self) -> tuple:
        # the carried inbox is device-resident between steps here
        return (self._box,)

    def resident_classes(self) -> tuple:
        return ("Inbox",)
