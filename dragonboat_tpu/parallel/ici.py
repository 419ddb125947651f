"""ICI transport: cross-chip replica groups via shard_map + collectives.

The reference's replicas talk over a framed TCP transport
(``internal/transport/tcp.go:64-394``); when every replica of a group is a
row of the same SPMD program, that transport seam collapses into an
``all_gather`` of the step's fixed-width out-lanes over the mesh's replica
axis — the message blocks ride ICI, and the per-address circuit breakers /
send queues disappear because delivery is the collective itself.

Layout
------
Mesh ``('g', 'r')``: axis ``r`` has one device per replica slot (R total);
axis ``g`` block-parallelizes disjoint group sets (no communication).  The
global state has leading dim ``G = g_size * R * n_local`` laid out
block-major: row ``((ig * R) + ir) * n_local + n`` is replica ``ir+1`` of
group ``ig * n_local + n``, so a flat ``P(('g', 'r'))`` sharding gives
device ``(ig, ir)`` the ``n_local`` rows of its replica slot.

Each step: local batched raft step → ``all_gather`` out-lanes over ``'r'``
→ rebuild the grouped ``[n_local * R]`` view → reuse the single-device
router → keep the rows addressed to my replica slot.  Correctness therefore
reduces to the router's (tests/test_device_router.py); these collectives
only change *where* the lanes live.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


from dragonboat_tpu.core import params as KP
from dragonboat_tpu.core.kernel import step
from dragonboat_tpu.core.kstate import (
    Inbox,
    ShardState,
    StepInput,
    StepOutput,
    empty_inbox,
    init_state,
)
from dragonboat_tpu.core.router import route


@dataclass(frozen=True)
class IciCluster:
    """Static geometry of a mesh-sharded cluster."""

    kp: KP.KernelParams
    mesh: Mesh
    replicas: int        # R — size of mesh axis 'r'
    n_local: int         # groups per device
    num_groups: int      # total groups = g_size * n_local

    @property
    def g_size(self) -> int:
        return self.mesh.shape["g"]

    @property
    def total_rows(self) -> int:
        return self.g_size * self.replicas * self.n_local

    def sharding(self) -> NamedSharding:
        """Rows over the mesh, spelled the way a jitted entry spells the
        arrays it returns: no mesh axis of size one, no trailing ``None``.
        Shardings that differ only in spelling compare unequal, so state
        placed as ``P(('g', 'r'), None)`` made the serve entry compile
        once for the placed arrays and again for its own outputs
        (tests/test_mesh_cell.py holds state and inbox to this spelling)."""
        axes = tuple(a for a in ("g", "r") if self.mesh.shape[a] > 1)
        if not axes:
            return NamedSharding(self.mesh, PS())
        return NamedSharding(
            self.mesh, PS(axes[0] if len(axes) == 1 else axes))

    def shard(self, tree):
        """Place a [G]-leading pytree onto the mesh."""
        sharding = self.sharding()
        return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def make_ici_cluster(
    kp: KP.KernelParams,
    mesh: Mesh,
    num_groups: int,
    election: int = 10,
) -> tuple[IciCluster, ShardState, Inbox]:
    """Build a cluster whose replica axis spans mesh axis 'r'.

    ``num_groups`` must divide evenly over mesh axis 'g'."""
    R = mesh.shape["r"]
    g_size = mesh.shape["g"]
    assert num_groups % g_size == 0, "num_groups must divide mesh axis g"
    n_local = num_groups // g_size
    cluster = IciCluster(kp=kp, mesh=mesh, replicas=R, n_local=n_local,
                         num_groups=num_groups)

    # block-major replica-id layout (see module docstring)
    rids = np.empty((cluster.total_rows,), np.int32)
    for ig in range(g_size):
        for ir in range(R):
            lo = (ig * R + ir) * n_local
            rids[lo:lo + n_local] = ir + 1
    pids = np.arange(1, R + 1, dtype=np.int32)
    state = init_state(kp, cluster.total_rows, rids, pids,
                       election_timeout=election)
    box = empty_inbox(kp, cluster.total_rows)
    return cluster, cluster.shard(state), cluster.shard(box)


def _exchange(kp: KP.KernelParams, R: int, n_local: int,
              out: StepOutput) -> Inbox:
    """Collective message exchange: all_gather the out-lanes over the
    replica axis, rebuild the grouped view, reuse the single-device
    router, keep the rows addressed to my replica slot."""
    gathered = jax.tree.map(
        lambda x: jax.lax.all_gather(x, "r", axis=0), out
    )

    def to_grouped(x):  # [R, n_local, ...] -> [n_local * R, ...] group-major
        if x is None:  # optional lanes (e.g. s_ent_val without payloads)
            return None
        x = jnp.swapaxes(x, 0, 1)
        return x.reshape((n_local * R,) + x.shape[2:])

    out_full = StepOutput(*[to_grouped(f) for f in gathered])
    box_full = route(kp, R, out_full)          # [n_local * R, ...] grouped
    t = jax.lax.axis_index("r")

    def mine(x):  # keep rows addressed to my replica slot
        g = x.reshape((n_local, R) + x.shape[1:])
        return jax.lax.dynamic_index_in_dim(g, t, axis=1, keepdims=False)

    return jax.tree.map(mine, box_full)


def _ici_body(kp: KP.KernelParams, replicas: int,
              state: ShardState, box: Inbox, inp: StepInput):
    """shard_map body: local [n_local] step + collective message exchange."""
    state, out = step(kp, state, box, inp)
    box = _exchange(kp, replicas, state.term.shape[0], out)
    return state, box, out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _jit_ici_step(kp, cluster: IciCluster, state, box, inp):
    body = shard_map(
        functools.partial(_ici_body, kp, cluster.replicas),
        mesh=cluster.mesh,
        in_specs=(PS(("g", "r")), PS(("g", "r")), PS(("g", "r"))),
        out_specs=(PS(("g", "r")), PS(("g", "r")), PS(("g", "r"))),
    )
    return body(state, box, inp)


def ici_cluster_step(cluster: IciCluster, state: ShardState, box: Inbox,
                     inp: StepInput):
    """One cluster step with cross-chip message routing.

    Equivalent of router.cluster_step for mesh-resident replicas; the
    transport seam (raftio.ITransport) is the all_gather inside."""
    return _jit_ici_step(cluster.kp, cluster, state, box, inp)


def _mask_outgoing(out: StepOutput, cut: jnp.ndarray) -> StepOutput:
    """Zero the out-lanes addressed over cut LINKS.

    ``cut`` is the per-link mask ``[G, num_peers] bool``: ``cut[g, p]``
    severs the mesh link between row ``g`` and its group peer rid
    ``p + 1`` (mesh addressing pins peer slot ``p`` to rid ``p + 1``, so
    the column index doubles as the slot index).  A whole-True row is
    the old per-lane partition (monkey.go:170 PartitionNode): the row
    sends nothing on the mesh, but still ticks, persists and applies.
    A single column is the round-17 hub-fallback surface: traffic for
    that link leaves the mesh and rides the host hub instead
    (MeshEngine._link_mask)."""
    P = cut.shape[1]

    def zpeer(a):  # [G, P(, E)] peer-slot lanes: zero slot p where cut
        c = cut.reshape(cut.shape + (1,) * (a.ndim - 2))
        return jnp.where(c, jnp.zeros_like(a), a)

    # response lanes are addressed by rid, not slot: lane k of row g is
    # masked when the link to its destination rid is cut.  One-hot
    # compare + any, NOT take_along_axis: a per-lane gather here would
    # breach the mesh HLO budget (analysis/hlo_budget.json gates them)
    rid = jnp.arange(1, P + 1, dtype=out.r_to.dtype)
    cut_to = jnp.any(
        (out.r_to[:, :, None] == rid) & cut[:, None, :], axis=-1)  # [G, K]
    return out._replace(
        r_type=jnp.where(cut_to, jnp.zeros_like(out.r_type), out.r_type),
        s_rep=zpeer(out.s_rep), s_hb=zpeer(out.s_hb),
        s_vote=zpeer(out.s_vote), s_timeout_now=zpeer(out.s_timeout_now),
    )


def _mask_incoming(box: Inbox, cut: jnp.ndarray) -> Inbox:
    """Zero inbox slots whose SOURCE arrives over a cut link.  Every
    field is zeroed, not just the type: the kernel's inbox contract is
    route()'s (invalid slots are all-zero), and a slot with mtype=0 but
    a live term would still feed term adoption (caught by
    tests/test_mesh_differential.py)."""
    P = cut.shape[1]
    # one-hot source match (gather-free, like _mask_outgoing); from_=0
    # (empty slot) matches no rid and stays untouched
    rid = jnp.arange(1, P + 1, dtype=box.from_.dtype)
    cut_src = jnp.any(
        (box.from_[:, :, None] == rid) & cut[:, None, :], axis=-1)  # [G, K]
    return jax.tree.map(
        lambda x: jnp.where(
            cut_src.reshape(cut_src.shape + (1,) * (x.ndim - 2)),
            jnp.zeros_like(x), x),
        box,
    )


def serve_body(kp: KP.KernelParams, replicas: int,
                state: ShardState, box: Inbox, inp: StepInput,
                cut: jnp.ndarray):
    """shard_map body for the SERVING path: host-staged StepInput, a
    device-resident inbox carried between steps, and a per-link cut
    mask reserving the host hub for cut / off-mesh links.

    Returns (state, next_box, out).  The round-16 ``pending`` scalar
    (a per-step device->host crossing) is gone: the host derives
    drain-pending from the [G, C] activity flags it already fetches
    every step (MeshDispatch.note_output_flags), so the serving step
    downloads nothing beyond the round's one packed array
    (parallel/round.py wraps this body)."""
    state, out = step(kp, state, box, inp)
    box = _exchange(kp, replicas, state.term.shape[0],
                    _mask_outgoing(out, cut))
    # symmetric receive-side masking: with BOTH endpoints of a cut link
    # masked, a one-sided (asymmetric) mask update can never leak a
    # message across a link the host already re-routed over the hub
    box = _mask_incoming(box, cut)
    return state, box, out


@functools.partial(jax.jit, static_argnums=(0, 1))
def jit_serve_step(kp, cluster: IciCluster, state, box, inp, cut):
    """Jitted serving entry (non-donated): the depth-0 mesh oracle the
    engine dispatch layer wraps in compile telemetry.  ``cut`` is the
    per-link mask ``[G, num_peers] bool`` (see ``_mask_outgoing``)."""
    body = shard_map(
        functools.partial(serve_body, kp, cluster.replicas),
        mesh=cluster.mesh,
        in_specs=(PS(("g", "r")), PS(("g", "r")), PS(("g", "r")),
                  PS(("g", "r"), None)),
        out_specs=(PS(("g", "r")), PS(("g", "r")), PS(("g", "r"))),
    )
    return body(state, box, inp, cut)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3, 4))
def jit_serve_step_donated(kp, cluster: IciCluster, state, box, inp, cut):
    """Donating twin of ``jit_serve_step`` for the pipelined dispatch:
    state, the carried inbox and the staged input hand their buffers to
    XLA (kstate.DONATION ``serve_step_donated``; host no-touch rule
    applies after dispatch).  ``cut`` is NOT donated — the engine caches
    the device copy of the per-link mask across steps."""
    body = shard_map(
        functools.partial(serve_body, kp, cluster.replicas),
        mesh=cluster.mesh,
        in_specs=(PS(("g", "r")), PS(("g", "r")), PS(("g", "r")),
                  PS(("g", "r"), None)),
        out_specs=(PS(("g", "r")), PS(("g", "r")), PS(("g", "r"))),
    )
    return body(state, box, inp, cut)


def ici_serve_step(cluster: IciCluster, state: ShardState, box: Inbox,
                   inp: StepInput, cut):
    """One serving step: kernel + in-mesh routing + per-link cut mask.

    The mesh-engine equivalent of router.cluster_step — the transport
    seam (transport.go:86-101) is the all_gather inside the body."""
    return jit_serve_step(cluster.kp, cluster, state, box, inp, cut)


def self_driving_input(kp: KP.KernelParams, state: ShardState,
                       tick: bool = True, propose: bool = True) -> StepInput:
    """bench_loop.full_step's feedback shape for sharded state: proposals on
    leaders, instant-apply RSM cursor, logical clock ticking."""
    G, B = state.term.shape[0], kp.proposal_cap
    is_leader = state.role == KP.LEADER
    pv = jnp.broadcast_to(is_leader[:, None], (G, B)) & jnp.asarray(propose)
    z = lambda: jnp.zeros((G,), jnp.int32)  # noqa: E731
    return StepInput(
        prop_valid=pv,
        prop_cc=jnp.zeros((G, B), bool),
        ri_valid=jnp.zeros((G,), bool),
        ri_low=z(),
        ri_high=z(),
        transfer_to=z(),
        tick=jnp.broadcast_to(jnp.asarray(tick, bool), (G,)),
        quiesced=jnp.zeros((G,), bool),
        applied=state.processed,
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def ici_run_steps(kp, cluster: IciCluster, iters: int, propose: bool,
                  state, box):
    """iters self-driving sharded steps under one jit (bench inner loop)."""
    body_fn = functools.partial(_ici_body, kp, cluster.replicas)

    def one(st, bx):
        inp = self_driving_input(kp, st, tick=True, propose=propose)
        st, bx, _ = body_fn(st, bx, inp)
        return st, bx

    def sharded(st, bx):
        return jax.lax.fori_loop(
            0, iters, lambda _, c: one(*c), (st, bx)
        )

    return shard_map(
        sharded,
        mesh=cluster.mesh,
        in_specs=(PS(("g", "r")), PS(("g", "r"))),
        out_specs=(PS(("g", "r")), PS(("g", "r"))),
    )(state, box)
