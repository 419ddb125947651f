"""The mesh engine round as ONE jitted program (the shard_map twin of
core/round.py): the resident state, the carried inbox and the packed
upload in; the new resident state, the carried inbox and the packed
download out.  The carried inbox is ONE [G, Wi] int32 array in the
upload's own inbox-column layout (kstate.py ``inbox_columns``), so the
entry takes 6 device arrays and returns 5.

``jit_serve_step`` / ``jit_serve_step_donated`` here are what
``MeshDispatch`` serves (a device capture shows them under the same
program names as parallel/ici.py's entries, which stay the unpacked
``(state, box, StepInput, cut)`` serving step the differentials, the HLO
budget and the benchmark's shape accounting call).  Everything added
around ``ici.serve_body`` is per-row, so it runs inside the shard_map on
each device's own rows: no collective beyond the step's own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from dragonboat_tpu.core.kstate import (
    Inbox,
    box_senders,
    inbox_columns,
    pack_columns,
    pack_state,
    unpack_columns,
    unpack_state,
    unpack_upload,
)
from dragonboat_tpu.core.round import pack_round
from dragonboat_tpu.parallel.ici import IciCluster, serve_body, shard_map


def _round_body(kp, replicas, state, box, up, cut):
    # hub-fallback deliveries (cut links, off-mesh senders) were staged
    # slot-exact by the host; a staged slot replaces the carried one
    staged, inp = unpack_upload(kp, up)
    box_cols, _ = inbox_columns(kp)
    live = staged.mtype != 0
    box = jax.tree.map(
        lambda s, b: jnp.where(
            live.reshape(live.shape + (1,) * (s.ndim - 2)), s, b),
        staged, unpack_columns(Inbox, box_cols, box))
    was = unpack_state(kp, state)
    s, box, out = serve_body(kp, replicas, was, box, inp, cut)
    return (pack_state(kp, s), pack_columns(box_cols, box._asdict()),
            pack_round(kp, was, s, out))


def _round(kp, cluster: IciCluster, state, box, up, cut):
    rows = PS(("g", "r"))
    body = shard_map(
        functools.partial(_round_body, kp, cluster.replicas),
        mesh=cluster.mesh,
        in_specs=(rows, rows, rows, PS(("g", "r"), None)),
        out_specs=(rows, rows, rows),
    )
    return body(state, box, up, cut)


@functools.partial(jax.jit, static_argnums=(0, 1))
def jit_serve_step(kp, cluster: IciCluster, state, box, up, cut):
    """One mesh round, non-donating (depth 0): ``state`` is the resident
    form and ``box`` the carried [G, Wi] inbox, both sharded along G like
    the staged [G, Wu] upload ``up``; ``cut`` is the per-link mask;
    returns ``(state, box, down)``."""
    return _round(kp, cluster, state, box, up, cut)


# The donating twin (kstate.DONATION ``round_serve_step_donated``): state
# and the carried inbox are donated; the upload matches no output's shape
# and the cached cut mask outlives the step.
@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def jit_serve_step_donated(kp, cluster: IciCluster, state, box, up, cut):
    return _round(kp, cluster, state, box, up, cut)


@functools.partial(jax.jit, static_argnums=(0,))
def box_from(kp, box):
    """[G, K] sender ids of the carried inbox, for callers outside a round
    (a lane's health row, the chaos oracle; the every-tenth-round
    collection slices them out inside its own program, core/digest.py)."""
    return box_senders(kp, box)
