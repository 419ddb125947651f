"""Bytes one chip receives in one mesh step's message exchange.

``parallel/ici.py`` ``_exchange`` all-gathers the step's out-lanes over the
replica axis and routes them on every chip (``core/router.py`` ``route``).
The out-lanes ``route`` never reads are dead code to the compiler, so what
crosses the interconnect is the fields below, each at the per-chip shape
the engine serves (``[n_local, ...]``), once from each of the other
``replicas - 1`` chips.  The compiler may gather less still (it slices and
fuses before some gathers): for the described 1x3 v5e it moves 57,312 B
where this counts 68,064 B; ``tests/test_chip_compile.py`` holds the two
within a third of each other, so a field ``route`` starts to read is
missed by neither.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import numpy as np

from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.core import kernel
from dragonboat_tpu.core.kstate import empty_inbox, empty_input, init_state
from dragonboat_tpu.nodehost import NodeHost

from benchmark import peaks

#: the StepOutput fields ``route`` reads (``s_ent_val`` rides only where the
#: kernel carries payloads inline; the benchmark's deployments do not)
EXCHANGED = (
    "term",
    "r_type", "r_to", "r_term", "r_log_index", "r_reject", "r_hint",
    "r_hint_high",
    "s_rep", "s_prev_index", "s_prev_term", "s_commit", "s_n_ent",
    "s_ent_term", "s_ent_cc", "s_ent_val",
    "s_vote", "s_vote_term", "s_vote_lindex", "s_vote_lterm", "s_vote_hint",
    "s_hb", "s_hb_commit", "s_hb_low", "s_hb_high", "s_timeout_now",
)


def kernel_params(config: dict):
    """The KernelParams a NodeHost of this configuration gives its mesh
    engine (``NodeHost._inject_mesh_shard``)."""
    host = SimpleNamespace(config=SimpleNamespace(
        expert=ExpertConfig(**config.get("expert", {}))))
    return NodeHost._kernel_params(
        host, min_inbox=5 * (int(config["mesh"]["replicas"]) - 1))


def exchange_bytes_per_chip(kp, n_local: int) -> int:
    """Bytes of the exchanged out-lanes of one chip's ``n_local`` rows."""
    peers = np.zeros((n_local, kp.num_peers), np.int32)
    _state, out = jax.eval_shape(
        functools.partial(kernel.step, kp),
        *jax.eval_shape(lambda: (
            init_state(kp, n_local, 1, peers),
            empty_inbox(kp, n_local), empty_input(kp, n_local))))
    return sum(peaks.leaf_bytes_per_device(getattr(out, name))
               for name in EXCHANGED if getattr(out, name) is not None)


def received_per_step(config: dict) -> int:
    """Bytes one chip receives in one step of the configuration's mesh."""
    mesh = config["mesh"]
    return (int(mesh["replicas"]) - 1) * exchange_bytes_per_chip(
        kernel_params(config), int(mesh["n_local"]))
