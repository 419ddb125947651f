"""The serial engine round as ONE jitted program: the resident state and
the packed upload in, the new resident state and the packed download out.

``step`` / ``step_donated`` here are what ``SerialDispatch`` serves (their
names are the program names a device capture shows, ``jit_step`` /
``jit_step_donated``); ``core/kernel.py``'s entries of the same names stay
the plain ``(state, Inbox, StepInput) -> (state, StepOutput)`` step the
differentials and the benchmark's shape accounting call, and the
``step_fn`` wrapped here (a static argument: the engine's kernel step, or
a chaos test's mutated one).  ``state`` here is the resident form
(kstate.py ``ResidentState``: three arrays, not a ShardState's 45), so
the entry takes 4 device arrays and returns 4: what a round lets go of
is what its thread waits for the interpreter over, one array at a time.
The layouts of all packed arrays are kstate.py's column table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dragonboat_tpu.core.kernel import output_row_flags
from dragonboat_tpu.core.kstate import (
    ACTIVE_LEADER,
    ACTIVE_OUTPUT,
    ACTIVE_TRIPLE,
    pack_download,
    pack_state,
    round_columns,
    unpack_state,
    unpack_upload,
)

I32 = jnp.int32


def ring_window(ring, first, size: int):
    """[G, size]: ``ring[g, (first[g] + j) & (CAP - 1)]`` for ``j < size``
    (``size`` a power of two dividing CAP).  Gather-free, like the kernel's
    one-hot reads (a batched gather serialises over [G] on the TPU): the
    window lies in two adjacent ``size``-blocks of the ring, picked by a
    one-hot over the blocks, then rotated into place by the bits of the
    offset."""
    G, cap = ring.shape
    nb = cap // size
    start = first & (cap - 1)
    b, r = start // size, start % size
    blocks = ring.reshape(G, nb, size)
    ids = jnp.arange(nb, dtype=I32)[None, :, None]

    def pick(k):
        return jnp.sum(jnp.where(ids == k[:, None, None], blocks, 0), axis=1)

    two = jnp.concatenate([pick(b), pick((b + 1) % nb)], axis=1)
    sh = 1
    while sh < size:
        two = jnp.where(((r & sh) != 0)[:, None],
                        jnp.roll(two, -sh, axis=1), two)
        sh <<= 1
    return two[:, :size]


def row_activity(flags, out, was, now):
    """[G] int32, the download's ``active`` column (kstate.py ``ACTIVE_*``):
    which rows the host has to retire, and why.  ``was`` is the state the
    step was given and ``now`` the one it returned: the host retires every
    row whose triple or leader moved in the round it moved in, so "moved in
    this step" is "differs from what the host last saw" and the host keeps
    no copy to compare with (the one row no step accounts for, a replica
    placed at a term > 0, the engine names itself: ``ctx.injected``).
    Elementwise over [G] (shards along G with no collective).  Nothing
    here asks whether a replica is placed in the row: a row's peer book
    does not say (a replica that joins is placed with an empty one and
    answers its leader all the same), a row that gets no tick and no
    message moves nothing and reads 0, and the host drops a named row it
    holds no replica in (a vacated mesh row a peer still writes to)."""
    output = (jnp.any(flags, axis=1) | out.ri_dropped | out.needs_host
              | (out.save_last >= out.save_first)
              | (out.apply_last >= out.apply_first))
    term = now.term != was.term
    triple = term | (now.vote != was.vote) | (now.committed != was.committed)
    leader = term | (now.leader != was.leader)
    return (jnp.where(output, ACTIVE_OUTPUT, 0)
            | jnp.where(triple, ACTIVE_TRIPLE, 0)
            | jnp.where(leader, ACTIVE_LEADER, 0)).astype(I32)


def pack_round(kp, was, state, out):
    """The round's download: the activity flags, the ``active`` column
    (``was``: the state the step was given), every StepOutput field and,
    from the state the step returned, the terms of the ``S`` ring entries
    from ``save_first`` on (what ``_build_update`` persists)."""
    terms = ring_window(state.lt, out.save_first, round_columns(kp).save_window)
    flags = output_row_flags(out)
    return pack_download(kp, flags, row_activity(flags, out, was, state),
                         out, terms)


def _round(kp, step_fn, state, up):
    inbox, inp = unpack_upload(kp, up)
    was = unpack_state(kp, state)
    s, out = step_fn(kp, was, inbox, inp)
    return pack_state(kp, s), pack_round(kp, was, s, out)


@functools.partial(jax.jit, static_argnums=(0, 1))
def step(kp, step_fn, state, up):
    """One round, non-donating (depth 0): ``state`` is the resident form,
    ``up`` the staged [G, Wu] upload; returns ``(state, down)`` with
    ``down`` the [G, Wd] download."""
    return _round(kp, step_fn, state, up)


# The donating twin for the pipelined loop (kstate.DONATION
# ``round_step_donated``): only the state is donated — the upload matches
# no output's shape, and the download is an output, so a deferred retire
# reads nothing XLA was handed.
@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def step_donated(kp, step_fn, state, up):
    return _round(kp, step_fn, state, up)


@jax.jit
def ring_row(ring, g):
    """One lane's whole [CAP] ring row, ``g`` traced: the fixed-shape
    fallback for a save window wider than ``S``."""
    return ring[g]


@jax.jit
def state_cell(cols, g, c):
    """One cell of the resident columns, ``g`` and ``c`` traced: a lane's
    scalar field (kstate.py ``state_columns`` names the column)."""
    return cols[g, c]
