"""Pallas kernels for the device-resident fabric's hot gather shapes.

Round 17 moves co-located consensus traffic onto the mesh
(parallel/ici.py), which leaves two gather-shaped selects on the
serving path's critical loop:

  1. **inbox staging** — picking response lanes by a per-row lane
     index (core/router.route's ``pick``), an ``[G, K]`` batched
     gather that XLA serializes over the batch axis on TPU (the same
     pathology kernel._get1 documents);
  2. **quorum match** — the q-th largest match among voting members
     (core/kernel._sorted_match_quorum_index), which XLA lowers as a
     full ``jnp.sort`` plus a gather even though only ONE order
     statistic is consumed.

Each kernel holds its row block in VMEM and stays VPU-shaped (one-hot
compares + reductions, no gathers/scatters — the raft kernel's
discipline).  Semantics are bit-identical to the XLA references
exported next to them; ``tests/test_fabric_pallas.py`` pins that in
interpret mode and ``scripts/tpu_pallas_ab.py`` A/Bs the compiled
numbers as ``kind=fabric_ab`` rungs.  ``interpret`` defaults to True on
the CPU backend only; on any other backend the kernel compiles or raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

I32 = jnp.int32
ROW_BLOCK = 8     # sublane dimension: rows per grid program
_INT_MIN = jnp.iinfo(jnp.int32).min
_INT_MAX = jnp.iinfo(jnp.int32).max


def _default_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


# ---------------------------------------------------------------------------
# inbox staging: batched lane gather
# ---------------------------------------------------------------------------


def gather_lanes_xla(vals, idx):
    """XLA reference arm: ``out[g, m] = vals[g, idx[g, m]]`` — the
    batched HLO gather route()'s lane pick would emit without the
    one-hot rewrite.  ``idx`` must be in range (no sentinel)."""
    return jnp.take_along_axis(vals, idx, axis=1)


def _gather_block_kernel(K: int, M: int, vals_ref, idx_ref, out_ref):
    """One grid program: M lane picks against an [8, K] block in VMEM.
    An out-of-range index has no hot slot and reads 0 — the router's
    lane==K sentinel convention, not an error.

    M is static and small, so the picks are a Python-unrolled loop over
    STATIC columns of whole-block values: Mosaic takes no dynamic lane
    index (a traced ``ref[:, j]`` is refused — "cannot statically prove
    that index in dimension 1 is a multiple of 128")."""
    pos = jax.lax.broadcasted_iota(I32, (ROW_BLOCK, K), 1)
    col = jax.lax.broadcasted_iota(I32, (ROW_BLOCK, M), 1)
    vals, idx = vals_ref[:, :], idx_ref[:, :]
    out = jnp.zeros((ROW_BLOCK, M), vals.dtype)
    for j in range(M):
        oh = pos == idx[:, j:j + 1]                   # [8, K] one-hot
        pick = jnp.sum(jnp.where(oh, vals, 0), axis=1, keepdims=True)
        out = jnp.where(col == j, pick, out)
    out_ref[:, :] = out


@functools.partial(jax.jit, static_argnums=(2,))
def _gather_pallas(vals, idx, interpret: bool):
    G, K = vals.shape
    M = idx.shape[1]
    pad = (-G) % ROW_BLOCK
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
    Gp = G + pad

    def block(i):
        return (i, 0)

    out = pl.pallas_call(
        functools.partial(_gather_block_kernel, K, M),
        grid=(Gp // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, K), block),
            pl.BlockSpec((ROW_BLOCK, M), block),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, M), block),
        out_shape=jax.ShapeDtypeStruct((Gp, M), vals.dtype),
        interpret=interpret,
    )(vals, idx)
    return out[:G]


def gather_lanes_pallas(vals, idx, interpret: bool | None = None):
    """``gather_lanes_xla`` semantics as a VMEM block kernel: the [G, K]
    value rows stay resident across all M picks instead of one gather
    dispatch per lane.  Bit-identical for in-range indexes; an index
    == K reads 0 (the one-hot sentinel, matching router.route's
    ``onehot_reads`` branch)."""
    return _gather_pallas(vals, idx, _default_interpret(interpret))


# ---------------------------------------------------------------------------
# quorum match: one order statistic, not a sort
# ---------------------------------------------------------------------------


def quorum_match_xla(match, voting, quorum):
    """XLA reference arm — core/kernel._sorted_match_quorum_index's
    exact shape: mask non-voters to INT_MAX, full ascending sort, then
    gather the single ``nv - quorum`` position (clipped)."""
    mv = jnp.where(voting, match, _INT_MAX)
    srt = jnp.sort(mv, axis=1)
    nv = jnp.sum(voting.astype(I32), axis=1)
    pos = jnp.clip(nv - quorum, 0, match.shape[1] - 1)
    return jnp.take_along_axis(srt, pos[:, None], axis=1)[:, 0]


def _quorum_block_kernel(R: int, match_ref, voting_ref, q_ref, out_ref):
    """Rank-select without the sort: the q-th largest voter match is
    the largest value v with at least q voter matches >= v (duplicate
    values collapse onto the same candidate, so ties pick the same
    element the ascending sort would).  When fewer than q voters exist
    the sort reference clips to position 0 — the smallest masked value
    — which the fallback arm reproduces (INT_MAX when no voters)."""
    m = match_ref[:, :]                               # [8, R]
    v = voting_ref[:, :] != 0
    q = q_ref[:, :]                                   # [8, 1]

    # cnt[i] = #{voting j : match[j] >= match[i]}  (R static and tiny:
    # unrolled 2D passes over static columns — Mosaic has no lowering
    # for a traced column slice of a value)
    cnt = jnp.zeros_like(m)
    for j in range(R):
        ge = (m[:, j:j + 1] >= m) & v[:, j:j + 1] & v
        cnt = cnt + ge.astype(I32)
    ok = v & (cnt >= q)
    best = jnp.max(jnp.where(ok, m, _INT_MIN), axis=1, keepdims=True)
    fallback = jnp.min(jnp.where(v, m, _INT_MAX), axis=1, keepdims=True)
    any_ok = jnp.max(ok.astype(I32), axis=1, keepdims=True) > 0
    out_ref[:, :] = jnp.where(any_ok, best, fallback)


@functools.partial(jax.jit, static_argnums=(3,))
def _quorum_pallas(match, voting, quorum, interpret: bool):
    G, R = match.shape
    pad = (-G) % ROW_BLOCK
    if pad:
        match = jnp.pad(match, ((0, pad), (0, 0)))
        voting = jnp.pad(voting, ((0, pad), (0, 0)))
        quorum = jnp.pad(quorum, (0, pad))
    Gp = G + pad

    def block(i):
        return (i, 0)

    out = pl.pallas_call(
        functools.partial(_quorum_block_kernel, R),
        grid=(Gp // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, R), block),
            pl.BlockSpec((ROW_BLOCK, R), block),
            pl.BlockSpec((ROW_BLOCK, 1), block),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, 1), block),
        out_shape=jax.ShapeDtypeStruct((Gp, 1), match.dtype),
        interpret=interpret,
    )(match, voting.astype(I32), quorum[:, None])
    return out[:G, 0]


def quorum_match_pallas(match, voting, quorum,
                        interpret: bool | None = None):
    """``quorum_match_xla`` semantics as a VMEM block kernel computing
    the one consumed order statistic via compare-counts instead of a
    full sort + gather.  Bit-identical (tests/test_fabric_pallas.py),
    including the fewer-voters-than-quorum and zero-voter clips."""
    return _quorum_pallas(match, voting, quorum,
                          _default_interpret(interpret))
