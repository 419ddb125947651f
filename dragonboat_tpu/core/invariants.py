"""Runtime protocol-invariant probe: the third leg of the safety verifier.

``core/kstate.py`` declares the protocol invariants (INVARIANTS, grammar
in ``analysis/common.py``); this module evaluates them on the LIVE fleet.
One jitted pass over the batched ``ShardState`` checks every declared
invariant on every group, carrying a compact per-group
``InvariantDigest`` (the ``prev.``-referenced columns plus an age
counter) between decimated probe ticks so STEP-scoped invariants
(term/commit monotonicity, vote-at-most-once, quorum-backed commit
advance) are checked over the transition between two observations —
sound for the monotone/guarded forms kstate.py declares, at any
decimation.  The ``InvariantReport`` is O(1): a violation total,
per-invariant counts, and the first-offender lane + its violation
bitmask.  An engine runs ``_check_invariants_impl`` inside its one
collection program (core/digest.py): the report is a block of that
program's one vector, the digest seven columns of its one carried array.

A nonzero total is ALWAYS a bug — either in the kernel or in the
declared invariant — never an operational condition: the engines raise
an ``invariant_violation`` flight event and ``/healthz`` degrades to
503.  The other two legs consume the same declarations statically:
``analysis/safety.py`` (store-site abstract interpretation) and
``scripts/model_check.py`` (small-scope exhaustive exploration).

``eval_row`` / ``recount`` are the pure-python oracle the tests, the
chaos detector and the model checker cross-check against.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from dragonboat_tpu.analysis.common import Invariant, parse_invariants
from dragonboat_tpu.core import params as P
from dragonboat_tpu.core.kstate import INVARIANTS as _SPECS

#: parsed invariants, declaration order (the bit order of first_mask)
PARSED: dict[str, Invariant] = parse_invariants(
    _SPECS, "core/kstate.py:INVARIANTS")
INVARIANT_NAMES = tuple(PARSED)
NUM_INVARIANTS = len(INVARIANT_NAMES)

INT32_MAX = 2**31 - 1

#: ShardState columns carried as ``prev_*`` digest fields — must cover
#: every ``prev.`` term any declared invariant references (checked below
#: at import, so adding an invariant with a new prev. field fails loudly
#: until the digest + CONTRACTS grow the column)
_PREV_FIELDS = ("term", "vote", "committed", "role", "quiesced",
                "quiesce_epoch")

_needed = {t.name
           for inv in PARSED.values()
           for c in (*inv.guards, inv.conclusion)
           for t in (c.lhs, c.rhs) if t.kind == "prev"}
if _needed - set(_PREV_FIELDS):
    raise ValueError(
        f"core/invariants.py: INVARIANTS reference prev. fields "
        f"{sorted(_needed - set(_PREV_FIELDS))} not carried by "
        "InvariantDigest — add them to _PREV_FIELDS and CONTRACTS")

# Partition contract (grammar: core/kstate.py CONTRACTS; checked by
# analysis/partition.py and the contracts pass).  The digest is per-group
# device state sharded along G; the report is an aggregate over ALL
# groups — replicated, produced by an intentional cross-G collective
# (``collective=declared`` licenses the reductions inside
# _check_invariants_impl that PS001 would otherwise flag).  Axis NI is a
# host-side constant (NUM_INVARIANTS), not kernel geometry.
CONTRACTS = {
    "InvariantDigest": {
        "prev_term": "[G] i32 part=G",
        "prev_vote": "[G] i32 part=G",
        "prev_committed": "[G] i32 part=G",
        "prev_role": "[G] i32 part=G",
        "prev_quiesced": "[G] i32 part=G",
        "prev_quiesce_epoch": "[G] i32 part=G",
        "ticks": "[G] i32 part=G",
    },
    "InvariantReport": {
        "total": "[] i32 part=replicated collective=declared",
        "checked": "[] i32 part=replicated collective=declared",
        "per_invariant": "[NI] i32 part=replicated collective=declared",
        "first_lane": "[] i32 part=replicated collective=declared",
        "first_mask": "[] i32 part=replicated collective=declared",
    },
}


class InvariantDigest(NamedTuple):
    """Fixed-width per-group carry between decimated probe ticks."""

    prev_term: jnp.ndarray       # [G]
    prev_vote: jnp.ndarray       # [G]
    prev_committed: jnp.ndarray  # [G]
    prev_role: jnp.ndarray       # [G]
    prev_quiesced: jnp.ndarray   # [G] (bool state column widened to i32)
    prev_quiesce_epoch: jnp.ndarray  # [G]
    ticks: jnp.ndarray           # [G] digest age (0 = no valid prev)


class InvariantReport(NamedTuple):
    """One collection's O(1) verdicts (all i32)."""

    total: jnp.ndarray           # [] groups violating >= 1 invariant
    checked: jnp.ndarray         # [] occupied groups evaluated
    per_invariant: jnp.ndarray   # [NUM_INVARIANTS] violating groups
    first_lane: jnp.ndarray      # [] lowest violating lane (-1 = none)
    first_mask: jnp.ndarray      # [] that lane's violation bitmask


def empty_digest(num_lanes: int, sharding=None) -> InvariantDigest:
    """All-zero digest for ``num_lanes`` groups (ticks=0 marks every
    step-scoped invariant vacuous until the first carry)."""
    z = jnp.zeros((num_lanes,), jnp.int32)
    d = InvariantDigest(*(z for _ in InvariantDigest._fields))
    if sharding is not None:
        d = jax.device_put(d, sharding)
    return d


#: comparison semantics shared by the jitted probe (jnp arrays), the
#: pure-python oracle (ints) and the model checker
OPS = {
    "<=": lambda a, b: a <= b,
    ">=": lambda a, b: a >= b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    ">": lambda a, b: a > b,
}


def _quorum_arr(state, col):
    """Vectorized [G] ``quorum(col)``: the q-th largest value among
    voting members — exactly core/kernel.py _sorted_match_quorum_index
    with the leading G axis kept."""
    i32 = jnp.int32
    voting = (state.kind == P.K_VOTER) | (state.kind == P.K_WITNESS)
    mv = jnp.where(voting, col.astype(i32), INT32_MAX)
    srt = jnp.sort(mv, axis=1)       # ascending; absent lanes at the end
    nv = voting.astype(i32).sum(axis=1)
    q = nv // 2 + 1
    pos = jnp.clip(nv - q, 0, mv.shape[1] - 1)
    return jnp.take_along_axis(srt, pos[:, None], axis=1)[:, 0]


def _term_arr(t, state, inv_digest):
    if t.kind == "const":
        return jnp.int32(t.value)
    if t.kind == "param":
        return jnp.int32(int(getattr(P, t.name)))
    if t.kind == "field":
        return getattr(state, t.name).astype(jnp.int32)
    if t.kind == "prev":
        return getattr(inv_digest, "prev_" + t.name)
    if t.kind == "quorum":
        return _quorum_arr(state, getattr(state, t.name))
    raise ValueError(f"unknown invariant term kind {t.kind!r}")


def _violations(inv: Invariant, state, inv_digest, occ, valid):
    """[G] bool: rows where ``inv``'s guards all hold but the conclusion
    does not.  Step-scoped invariants are vacuous without a valid prev."""
    live = occ & valid if inv.scope == "step" else occ
    for g in inv.guards:
        live = live & OPS[g.op](_term_arr(g.lhs, state, inv_digest),
                                _term_arr(g.rhs, state, inv_digest))
    c = inv.conclusion
    holds = OPS[c.op](_term_arr(c.lhs, state, inv_digest),
                      _term_arr(c.rhs, state, inv_digest))
    return live & ~holds


def _check_invariants_impl(state, inv_digest: InvariantDigest
                           ) -> tuple[InvariantReport, InvariantDigest]:
    i32 = jnp.int32
    occ = (state.kind != P.K_ABSENT).any(axis=1)              # [G] bool
    valid = inv_digest.ticks > 0                              # [G] bool
    viol_mat = jnp.stack(
        [_violations(inv, state, inv_digest, occ, valid)
         for inv in PARSED.values()], axis=1).astype(i32)     # [G, NI]
    per_invariant = viol_mat.sum(axis=0)                      # [NI]
    bits = (1 << jnp.arange(NUM_INVARIANTS, dtype=i32))
    mask = (viol_mat * bits[None, :]).sum(axis=1)             # [G]
    bad = mask > 0
    total = bad.astype(i32).sum()
    lanes = jnp.arange(mask.shape[0], dtype=i32)
    first = jnp.min(jnp.where(bad, lanes, INT32_MAX))
    first_lane = jnp.where(total > 0, first, -1)
    first_mask = jnp.where(
        total > 0,
        jnp.take(mask, jnp.clip(first, 0, mask.shape[0] - 1)), 0)
    report = InvariantReport(
        total=total, checked=occ.astype(i32).sum(),
        per_invariant=per_invariant, first_lane=first_lane,
        first_mask=first_mask)
    new_digest = InvariantDigest(
        prev_term=state.term, prev_vote=state.vote,
        prev_committed=state.committed, prev_role=state.role,
        prev_quiesced=state.quiesced.astype(i32),
        prev_quiesce_epoch=state.quiesce_epoch,
        ticks=inv_digest.ticks + 1)
    return report, new_digest


check_invariants = jax.jit(_check_invariants_impl)


# ---------------------------------------------------------------------------
# host-side converters + exposition
# ---------------------------------------------------------------------------


def _decode_mask(mask: int) -> list[str]:
    return [INVARIANT_NAMES[i] for i in range(NUM_INVARIANTS)
            if (mask >> i) & 1]


def report_to_dict(report: InvariantReport) -> dict:
    """Fetch to host and flatten into plain ints/dicts — the shape the
    callback gauges (and ``engine.last_invariants``) serve."""
    return host_dict(jax.device_get(report))


def host_dict(r: InvariantReport) -> dict:
    """``report_to_dict`` of a report already on the host: numpy values,
    or the Python ints and lists the engines decode from their packed
    digest (core/digest.py)."""
    d = {
        "total": int(r.total),
        "checked": int(r.checked),
        "per_invariant": {INVARIANT_NAMES[i]: int(r.per_invariant[i])
                          for i in range(NUM_INVARIANTS)},
        "first": None,
    }
    if d["total"] > 0:
        d["first"] = {"lane": int(r.first_lane),
                      "invariants": _decode_mask(int(r.first_mask))}
    return d


def empty_dict() -> dict:
    """All-zero invariants dict (merge identity for hosts w/o engine)."""
    return {
        "total": 0,
        "checked": 0,
        "per_invariant": {n: 0 for n in INVARIANT_NAMES},
        "first": None,
    }


def merge_into(base: dict, other: dict, engine: str | None = None) -> None:
    """Accumulate ``other`` (same shape as ``empty_dict``) into ``base``:
    counts add; the first-offender slot keeps base's unless empty, and
    ``engine`` tags an adopted offender so a merged multi-engine view
    stays attributable."""
    base["total"] += other["total"]
    base["checked"] += other["checked"]
    for n in base["per_invariant"]:
        base["per_invariant"][n] += other["per_invariant"].get(n, 0)
    if base["first"] is None and other["first"] is not None:
        first = dict(other["first"])
        if engine is not None:
            first.setdefault("engine", engine)
        base["first"] = first


def register_exposition(registry, source, replace: bool = False) -> None:
    """Register the invariant callback-gauge families on ``registry``,
    backed by ``source()`` -> invariants dict (or None for "no data
    yet").  Idempotent when ``replace`` is False (same protocol as
    ``health.register_exposition``)."""
    if not replace \
            and registry.kind_of("invariant_violations") is not None:
        return

    def _get() -> dict:
        d = source()
        return d if d is not None else empty_dict()

    registry.gauge_fn(
        "invariant_violations",
        lambda: {(n,): _get()["per_invariant"][n]
                 for n in INVARIANT_NAMES},
        help="groups currently violating each protocol invariant",
        labelnames=("invariant",))
    registry.gauge_fn("invariants.violating_shards",
                      lambda: _get()["total"],
                      help="groups violating at least one invariant")
    registry.gauge_fn("invariants.checked_shards",
                      lambda: _get()["checked"],
                      help="occupied groups the probe evaluated")


def validate_invariants(d: dict, where: str = "invariants") -> None:
    """Strictly check an ``empty_dict``-shaped invariants snapshot (the
    ``/healthz`` 503 ``invariants`` section and chaos oracle rows)."""
    for key in ("total", "checked"):
        v = d.get(key)
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            raise ValueError(f"{where}.{key}: bad count {v!r}")
    per = d.get("per_invariant")
    if not isinstance(per, dict) or set(per) != set(INVARIANT_NAMES):
        raise ValueError(f"{where}.per_invariant: invariants "
                         f"{sorted(per) if isinstance(per, dict) else per!r}"
                         f" != {sorted(INVARIANT_NAMES)}")
    for n, v in per.items():
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            raise ValueError(f"{where}.per_invariant[{n!r}]: {v!r}")
    first = d.get("first", 0)
    if first is not None:
        if not isinstance(first, dict):
            raise ValueError(f"{where}.first: expected dict|None, "
                             f"got {first!r}")
        lane = first.get("lane")
        if isinstance(lane, bool) or not isinstance(lane, int):
            raise ValueError(f"{where}.first.lane: {lane!r}")
        for n in first.get("invariants", ()):
            if n not in INVARIANT_NAMES:
                raise ValueError(f"{where}.first: unknown invariant {n!r}")


# ---------------------------------------------------------------------------
# pure-python oracle (tests / chaos detector / model checker)
# ---------------------------------------------------------------------------


def quorum_py(match, kind) -> int:
    """Python mirror of _quorum_arr for one group's [P] rows."""
    voting = [int(k) in (P.K_VOTER, P.K_WITNESS) for k in kind]
    mv = sorted(int(m) if v else INT32_MAX for m, v in zip(match, voting))
    nv = sum(voting)
    pos = min(max(nv - (nv // 2 + 1), 0), len(mv) - 1)
    return mv[pos]


def _term_row(t, cur: dict, prev: dict | None):
    if t.kind == "const":
        return t.value
    if t.kind == "param":
        return int(getattr(P, t.name))
    if t.kind == "field":
        return int(cur[t.name])
    if t.kind == "prev":
        return int(prev[t.name])
    if t.kind == "quorum":
        return quorum_py(cur[t.name], cur["kind"])
    raise ValueError(f"unknown invariant term kind {t.kind!r}")


def eval_row(inv: Invariant, cur: dict, prev: dict | None) -> bool:
    """True iff ``inv`` is VIOLATED on one group's row.  ``cur`` maps
    ShardState field -> int ([G] columns) or [P] sequence (``match`` /
    ``kind``); ``prev`` maps prev-field -> int, or None for "no prior
    observation" (step-scoped invariants pass vacuously)."""
    if inv.scope == "step" and prev is None:
        return False
    for g in inv.guards:
        if not OPS[g.op](_term_row(g.lhs, cur, prev),
                         _term_row(g.rhs, cur, prev)):
            return False
    c = inv.conclusion
    return not OPS[c.op](_term_row(c.lhs, cur, prev),
                         _term_row(c.rhs, cur, prev))


def recount(state, inv_digest) -> tuple[dict, dict]:
    """Recompute ``check_invariants`` with per-group host loops over
    fetched arrays (``jax.device_get`` the inputs first).  Returns
    ``(report_dict, digest_dict)`` where report_dict matches
    ``report_to_dict`` and digest_dict maps InvariantDigest field ->
    list — the oracle the probe's differential tests cite."""
    G = len(inv_digest.ticks)
    counts = {n: 0 for n in INVARIANT_NAMES}
    total = checked = 0
    first = None
    out = {f: [0] * G for f in InvariantDigest._fields}
    for g in range(G):
        occ = any(int(k) != P.K_ABSENT for k in state.kind[g])
        valid = int(inv_digest.ticks[g]) > 0
        cur = {"kind": [int(v) for v in state.kind[g]]}
        for f in sorted({f for inv in PARSED.values() for f in inv.fields}):
            col = getattr(state, f)[g]
            cur[f] = ([int(v) for v in col] if getattr(col, "ndim", 0)
                      else int(col))
        prev = ({f: int(getattr(inv_digest, "prev_" + f)[g])
                 for f in _PREV_FIELDS} if valid else None)
        if occ:
            checked += 1
        mask = 0
        for i, inv in enumerate(PARSED.values()):
            if occ and eval_row(inv, cur, prev):
                counts[inv.name] += 1
                mask |= 1 << i
        if mask:
            total += 1
            if first is None:
                first = {"lane": g, "invariants": _decode_mask(mask)}
        new = {"prev_" + f: int(getattr(state, f)[g])
               for f in _PREV_FIELDS}
        new["ticks"] = int(inv_digest.ticks[g]) + 1
        for f, v in new.items():
            out[f][g] = v
    report = {"total": total, "checked": checked,
              "per_invariant": counts, "first": first}
    return report, out
