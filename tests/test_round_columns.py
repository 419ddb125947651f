"""The round's two packed crossings and the resident form of the state
between rounds (kstate.py's column table, the jitted round entries of
core/round.py and parallel/round.py): pack then unpack is the identity on
every field, the host builders write the same columns the device unpacks,
and the packed step on the resident state is ``core.kernel.step`` on the
ShardState (the mesh one ``ici.jit_serve_step``) with nothing added or
lost, field for field, after every round."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dragonboat_tpu.core import kernel, kstate, params as KP, round as cround
from dragonboat_tpu.core.kstate import (
    Inbox,
    ShardState,
    StepInput,
    StepOutput,
)
from dragonboat_tpu.core.router import route
from dragonboat_tpu.engine import kernel_engine as ke
from dragonboat_tpu.parallel import ici, round as pround

GEOMETRIES = {
    # the served default (NodeHost._kernel_params), a bench-like wide one,
    # and one where the save window is the whole ring
    "served": dict(num_peers=5, log_cap=1024, inbox_cap=8, msg_entries=8,
                   proposal_cap=8, readindex_cap=4),
    "wide": dict(num_peers=3, log_cap=128, inbox_cap=10, msg_entries=32,
                 proposal_cap=32, readindex_cap=4),
    "tiny": dict(num_peers=2, log_cap=8, inbox_cap=5, msg_entries=2,
                 proposal_cap=2, readindex_cap=2),
}
G = 6


def _random_tree(cls, kp, rng):
    """A ``cls`` whose every materialised field holds random values of its
    contract shape and dtype (numpy)."""
    cols, _ = kstate._class_columns(cls, kp, 0)
    by = {c.field: c for c in cols}
    vals = {}
    for f in cls._fields:
        c = by.get(f)
        if c is None:
            vals[f] = None
        elif c.dtype == "bool":
            vals[f] = rng.random((G,) + c.shape) < 0.5
        else:
            vals[f] = rng.integers(-2**31, 2**31 - 1, (G,) + c.shape,
                                   dtype=np.int64).astype(np.int32)
    return cls(**vals)


def _assert_same(tag, got, want):
    for f in type(want)._fields:
        a, b = getattr(got, f), getattr(want, f)
        if b is None:
            assert a is None, f"{tag}.{f}: expected no columns"
            continue
        a = np.asarray(a)
        assert a.shape == b.shape, f"{tag}.{f}: shape {a.shape} != {b.shape}"
        assert a.dtype == b.dtype, f"{tag}.{f}: dtype {a.dtype} != {b.dtype}"
        assert np.array_equal(a, b), f"{tag}.{f}: values differ"


@pytest.mark.parametrize("inline", [False, True], ids=["plain", "inline"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_pack_then_unpack_returns_every_field(geometry, inline):
    kp = KP.KernelParams(inline_payloads=inline, **GEOMETRIES[geometry])
    rc = kstate.round_columns(kp)
    rng = np.random.default_rng(7)
    box, inp, out = (_random_tree(c, kp, rng)
                     for c in (Inbox, StepInput, StepOutput))
    for optional in (box.ent_val, inp.prop_val, out.s_ent_val):
        assert (optional is not None) == inline
    # widths are what the fields add up to, in both directions
    assert rc.up_width == sum(c.width for c in rc.up)
    assert rc.down_width == sum(c.width for c in rc.down)
    assert [c.start for c in rc.up] == list(
        np.cumsum([0] + [c.width for c in rc.up])[:-1])

    # upload: device pack -> device unpack
    up = kstate.pack_columns(
        rc.up, {k: jnp.asarray(v) for k, v in
                {**box._asdict(), **inp._asdict()}.items() if v is not None})
    assert up.shape == (G, rc.up_width) and up.dtype == jnp.int32
    got_box, got_inp = kstate.unpack_upload(kp, up)
    _assert_same("Inbox", got_box, box)
    _assert_same("StepInput", got_inp, inp)

    # upload: what the host builders' views write is what the device reads
    staging = ke._RoundStaging(kp, G)
    for name, view in kstate.column_views(rc.up, staging.up).items():
        src = getattr(box, name) if name in Inbox._fields \
            else getattr(inp, name)
        assert np.shares_memory(view, staging.up), name
        view[...] = src
    got_box, got_inp = kstate.unpack_upload(kp, jnp.asarray(staging.up))
    _assert_same("staged Inbox", got_box, box)
    _assert_same("staged StepInput", got_inp, inp)
    # a reset clears the rows the BUILDERS wrote and no other (the views
    # above wrote behind their backs: PR 43, tests/test_staged_rows.py)
    assert staging.reset() == 0 and staging.up.any()
    staging.rows.update(range(G))
    assert staging.reset() == G
    assert not staging.up.any() and not staging.rows

    # download: device pack -> device unpack, and the host view
    flags = rng.random((G, len(kstate.FLAG_CLASSES))) < 0.5
    terms = rng.integers(0, 2**31 - 1, (G, rc.save_window)).astype(np.int32)
    active = rng.integers(0, 8, (G,)).astype(np.int32)
    down = kstate.pack_download(
        kp, jnp.asarray(flags), jnp.asarray(active),
        StepOutput(*(None if v is None else jnp.asarray(v) for v in out)),
        jnp.asarray(terms))
    assert down.shape == (G, rc.down_width) and down.dtype == jnp.int32
    _assert_same("StepOutput",
                 kstate.unpack_columns(StepOutput, rc.down, down), out)
    o = ke._RoundDown(np.asarray(down), {c.field: c for c in rc.down})
    for f in StepOutput._fields:
        want = getattr(out, f)
        if want is not None:
            assert o[f].shape == want.shape and o[f].dtype == want.dtype, f
            assert np.array_equal(o[f], want), f
            assert o[f] is o[f], "memoised"
    assert np.array_equal(o["flags"], flags) and o["flags"].dtype == bool
    assert np.array_equal(o["active"], active) and o["active"].shape == (G,)
    assert np.array_equal(o["save_terms"], terms)


@pytest.mark.parametrize("inline", [False, True], ids=["plain", "inline"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_pack_state_then_unpack_returns_every_field(geometry, inline):
    """``unpack_state(pack_state(s)) == s`` field for field on a random
    state (bools, the [G, RI, P] ``ri_acks``, ``lv`` present and absent):
    the resident form is three arrays (four with ``lv``), the rings handed
    over as they are."""
    kp = KP.KernelParams(inline_payloads=inline, **GEOMETRIES[geometry])
    state = _random_tree(ShardState, kp, np.random.default_rng(11))
    assert (state.lv is not None) == inline
    assert state.ri_acks.shape == (G, kp.readindex_cap, kp.num_peers)
    cols, width = kstate.state_columns(kp)
    assert width == sum(c.width for c in cols)
    assert not {c.field for c in cols} & set(kstate.RING_FIELDS)
    assert {c.field for c in cols} | set(kstate.RING_FIELDS) \
        == set(ShardState._fields)
    resident = kstate.pack_program(kp)(state)
    assert len(jax.tree.leaves(resident)) == (4 if inline else 3)
    assert resident.cols.shape == (G, width)
    assert resident.cols.dtype == jnp.int32
    for ring in kstate.RING_FIELDS:
        if getattr(state, ring) is not None:
            assert np.array_equal(getattr(resident, ring),
                                  getattr(state, ring)), ring
    # the view the engine's ``state`` property runs leaves the rings out
    view = kstate.view_program(kp)(resident.cols)
    assert view.lt is None and view.lcc is None and view.lv is None
    _assert_same("ShardState", _unpack(kp, resident), state)


def _unpack(kp, resident, placement=None) -> ShardState:
    """As the engine's ``state`` property: the columns unpacked by one
    program, the resident rings handed over."""
    return kstate.view_program(kp, placement)(resident.cols)._replace(
        lt=resident.lt, lcc=resident.lcc, lv=resident.lv)


def test_served_geometry_widths():
    """The sizes PERF.md quotes: 231 columns up, 345 down, S 64; 108
    columns of resident state, 208 of carried mesh inbox."""
    served = KP.KernelParams(**GEOMETRIES["served"])
    rc = kstate.round_columns(served)
    assert (rc.up_width, rc.down_width, rc.save_window) == (231, 345, 64)
    assert kstate.state_columns(served)[1] == 108
    assert kstate.inbox_columns(served)[1] == 208
    assert kstate.save_window(KP.KernelParams(
        log_cap=16, **{k: v for k, v in GEOMETRIES["served"].items()
                       if k != "log_cap"})) == 16, "never past the ring"
    assert kstate.save_window(KP.KernelParams(
        save_window=4, **GEOMETRIES["served"])) == 4


@pytest.mark.parametrize("cap,size", [(1024, 64), (16, 16), (32, 4), (8, 2)])
def test_ring_window_reads_the_ring_modulo_cap(cap, size):
    rng = np.random.default_rng(cap + size)
    ring = rng.integers(0, 1000, (9, cap)).astype(np.int32)
    first = rng.integers(0, 5 * cap, (9,)).astype(np.int32)
    got = np.asarray(cround.ring_window(
        jnp.asarray(ring), jnp.asarray(first), size))
    want = np.stack([ring[g, (first[g] + np.arange(size)) & (cap - 1)]
                     for g in range(9)])
    assert np.array_equal(got, want)


# -- the packed step is the step ---------------------------------------------

STEPS = 50
REPLICAS = 3


def _step_kp(replicas: int) -> KP.KernelParams:
    return KP.KernelParams(
        num_peers=replicas, log_cap=64, inbox_cap=5 * (replicas - 1),
        msg_entries=4, proposal_cap=4, readindex_cap=4, apply_batch=16,
        compaction_overhead=16)


def _busy_input(kp, rng, state) -> StepInput:
    Gn, B = state.term.shape[0], kp.proposal_cap
    lead = np.asarray(state.role) == KP.LEADER
    z = lambda: np.zeros((Gn,), np.int32)  # noqa: E731
    return StepInput(
        prop_valid=(rng.random((Gn, B)) < 0.5) & lead[:, None],
        prop_cc=np.zeros((Gn, B), bool),
        ri_valid=(rng.random(Gn) < 0.3) & lead,
        ri_low=rng.integers(1, 1000, Gn).astype(np.int32), ri_high=z(),
        transfer_to=z(), tick=rng.random(Gn) < 0.9,
        quiesced=np.zeros((Gn,), bool),
        applied=np.asarray(state.processed))


def _pack_up(kp, box, inp):
    return kstate.pack_columns(
        kstate.round_columns(kp).up,
        {k: jnp.asarray(v) for k, v in
         {**box._asdict(), **inp._asdict()}.items() if v is not None})


def _assert_trees_equal(tag, a, b):
    for f in type(b)._fields:
        x, y = getattr(a, f), getattr(b, f)
        if y is None:
            assert x is None, f"{tag}.{f}"
            continue
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and np.array_equal(x, y), \
            f"{tag}: field {f} diverged"


def _check_download(tag, kp, down, state, out):
    """``down`` holds the flags of ``out``, ``out`` itself and the save
    window's terms read from ``state`` (what the step returned)."""
    rc = kstate.round_columns(kp)
    down = np.asarray(down)
    _assert_trees_equal(tag + " out",
                        kstate.unpack_columns(StepOutput, rc.down, down), out)
    o = ke._RoundDown(down, {c.field: c for c in rc.down})
    assert np.array_equal(
        o["flags"], np.asarray(kernel.output_row_flags(out))), tag
    lt, first = np.asarray(state.lt), np.asarray(out.save_first)
    want = np.stack([lt[g, (first[g] + np.arange(rc.save_window))
                           & (kp.log_cap - 1)] for g in range(lt.shape[0])])
    assert np.array_equal(o["save_terms"], want), f"{tag}: save window"
    return int((np.asarray(out.save_last) >= first).sum())


@pytest.mark.parametrize("depth", [0, 1])
def test_packed_serial_step_equals_kernel_step(depth):
    """50 random busy steps of 4 routed groups: ``core.round.step`` (depth
    1: the donating ``step_donated``) on the resident state and the packed
    upload, its own result carried from round to round as an engine carries
    it, returns the state and (unpacked) outputs of ``core.kernel.step`` on
    the ShardState and the same inputs, bit for bit after every round."""
    from dragonboat_tpu.bench_loop import make_cluster

    kp = _step_kp(REPLICAS)
    entry = cround.step_donated if depth else cround.step
    state = make_cluster(kp, 4, REPLICAS)
    resident = kstate.pack_program(kp)(state)
    box = kstate.empty_inbox(kp, state.term.shape[0])
    rng = np.random.default_rng(5)
    route_jit = jax.jit(route, static_argnums=(0, 1))
    saved = committed = 0
    for i in range(STEPS):
        inp = _busy_input(kp, rng, state)
        want_state, want_out = kernel.step(kp, state, box, inp)
        args = (resident, _pack_up(kp, box, inp))
        resident, down = entry(kp, kernel.step, *args)
        # the entry takes and returns 4 device arrays, not 46
        assert len(jax.tree.leaves(args)) == 4
        assert len(jax.tree.leaves((resident, down))) == 4
        got_state = _unpack(kp, resident)
        _assert_trees_equal(f"step {i} state", got_state, want_state)
        saved += _check_download(f"step {i}", kp, down, got_state, want_out)
        state, box = want_state, route_jit(kp, REPLICAS, want_out)
        committed = int(np.asarray(state.committed).max())
    assert committed > 0 and saved > STEPS, "the steps were not busy"


def test_packed_mesh_step_equals_serve_step():
    """The same over a 1x2 device mesh (forced host devices):
    ``parallel.round.jit_serve_step`` against ``ici.jit_serve_step``, with
    hub-fallback rows staged in the upload's inbox columns on some steps
    (merged into the carried inbox as MeshDispatch used to, eagerly); the
    resident state and the carried [G, Wi] inbox are the entry's own
    results, carried from round to round."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    kp = _step_kp(2)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("g", "r"))
    cluster, state, box = ici.make_ici_cluster(kp, mesh, num_groups=4)
    Gn = cluster.total_rows
    cut = cluster.shard(np.zeros((Gn, kp.num_peers), bool))
    placed = cluster.sharding()
    box_cols, wi = kstate.inbox_columns(kp)
    resident = kstate.pack_program(kp, placed)(state)
    carried = jax.device_put(
        np.asarray(kstate.pack_columns(box_cols, box._asdict())), placed)
    assert carried.shape == (Gn, wi)
    rng = np.random.default_rng(9)
    empty = jax.tree.map(np.asarray, kstate.empty_inbox(kp, Gn))
    saved = committed = 0
    for i in range(STEPS):
        inp = _busy_input(kp, rng, state)
        staged = empty
        if i % 5 == 4:
            # a stray hub delivery in a free slot: a NOOP-typed message
            # the kernel ignores but the merge must carry
            mt = np.zeros_like(empty.mtype)
            mt[rng.integers(0, Gn), kp.inbox_cap - 1] = 1
            staged = empty._replace(mtype=mt, term=mt * 0)
        live = staged.mtype != 0
        merged = jax.tree.map(
            lambda s, b: jnp.where(
                live.reshape(live.shape + (1,) * (s.ndim - 2)), s, b),
            staged, box)
        want_state, want_box, want_out = ici.jit_serve_step(
            kp, cluster, state, cluster.shard(merged),
            cluster.shard(inp), cut)
        up = jax.device_put(np.asarray(_pack_up(kp, staged, inp)),
                            cluster.sharding())
        args = (resident, carried, up, cut)
        resident, carried, down = pround.jit_serve_step(kp, cluster, *args)
        assert len(jax.tree.leaves(args)) == 6
        assert len(jax.tree.leaves((resident, carried, down))) == 5
        for x in jax.tree.leaves((resident, carried)):
            assert x.sharding == placed, "spelled as it was placed"
        got_state = _unpack(kp, resident, placed)
        got_box = kstate.box_view_program(kp, placed)(carried)
        _assert_trees_equal(f"step {i} state", got_state, want_state)
        _assert_trees_equal(f"step {i} box", got_box, want_box)
        saved += _check_download(f"step {i}", kp, down, got_state, want_out)
        assert down.sharding.is_equivalent_to(cluster.sharding(), 2)
        state, box = want_state, want_box
        committed = int(np.asarray(state.committed).max())
    assert committed > 0 and saved > STEPS // 2, "the steps were not busy"
