"""Compile the main path's device programs for a v5e that is described, not
attached: what the TPU compiler refuses here costs no chip time.

Covers the entries an engine serves through (the packed rounds of
``core/round.py`` and ``parallel/round.py`` with the KernelParams a NodeHost
picks on a TPU, serial and on a 1x3 mesh), the unpacked steps they wrap
(``kernel.step`` / ``step_donated``, ``ici.jit_serve_step``: the
differentials' entries) and the three Pallas kernels
at their bench shapes.  Nothing runs — these say nothing about results or
times.

One file on purpose: only one process may load the TPU's library, and the
worker that is handed this file is the one that describes the topology
(inside the fixture — never at import, never in a child process).
"""

import math
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.core import kernel, round as cround
from dragonboat_tpu.core.kstate import (
    empty_inbox,
    empty_input,
    inbox_columns,
    init_state,
    pack_state,
    round_columns,
)
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.parallel import fabric_pallas, ici, round as pround
from dragonboat_tpu.rsm import device_kv_pallas
from dragonboat_tpu.rsm.device_kv import DeviceKV

ROWS = 3072          # 1024 groups x 3 replicas
WIDE = 4096          # an engine as ``fleet-4k`` states it: one program over
                     # 4,096 rows (past the ~3k rows of README "The TPU design")


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run would warn and
    compile again): keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture
def device_kp(monkeypatch):
    """-> f(min_inbox): the KernelParams ``NodeHost._kernel_params`` picks
    on a TPU with the default ExpertConfig (one-hot ring reads)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    host = SimpleNamespace(config=SimpleNamespace(expert=ExpertConfig()))

    def pick(min_inbox: int = 0):
        kp = NodeHost._kernel_params(host, min_inbox)
        assert kp.onehot_reads and kp.log_cap == 1024
        return kp

    return pick


def _shapes(tree, sharding_of):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding_of(x)), tree)


def _step_args(kp, rows):
    rids = np.arange(rows, dtype=np.int32) % 3 + 1
    pids = np.zeros((rows, kp.num_peers), np.int32)
    return jax.eval_shape(lambda: (init_state(kp, rows, rids, pids),
                                   empty_inbox(kp, rows),
                                   empty_input(kp, rows)))


def _round_args(kp, rows, sharding):
    """What a served round's entry takes: the resident state (three
    arrays), the mesh's carried [G, Wi] inbox, one [G, Wu] upload."""
    state, _box, _inp = _step_args(kp, rows)
    resident = jax.eval_shape(lambda s: pack_state(kp, s), state)
    mat = lambda w: jax.ShapeDtypeStruct((rows, w), jnp.int32)  # noqa: E731
    return _shapes((resident, mat(inbox_columns(kp)[1]),
                    mat(round_columns(kp).up_width)), lambda x: sharding)


#: the unpacked step's program text by height: the round's cases hold
#: their gathers against it, and it is compiled once a height
_PLAIN_STEP: dict[int, str] = {}


def _plain_step(kp, rows, one_chip) -> str:
    if rows not in _PLAIN_STEP:
        compiled = kernel.step.lower(
            kp, *_shapes(_step_args(kp, rows), lambda x: one_chip)).compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
        _PLAIN_STEP[rows] = compiled.as_text()
    return _PLAIN_STEP[rows]


@pytest.mark.parametrize("entry,rows", [
    ("step", ROWS), ("step_donated", ROWS), ("step", WIDE)])
def test_kernel_step_compiles_for_v5e(one_chip, device_kp, entry, rows):
    kp = device_kp()
    if entry == "step":
        assert _plain_step(kp, rows, one_chip)
        return
    args = _shapes(_step_args(kp, rows), lambda x: one_chip)
    compiled = getattr(kernel, entry).lower(kp, *args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


@pytest.mark.parametrize("entry,rows", [
    ("step", ROWS), ("step_donated", ROWS), ("step", WIDE)])
def test_round_step_compiles_for_v5e(one_chip, device_kp, entry, rows):
    """The serial round as served: the resident state and one [G, Wu]
    upload in (4 arrays), the resident state and one [G, Wd] download out
    (4), and no gather added around the step (the save window is read by
    block select, core/round.py).  Again at the 4,096 rows ``fleet-4k``'s
    engines hold (depth 0, as every cell serves)."""
    kp = device_kp()
    rc = round_columns(kp)
    state, _box, up = _round_args(kp, rows, one_chip)
    assert len(jax.tree.leaves((state, up))) == 4
    compiled = getattr(cround, entry).lower(
        kp, kernel.step, state, up).compile()
    assert jax.eval_shape(
        lambda s, u: getattr(cround, entry)(kp, kernel.step, s, u)[1],
        state, up).shape == (rows, rc.down_width)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    assert compiled.as_text().count(" gather(") <= _plain_step(
        kp, rows, one_chip).count(" gather(")


@pytest.mark.parametrize("batch", [8, 1024])
def test_the_admission_program_compiles_for_v5e(one_chip, device_kp, batch):
    """``kstate.inject_program`` as ``_flush_injections`` calls it on an
    engine of 4,096 lanes: the resident state, a batch of lanes and their
    rows in, the resident state out; at the least batch and at the size
    class 12,288 ``start_replica`` calls fill."""
    from dragonboat_tpu.core.kstate import inject_program

    kp = device_kp()
    resident, _box, _up = _round_args(kp, WIDE, one_chip)
    one = lambda *shape, dtype=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        (batch, *shape), dtype, sharding=one_chip)
    rows = {f: one() for f in (
        "replica_id", "seed", "rand_timeout", "e_timeout", "h_timeout",
        "role", "term", "vote", "applied", "snap_index", "snap_term",
        "last", "committed")}
    rows.update({f: one(dtype=bool)
                 for f in ("check_quorum", "pre_vote", "quiesce_on")})
    rows.update(pid=one(kp.num_peers), kind=one(kp.num_peers),
                lt=one(kp.log_cap), lcc=one(kp.log_cap, dtype=bool))
    compiled = inject_program(kp).lower(resident, one(), rows).compile()
    out = jax.eval_shape(inject_program(kp), resident, one(), rows)
    assert out.cols.shape == resident.cols.shape
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1e9


@pytest.mark.parametrize("entry", ["jit_serve_step",
                                   "jit_serve_step_donated"])
def test_mesh_round_compiles_for_v5e(topo, device_kp, entry):
    """The mesh round as served, on the geometry of the test below: the
    step's collectives and no more (pack, unpack and the hub merge are
    per row, inside the shard_map)."""
    kp = device_kp(min_inbox=10)
    mesh = Mesh(np.array(topo.devices[:3]).reshape(1, 3), ("g", "r"))
    cl = ici.IciCluster(kp=kp, mesh=mesh, replicas=3, n_local=48,
                        num_groups=48)
    state, box, inp = _shapes(_step_args(kp, cl.total_rows),
                              lambda x: cl.sharding())
    cut = jax.ShapeDtypeStruct((cl.total_rows, kp.num_peers), bool,
                               sharding=cl.sharding())
    hlo = getattr(pround, entry).lower(
        kp, cl, *_round_args(kp, cl.total_rows, cl.sharding()), cut,
    ).compile().as_text()
    plain = ici.jit_serve_step.lower(
        kp, cl, state, box, inp, cut).compile().as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert hlo.count(f" {collective}(") == plain.count(
            f" {collective}("), collective
    assert "all-gather" in hlo


#: rows a chip of the two mesh geometries the benchmark serves
MESH_N_LOCAL = {"mesh-1x3": 48, "mesh-1x3-1024": 1024}


@pytest.mark.parametrize("where", ["one-chip", "one-chip-4096",
                                   *MESH_N_LOCAL])
def test_the_collection_compiles_for_v5e(topo, one_chip, device_kp, where):
    """The every-tenth-round collection (core/digest.py ``digest_program``:
    fleet statistics, health triage with its top-K, the invariant probe with
    its per-row sort) as an engine runs it: serial, the [G, K] sender ids
    uploaded; on the mesh, sliced out of the carried [G, Wi] inbox inside the
    program, state, inbox and carry sharded along G.  One int32 vector and
    the carried [G, 17] array out, the carry placed as it came in.  The
    last case is the width ``fleet-1k-mesh4`` serves: 1,024 rows a chip;
    the second the 4,096 rows of ONE program that ``fleet-4k`` serves."""
    from dragonboat_tpu.core import digest, health

    if where.startswith("one-chip"):
        kp, placement, boxed = device_kp(), None, False
        rows = WIDE if where.endswith("4096") else ROWS
        rows_sharding = one_chip
    else:
        kp = device_kp(min_inbox=10)
        mesh = Mesh(np.array(topo.devices[:3]).reshape(1, 3), ("g", "r"))
        n_local = MESH_N_LOCAL[where]
        cl = ici.IciCluster(kp=kp, mesh=mesh, replicas=3, n_local=n_local,
                            num_groups=n_local)
        rows, boxed = cl.total_rows, True
        placement = rows_sharding = cl.sharding()
    resident, box, _up = _round_args(kp, rows, rows_sharding)
    mat = lambda w: jax.ShapeDtypeStruct(  # noqa: E731
        (rows, w), jnp.int32, sharding=rows_sharding)
    inbox = box if boxed else mat(kp.inbox_cap)
    program = digest.digest_program(
        kp, health.DEFAULT_THRESHOLDS, health.DEFAULT_TOP_K, True, boxed,
        placement)
    compiled = program.lower(
        resident, inbox, mat(digest.CARRY_WIDTH)).compile()
    vec, carry = jax.eval_shape(program, resident, inbox,
                                mat(digest.CARRY_WIDTH))
    assert vec.shape == (digest.layout(
        rows, health.DEFAULT_TOP_K, True)[1],) and vec.dtype == jnp.int32
    assert carry.shape == (rows, digest.CARRY_WIDTH)
    if boxed:
        assert compiled.output_shardings[1] == placement
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1e9


def _collective_result_bytes(hlo: str) -> int:
    """Bytes of every all-gather's and all-reduce's result in the text."""
    size = {"pred": 1, "s8": 1, "u8": 1, "s32": 4, "u32": 4, "f32": 4}
    total = 0
    for line in hlo.splitlines():
        m = re.search(r" = (.*?) (?:all-gather|all-reduce)\(", line)
        if m:
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
                total += size[dtype] * math.prod(
                    int(d) for d in dims.split(",") if d)
    return total


@pytest.mark.parametrize("n_local", MESH_N_LOCAL.values())
def test_collective_bytes_cover_what_the_compiler_moves(topo, device_kp,
                                                        n_local):
    """``benchmark/collective_bytes.py`` counts the out-lanes ``route``
    reads; the compiler moves no more than that for the served mesh round
    (an all-gather brings a chip two thirds of its result; the one
    all-reduce is a gather of [G] lanes written as update-slice and sum),
    and not much less: a field ``route`` starts or stops reading shows.
    At 48 rows a chip (``upstream-48-mesh4``) and at the 1,024 that
    ``fleet-1k-mesh4`` serves, where the round is compiled for the first
    time at its real width (25 all-gathers there, no all-reduce)."""
    from benchmark import collective_bytes

    kp = device_kp(min_inbox=10)
    mesh = Mesh(np.array(topo.devices[:3]).reshape(1, 3), ("g", "r"))
    cl = ici.IciCluster(kp=kp, mesh=mesh, replicas=3, n_local=n_local,
                        num_groups=n_local)
    cut = jax.ShapeDtypeStruct((cl.total_rows, kp.num_peers), bool,
                               sharding=cl.sharding())
    hlo = pround.jit_serve_step.lower(
        kp, cl, *_round_args(kp, cl.total_rows, cl.sharding()), cut,
    ).compile().as_text()
    moved = _collective_result_bytes(hlo) * 2 // 3
    counted = 2 * collective_bytes.exchange_bytes_per_chip(kp, n_local)
    assert 0 < moved <= counted <= moved * 4 // 3, (moved, counted)


@pytest.mark.parametrize("entry", ["jit_serve_step",
                                   "jit_serve_step_donated"])
def test_mesh_serve_step_compiles_for_v5e(topo, device_kp, entry):
    """1x3 mesh of described chips, 48 group lanes per replica slot — the
    geometry the cell ``upstream-48-mesh4.write16`` serves — and the
    collectives are in the program."""
    kp = device_kp(min_inbox=10)    # as NodeHost._inject_mesh_shard asks
    mesh = Mesh(np.array(topo.devices[:3]).reshape(1, 3), ("g", "r"))
    cl = ici.IciCluster(kp=kp, mesh=mesh, replicas=3, n_local=48,
                        num_groups=48)
    state, box, inp = _shapes(_step_args(kp, cl.total_rows),
                              lambda x: cl.sharding())
    cut = jax.ShapeDtypeStruct((cl.total_rows, kp.num_peers), bool,
                               sharding=cl.sharding())
    hlo = getattr(ici, entry).lower(
        kp, cl, state, box, inp, cut).compile().as_text()
    assert "all-gather" in hlo


def _one(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_gather_pallas_compiles_for_v5e(one_chip):
    hlo = fabric_pallas._gather_pallas.lower(
        _one(one_chip, (ROWS, 15)), _one(one_chip, (ROWS, 10)), False,
    ).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_quorum_pallas_compiles_for_v5e(one_chip):
    hlo = fabric_pallas._quorum_pallas.lower(
        _one(one_chip, (ROWS, 3)), _one(one_chip, (ROWS, 3), bool),
        _one(one_chip, (ROWS,)), False,
    ).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_device_kv_apply_pallas_compiles_for_v5e(one_chip):
    kv = DeviceKV(table_cap=1024)
    G, AB = 1024, 32
    hlo = device_kv_pallas._apply_pallas.lower(
        kv, False, _one(one_chip, (G, 1024)), _one(one_chip, (G, 1024)),
        _one(one_chip, (G,)), _one(one_chip, (G, AB, 2)),
        _one(one_chip, (G, AB), bool),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
