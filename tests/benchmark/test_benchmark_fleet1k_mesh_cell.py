"""The cell ``fleet-1k-mesh4.write16-hot96`` (1,024 shards x 3 replicas in
ONE mesh engine over three of four chips, 96 written to, the rest quiesced)
and the two per-layer metrics that came with it: the configuration file key
by key against the two it is made of, every entry found by its name, the two
readers on the hand-made mesh run of ``test_benchmark_mesh_cell.py`` at 1,024
rows a chip, and the cell rehearsed small on four forced host devices (12
groups, 3 busy), sound and with a control."""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import collective_bytes, layers, run
from benchmark.deployment import load_json, shard_settings
from dragonboat_tpu import tracing
from test_benchmark_fleet1k_cell import phase
from test_benchmark_layers import view
from test_benchmark_mesh_cell import MESH_CONFIG, mesh_view

CELL = "fleet-1k-mesh4.write16-hot96"
CONFIG = "fleet-1k-mesh4"
NEW = ("exchange_us_per_chip", "exchange_roofline")
WIDE = dict(MESH_CONFIG, mesh={"g_size": 1, "replicas": 3, "n_local": 1024})
ICI_BYTES_PER_S = 200e9


def bench():
    with open(run.ROOT + "/BENCHMARK.json") as f:
        return json.load(f)


def by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, f"{name}: {len(found)} entries"
    return found[0]


# -- the configuration file, key by key ----------------------------------------

FLEET, MESH, CFG = (load_json("configs", n)
                    for n in ("fleet-1k", "upstream-48-mesh4", CONFIG))
#: what the new file takes from the mesh configuration as it is
AS_THE_MESH = ("replicas", "servers", "engine", "expert", "raft",
               "state_machine", "logdb", "message_delay_ms", "guarantees",
               "step_entries", "step_programs", "capture_seconds", "reduced",
               "chips_with_state", "warm_row_fetch", "start_hosts_in_parallel")
#: and from ``fleet-1k``
AS_THE_FLEET = ("shards", "replicas", "servers", "expert", "shard", "raft",
                "state_machine", "logdb", "message_delay_ms", "guarantees",
                "capture_seconds", "assumed", "rehearsal", "leader_placement")
#: keys whose value is this file's own
OWN = ("name", "source", "fixes", "chips", "mesh", "reduced_why",
       "start_hosts_in_parallel_why")


@pytest.mark.parametrize("key", AS_THE_MESH)
def test_the_file_keeps_the_mesh_configurations(key):
    assert CFG[key] == MESH[key], key


@pytest.mark.parametrize("key", AS_THE_FLEET)
def test_the_file_keeps_the_fleets(key):
    assert CFG[key] == FLEET[key], key


def test_what_differs_is_the_width_the_placement_and_the_names():
    assert set(CFG) == set(MESH) | {"shard", "start_hosts_in_parallel_why"}
    assert set(CFG) == set(AS_THE_MESH) | set(AS_THE_FLEET) | set(OWN)
    assert CFG["start_hosts_in_parallel_why"].startswith("false")
    assert {k for k in MESH if CFG[k] != MESH[k]} == {
        "name", "source", "fixes", "shards", "chips", "assumed",
        "reduced_why", "rehearsal", "mesh", "leader_placement"}
    assert (CFG["shards"], CFG["engine"], CFG["expert"]) == (1024, "mesh", {})
    assert shard_settings(CFG) == {"quiesce": True}
    assert CFG["mesh"] == dict(MESH["mesh"], n_local=1024)
    assert CFG["chips"]["count"] == 4 and CFG["chips_with_state"] == 3
    assert CFG["rehearsal"] == {"shards": 12}
    assert CFG["reduced"] == ["servers", "chips_with_state"] == list(
        CFG["reduced_why"])
    assert CFG["reduced_why"] == {
        "servers": FLEET["reduced_why"]["servers"],
        "chips_with_state": MESH["reduced_why"]["chips_with_state"]}
    assert CFG["source"].startswith(FLEET["source"].split(";")[0])
    assert CFG["source"].endswith(
        "placed on one four-chip v5e host by config.MeshSpec")
    assert CFG["source"] not in (FLEET["source"], MESH["source"])
    assert len(CFG["source"]) <= 200


# -- the entries, by name -----------------------------------------------------

def test_the_configuration_and_the_cell_have_their_entries():
    b = bench()
    config = by_name(b["configs"], CONFIG)
    assert (config["file"], config["reduced"], config["source"]) == (
        f"benchmark/configs/{CONFIG}.json", CFG["reduced"], CFG["source"])
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "write16-hot96", 4)
    # the mix is the one-chip fleet's, unedited
    assert by_name(b["workloads"], "fleet-1k.write16-hot96")["traffic"] == \
        cell["traffic"]
    four = [w["name"] for w in b["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(b["workloads"]) // 2)
    # every end-to-end metric the one-chip fleet reports, under its bound
    e2e = {m["name"] for m in b["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"acked_ops_per_s", "setup_s"}


@pytest.mark.parametrize("name,unit,better", [
    ("exchange_us_per_chip", "us", "lower"),
    ("exchange_roofline", "%", "higher")])
def test_a_new_metric_has_its_entry(name, unit, better):
    entries = bench()["per_layer"]
    m = by_name(entries, name)
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        unit, better, "device_trace", "acked_ops_per_s")
    assert m["workloads"] == [CELL]
    assert m["layer"] == by_name(entries, "collective_roofline")["layer"]


def test_no_list_that_was_there_names_the_new_cell():
    b = bench()
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", ()), m["name"]
    # the sharded step's own share has no list, so it reports here too
    assert "workloads" not in by_name(b["per_layer"], "step_roofline")


# -- the two readers ----------------------------------------------------------

def test_the_exchange_at_1024_rows_a_chip_is_21_times_the_bytes():
    narrow = collective_bytes.received_per_step(MESH_CONFIG)
    wide = collective_bytes.received_per_step(WIDE)
    assert (narrow, wide) == (68_064, 1_452_032)
    assert wide / narrow == pytest.approx(1024 / 48)
    assert collective_bytes.received_per_step(CFG) == wide


@pytest.mark.parametrize("config", [MESH_CONFIG, WIDE],
                         ids=["n_local-48", "n_local-1024"])
def test_readers_on_a_known_run(config):
    """6 ms of collectives over 300 chip-steps is 20 us a step and chip;
    the share is ``collective_roofline``'s on the same view, from the
    function's bytes at the configuration's own ``n_local``."""
    run_ = mesh_view(config=config)
    assert layers.load_reader("exchange_us_per_chip")(run_) == \
        pytest.approx(20.0)
    least_s = collective_bytes.received_per_step(config) / ICI_BYTES_PER_S
    share = layers.load_reader("exchange_roofline")(run_)
    assert share == pytest.approx(100 * least_s / 20e-6)
    assert share == pytest.approx(
        layers.load_reader("collective_roofline")(run_))
    assert 0.0 < share < 105.0
    # per chip, where ``collective_us_per_step`` sums the three
    assert layers.load_reader("collective_us_per_step")(run_) == \
        pytest.approx(3 * 20.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """A capture without collectives, no capture, a window without a step
    of the program, a configuration with no ``mesh`` key (every one-chip
    cell, and the parent's program under these files): None, no raise."""
    read = layers.load_reader(name)
    quiet = dict(mesh_view().capture, collective_s=0.0)
    assert read(mesh_view(config=WIDE, capture=quiet)) is None
    assert read(mesh_view(config=WIDE, capture=None)) is None
    no_step = dict(mesh_view().capture, programs={})
    assert read(mesh_view(config=WIDE, capture=no_step)) is None
    assert read(view()) is None


# -- the cell, rehearsed -------------------------------------------------------

SOUND, LOST_WRITE = 2**31 + 39, 40


@pytest.fixture(scope="module")
def rehearsal():
    """Twelve shards of which three are busy, one mesh engine on three of
    four forced host devices: a sound traced episode, then a control.
    The round records go when the module is done: the one engine's count
    27 lanes asleep, and ``test_benchmark_fleet1k_cell.py`` reads the
    process's ring for its own engines' 9."""
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main([
            "--workload", CELL, "--seed", "39", "--seconds", "2",
            "--trace", "1", "--rehearse",
            "--episodes", f"{SOUND},{LOST_WRITE}:lost-write"]) == 0
    yield [json.loads(line) for line in out.getvalue().splitlines()]
    tracing.ROUNDS.reset()


def test_the_rehearsal_is_one_mesh_engine_with_every_link_resident(rehearsal):
    deployed = phase(rehearsal, "deployed")
    assert (deployed["config"], deployed["shards"], deployed["replicas"],
            deployed["active_shards"], deployed["idle_shards"]) == (
        CONFIG, 12, 3, 3, 9)
    assert len(deployed["state_devices"]) == 3
    assert len(deployed["link_classes"]) == 6
    assert set(deployed["link_classes"].values()) == {"resident"}
    assert deployed["shards_led_by_host"] == {"1": 4, "2": 4, "3": 4}
    assert deployed["busy_leaders_by_host"] == {"1": 1, "2": 1, "3": 1}
    assert phase(rehearsal, "start")["shard"] == {"quiesce": True}


def test_the_rehearsal_is_correct_and_its_idle_replicas_sleep(rehearsal):
    sound = phase(rehearsal, "episode", SOUND)
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    # 27 of 27 idle replicas asleep at both ends of the window by the
    # ONE engine's digest, and no leader moved in it
    drained = phase(rehearsal, "drained", SOUND)
    assert drained["quiesced_lanes_at_window_ends"] == [27, 27]
    assert drained["leaders_moved_since_warmup"] == 0
    assert drained["compiles_in_window"]["compiles"] == 0
    check = next(line for line in rehearsal if line.get("phase") == "check"
                 and line["seed"] == SOUND
                 and line["check"] == "idle_groups_that_did_not_serve")
    assert (check["value"], check["limit"], check["of"]) == (0, 0, 9)
    assert list(sound)[-1] == "checks"


def test_the_rehearsal_reports_what_has_something_to_read(rehearsal):
    """The round timer's and the counters' metrics are in the line (a
    rehearsal withholds every value); the two new readers and the kernel's
    want a device capture, which a chip run has, and the five listed elders
    do not name the cell."""
    metrics = phase(rehearsal, "episode", SOUND)["metrics"]
    assert {"round_ms", "round_upload_ms", "round_fetch_ms", "crossing_ms",
            "round_crossings", "lanes_per_round", "lane_us",
            "round_sweeps_ms", "upload_release_ms", "round_finish_ms",
            "retire_named_pct", "engine_steps_per_s",
            "acked_per_step", "admission_fill_pct", "start_replica_ms",
            "inject_batch_rows"} <= set(metrics)
    assert all(m["value"] is None for m in metrics.values())
    for name in NEW + ("collective_us_per_step", "collective_roofline",
                       "mesh_hub_msgs_per_step", "quiesced_lanes_pct",
                       "quiesce_wakes_per_kround", "device_idle_pct",
                       "step_kernel_us", "step_roofline"):
        assert name not in metrics, name


def test_the_control_comes_out_not_correct(rehearsal):
    control = phase(rehearsal, "episode", LOST_WRITE)
    assert control["fault"] == "lost-write"
    assert control["correct"] is False and control["attempted"] > 0
