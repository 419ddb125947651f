"""The cell ``fleet-4k.write16-hot96`` (4,096 shards x 3 replicas on one chip
in three engines of 4,096 lanes, 96 written to, 4,000 quiesced) and the two
per-layer metrics that came with it: the configuration file key by key
against ``fleet-1k.json``, every entry found by its name, the two readers on
a hand-made run at 1,024 and at 4,096 rows, and the cell rehearsed small on
the CPU backend ONCE for the module (12 groups, 3 busy, engines of 4,096
lanes and a 64-entry ring, so that the CPU steps no ``[4096, 1024]`` ring),
sound and with a control."""

import dataclasses
import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import layers, run, traffic
from benchmark.deployment import (
    Deployment, load_json, shard_settings, wanted_leaders)
from dragonboat_tpu import telemetry, tracing
from test_benchmark_fleet1k_cell import phase
from test_benchmark_fleet1k_mesh_cell import bench, by_name
from test_benchmark_layers import view

CELL = "fleet-4k.write16-hot96"
ELDER = "fleet-1k.write16-hot96"
CONFIG = "fleet-4k"
NEW = ("step_us_per_klane", "crossing_kb_per_round")
LANES = 4096


# -- the configuration file, key by key against fleet-1k's ---------------------

FLEET, CFG = load_json("configs", "fleet-1k"), load_json("configs", CONFIG)
#: keys whose value is this file's own; every other key is ``fleet-1k``'s
OWN = ("name", "source", "fixes", "shards", "expert", "chips", "assumed",
       "reduced", "reduced_why", "leader_placement")


@pytest.mark.parametrize("key", sorted(set(FLEET) - set(OWN)))
def test_the_file_keeps_the_thousand_group_fleets(key):
    assert CFG[key] == FLEET[key], key


def test_what_differs_is_the_width_and_what_states_it():
    assert set(CFG) == set(FLEET)
    assert {k for k in FLEET if CFG[k] != FLEET[k]} == set(OWN)
    assert (CFG["name"], CFG["shards"], CFG["replicas"], CFG["engine"]) == (
        CONFIG, LANES, 3, "kernel")
    assert CFG["expert"] == {"kernel_capacity": LANES}
    assert shard_settings(CFG) == {"quiesce": True}
    assert "warm_row_fetch" not in CFG and CFG["start_hosts_in_parallel"]
    assert CFG["rehearsal"] == {"shards": 12}
    assert CFG["chips"]["count"] == 1
    assert "4,096 lanes" in CFG["chips"]["mapping"]
    assert "12,288 replicas" in CFG["fixes"] and "4,000 idle" in CFG["fixes"]


def test_the_guarantees_are_the_fleets_word_for_word():
    assert CFG["guarantees"] == FLEET["guarantees"] == {
        "replicas": 3,
        "commit": "quorum (2 of 3)",
        "durability": "every acknowledged write fsynced in the on-disk "
                      "sharded-tan LogDB of a quorum before the ack",
        "apply": "an acknowledged write is applied on the leader before "
                 "the ack and on all three replicas once converged",
        "reads": "linearizable through ReadIndex"}
    assert CFG["raft"] == {"rtt_millisecond": 5, "election_rtt": 10,
                           "heartbeat_rtt": 2}
    assert CFG["logdb"].endswith("fsync honoured")


def test_the_cuts_are_servers_and_shards_with_both_reasons():
    assert CFG["reduced"] == ["servers", "shards"] == list(CFG["reduced_why"])
    why = CFG["reduced_why"]["shards"]
    # what the chip's memory would hold, the source's scale, what cut it
    assert "2,479,851" in why and "100k" in why and "set-up" in why
    entry = by_name(bench()["configs"], CONFIG)
    assert entry["reduced"] == CFG["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["source"]) <= 200 and "README.md:50" in entry["source"]
    assert "max concurrent groups" in entry["source"]
    assert "README.md:50" in CFG["source"] and "config 3" in CFG["source"]


def test_what_was_assumed_is_said():
    assumed = CFG["assumed"]
    assert assumed[:3] == FLEET["assumed"][:3] and len(assumed) == 7
    text = " ".join(assumed)
    assert "96 of the 4,096" in text and "4,000 idle" in text
    assert "kernel_capacity 4096" in text and "no such knob" in text
    assert "writes only" in text and "4,095 groups" in text


def test_the_placement_gives_every_host_a_third():
    wanted = wanted_leaders(range(1, LANES + 1), 3)
    by_host = [sum(h == host for h in wanted.values()) for host in (1, 2, 3)]
    assert by_host == [1366, 1365, 1365]
    assert "1,366 / 1,365 / 1,365" in CFG["leader_placement"]
    assert "32 / 32 / 32 busy" in CFG["leader_placement"]


@pytest.mark.parametrize("seed", [1, 2**31 + 12345])
def test_every_seed_draws_32_busy_leaders_a_host_of_4096_groups(seed):
    mix = load_json("traffic", "write16-hot96")
    wanted = wanted_leaders(range(1, LANES + 1), 3)
    busy = traffic.active_shards(mix, seed, wanted)
    assert len(set(busy)) == 96 and max(busy) <= LANES
    assert [sum(wanted[s] == host for s in busy)
            for host in (1, 2, 3)] == [32, 32, 32]
    assert max(busy) > 1024, "the draw reaches past fleet-1k's groups"
    traffic.validate(mix, LANES)        # the generator takes the width


# -- the entries, each found by its name ---------------------------------------

def test_the_cell_has_its_entry():
    cell = by_name(bench()["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "write16-hot96", 1)
    assert len(cell["why"]) <= 200 and "4,096 rows" in cell["why"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}


@pytest.mark.parametrize("name,unit,source,layer_of", [
    ("step_us_per_klane", "us/klane", "device_trace", "step_kernel_us"),
    ("crossing_kb_per_round", "KB/round", "program_counter",
     "round_crossings")])
def test_every_new_metric_has_its_entry(name, unit, source, layer_of):
    per_layer = bench()["per_layer"]
    m = by_name(per_layer, name)
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        unit, "lower", source, "acked_ops_per_s")
    assert m["layer"] == by_name(per_layer, layer_of)["layer"]
    assert m["workloads"] == [ELDER, CELL]


def test_the_cell_reports_what_every_cell_reports():
    """The metrics with no list are the new cell's too, the listed ones
    name it only where this PR appended it, and every reader is a file."""
    b = bench()
    mine = {m["name"] for m in run.metrics_for(b, "per_layer", CELL)}
    assert {"step_kernel_us", "step_roofline", "device_idle_pct", "round_ms",
            "round_upload_ms", "round_fetch_ms", "round_sweeps_ms",
            "upload_release_ms", "lanes_per_round", *NEW} <= mine
    assert {m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", ())} == set(NEW)
    assert {m["name"] for m in run.metrics_for(b, "end_to_end", CELL)} == {
        "acked_ops_per_s", "setup_s"}
    for name in NEW:
        assert callable(layers.load_reader(name))


# -- the two readers, on a hand-made run at both heights -----------------------

def hand_made(lanes: int, step_us: float):
    """A traced window of three engines of ``lanes`` lanes: 300 rounds, a
    round's upload ``[lanes, 231]`` and download ``[lanes, 345]`` int32,
    30 collections of ``[lanes, 8]`` sender ids up and 169 ints down."""
    up, down = lanes * 231 * 4, lanes * 345 * 4
    digest = lanes * 8 * 4 + 169 * 4
    before = {"engine_round_us.count{phase=total}": 120,
              "device_crossing_bytes{tag=round_up}": 120 * up,
              "device_crossing_bytes{tag=round_down}": 120 * down,
              "device_crossing_bytes{tag=inject_up}": 5_000_000,
              **{f"engine_lanes{{what=capacity,engine=nh{i}}}": lanes
                 for i in (1, 2, 3)}}
    after = {"engine_round_us.count{phase=total}": 420,
             "device_crossing_bytes{tag=round_up}": 420 * up,
             "device_crossing_bytes{tag=round_down}": 420 * down,
             "device_crossing_bytes{tag=digest_down}": 30 * digest,
             "device_crossing_bytes{tag=inject_up}": 5_000_000,
             "engine_lanes{what=capacity,engine=closed}": 0,
             **{f"engine_lanes{{what=capacity,engine=nh{i}}}": lanes
                for i in (1, 2, 3)}}
    capture = {"window_s": 3.0, "busy_s": 0.1, "devices_with_operations": 1,
               "programs": {"jit_step": {"calls": 90,
                                         "seconds": 90 * step_us / 1e6}}}
    want_kb = (up + down + digest / 10) / 1000
    return view(registry_before=before, registry_after=after,
                capture=capture), want_kb


@pytest.mark.parametrize("lanes,step_us,per_klane", [
    (1024, 1472.0, 1437.5), (4096, 5888.0, 1437.5), (4096, 4096.0, 1000.0)])
def test_the_readers_on_a_known_run(lanes, step_us, per_klane):
    v, want_kb = hand_made(lanes, step_us)
    assert layers.load_reader("step_us_per_klane")(v) == pytest.approx(
        per_klane)
    assert layers.load_reader("crossing_kb_per_round")(v) == pytest.approx(
        want_kb)
    # by the shapes: ~2.4 MB a round at 1,024 lanes, ~9.4 MB at 4,096
    assert want_kb == pytest.approx(9.4e3 if lanes == LANES else 2.36e3,
                                    rel=0.01)


def test_engines_of_two_heights_behind_one_step_time_read_nothing():
    v, _ = hand_made(LANES, 5888.0)
    mixed = {**v.registry_after,
             "engine_lanes{what=capacity,engine=mesh:m}": 1024}
    assert layers.load_reader("step_us_per_klane")(view(
        registry_before=v.registry_before, registry_after=mixed,
        capture=v.capture)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counter_or_the_gauge_reads_nothing(name):
    """The parent commit's registry has neither ``device_crossing_bytes``
    nor ``engine_lanes``: the reader returns None and does not raise; nor
    without a capture, nor without a round in the window."""
    v, _ = hand_made(LANES, 5888.0)
    old = [{k: x for k, x in r.items()
            if not k.startswith(("device_crossing_bytes", "engine_lanes"))}
           for r in (v.registry_before, v.registry_after)]
    read = layers.load_reader(name)
    assert read(view(registry_before=old[0], registry_after=old[1],
                     capture=v.capture)) is None
    assert read(view(registry_before={}, registry_after={})) is None
    assert read(view(registry_before=v.registry_before,
                     registry_after=dict(v.registry_before),
                     capture=None)) is None


# -- the cell, rehearsed once --------------------------------------------------

SOUND, LOST_WRITE = 2**31 + 42, 2**31 + 43


@pytest.fixture(scope="module")
def rehearsal():
    """Twelve shards of which three are busy in engines of 4,096 lanes, on
    the CPU backend: a sound traced episode, then a control; the program's
    registry at both ends.  The engines keep the file's 4,096 lanes and take a 64-entry ring
    (and the apply batch and compaction overhead that fit one, as
    ``tests/test_wide_served.py`` has them) from the test (the CPU then steps ``[4096, 64]`` rings and not
    ``[4096, 1024]``: the height is what is rehearsed, and the round's upload
    and download do not depend on the ring).  The ring of round records is
    emptied before it and behind it (a worker's files read their own
    engines' records out of it)."""
    mp = pytest.MonkeyPatch()
    stated = Deployment._expert
    mp.setattr(Deployment, "_expert", lambda self, every: dataclasses.replace(
        stated(self, every), kernel_log_cap=64, kernel_apply_batch=16,
        kernel_compaction_overhead=8))
    tracing.ROUNDS.reset()
    before = telemetry.GLOBAL.snapshot()
    out = io.StringIO()
    try:
        with redirect_stdout(out):
            assert run.main([
                "--workload", CELL, "--seed", "42", "--seconds", "1",
                "--trace", "1", "--rehearse",
                "--episodes", f"{SOUND},{LOST_WRITE}:lost-write"]) == 0
        lines = [json.loads(line) for line in out.getvalue().splitlines()]
        yield (lines, before, telemetry.GLOBAL.snapshot(),
               tracing.ROUNDS.rounds())
    finally:
        mp.undo()
        tracing.ROUNDS.reset()


def test_the_rehearsal_is_correct_and_its_idle_replicas_sleep(rehearsal):
    lines = rehearsal[0]
    deployed = phase(lines, "deployed")
    assert (deployed["config"], deployed["shards"], deployed["replicas"],
            deployed["active_shards"], deployed["idle_shards"]) == (
        CONFIG, 12, 3, 3, 9)
    assert deployed["shards_led_by_host"] == {"1": 4, "2": 4, "3": 4}
    assert deployed["busy_leaders_by_host"] == {"1": 1, "2": 1, "3": 1}
    for key in ("start_replicas_s", "elect_s", "place_leaders_s"):
        assert deployed[key] > 0, key          # set-up, phase by phase
    assert phase(lines, "start")["shard"] == {"quiesce": True}
    sound = phase(lines, "episode", SOUND)
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    drained = phase(lines, "drained", SOUND)
    assert drained["quiesced_lanes_at_window_ends"] == [27, 27]
    assert drained["leaders_moved_since_warmup"] == 0
    assert drained["compiles_in_window"]["compiles"] == 0
    assert sound["checks"]["idle_groups_that_did_not_serve"] == {
        "value": 0, "limit": 0}
    for check in ("lost_acknowledged_writes", "keys_replicas_disagree_on",
                  "wrong_linearizable_read_backs",
                  "shards_with_unequal_sm_hash"):
        assert sound["checks"][check] == {"value": 0, "limit": 0}, check


def test_the_control_at_this_height_comes_out_not_correct(rehearsal):
    control = phase(rehearsal[0], "episode", LOST_WRITE)
    assert control["fault"] == "lost-write"
    assert control["correct"] is False and control["attempted"] > 0


def test_the_rehearsal_prints_the_new_names_without_a_value(rehearsal):
    """``crossing_kb_per_round`` is in the line as null (a rehearsal prints
    no value); ``step_us_per_klane`` wants a device capture, as
    ``step_kernel_us`` does, and a rehearsal has none."""
    sound = phase(rehearsal[0], "episode", SOUND)
    assert sound["metrics"]["crossing_kb_per_round"] == {
        "value": None, "unit": "KB/round"}
    assert "step_us_per_klane" not in sound["metrics"]
    assert "step_kernel_us" not in sound["metrics"]
    assert all(m["value"] is None for m in sound["metrics"].values())


def test_the_engines_were_4096_lanes_tall_and_the_bytes_say_so(rehearsal):
    """The gauge states the capacity the file stated; a round moved the
    bytes of ``[4096, 231]`` up and ``[4096, 345]`` down, so the reader on
    the rehearsal's own registry reads ~9.4 MB a round; the round records
    say how many lanes an engine held."""
    _, before, after, rounds = rehearsal
    stated = {k: v for k, v in after.items() if k.startswith(
        "engine_lanes{what=capacity,") and k not in before}
    assert list(stated.values()) == [0, 0, 0]     # three engines, closed now
    rounds_in = (after["engine_round_us.count{phase=total}"]
                 - before.get("engine_round_us.count{phase=total}", 0))
    for tag, width in (("round_up", 231), ("round_down", 345)):
        key = f"device_crossing_bytes{{tag={tag}}}"
        per_round = (after[key] - before.get(key, 0)) / rounds_in
        assert per_round == pytest.approx(LANES * width * 4, rel=0.02), tag
    assert after["device_crossing_bytes{tag=inject_up}"] > before.get(
        "device_crossing_bytes{tag=inject_up}", 0)
    kb = layers.load_reader("crossing_kb_per_round")(
        view(registry_before=before, registry_after=after))
    assert 9.4e3 <= kb <= 9.6e3, kb
    held = [r["lanes_held"] for r in rounds if "lanes_held" in r]
    assert held and max(held) == 12 and held[-1] == 12
