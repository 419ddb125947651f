"""The cell ``fleet-1k.write16-hot96`` (1,024 shards x 3 replicas on one chip,
96 written to, the rest quiesced) and the two per-layer metrics that came
with it: their entries, each reader on a hand-made registry and on a
rehearsal's own, the mix's streams, and the cell rehearsed small on the CPU
backend (12 groups, 3 busy), sound and with a control."""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import layers, run, traffic
from benchmark.deployment import load_json, shard_settings, wanted_leaders
from dragonboat_tpu import telemetry, tracing
from test_benchmark_layers import view

CELL = "fleet-1k.write16-hot96"
NEW = ("quiesced_lanes_pct", "quiesce_wakes_per_kround")
COUNTERS = ("engine_fleet_lanes", "engine_quiesce_wakes")


def test_the_configuration_the_cell_and_its_metrics_have_their_entries():
    with open(run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "fleet-1k", "write16-hot96", 1)
    config = bench["configs"][-1]
    assert (config["name"], config["file"], config["reduced"]) == (
        "fleet-1k", "benchmark/configs/fleet-1k.json", ["servers"])
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert tuple(entries)[-2:] == NEW, "appended, behind what was there"
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["moves"] == "acked_ops_per_s"
        assert entries[name]["layer"] == entries["step_kernel_us"]["layer"]
    assert [(entries[n]["unit"], entries[n]["better"]) for n in NEW] == [
        ("%", "higher"), ("wakes/kround", "lower")]
    # no list that was there names the new cell
    for m in bench["end_to_end"] + bench["per_layer"][:-2]:
        assert CELL not in m.get("workloads", ())


def test_the_deployment_is_upstreams_at_full_width():
    cfg, fleet = load_json("configs", "fleet-1k"), load_json("configs", "fleet")
    assert (cfg["shards"], cfg["replicas"], cfg["expert"]) == (1024, 3, {})
    assert shard_settings(cfg) == {"quiesce": True}
    assert cfg["reduced"] == ["servers"] and list(cfg["reduced_why"]) == [
        "servers"]
    for key in ("raft", "guarantees", "engine", "state_machine", "logdb",
                "message_delay_ms", "step_entries", "step_programs"):
        assert cfg[key] == fleet[key], key
    assert cfg["rehearsal"] == {"shards": 12}
    assumed = " ".join(cfg["assumed"])
    assert "96 of the 1,024" in assumed and "quiesce on" in assumed
    wanted = wanted_leaders(range(1, 1025), 3)
    assert [sum(h == host for h in wanted.values())
            for host in (1, 2, 3)] == [342, 341, 341]
    mix, base = load_json("traffic", "write16-hot96"), load_json(
        "traffic", "write16")
    assert mix["active_shards"] == 96
    assert mix["rehearsal"] == {"warmup_s": 1.0, "warmup_acks_per_thread": 4,
                                "active_shards": 3}
    for key in set(base) - {"name", "what", "rehearsal"}:
        assert mix[key] == base[key], key


@pytest.mark.parametrize("seed", [1, 2**31 + 12345])
def test_every_seed_draws_32_busy_leaders_a_host(seed):
    mix = load_json("traffic", "write16-hot96")
    wanted = wanted_leaders(range(1, 1025), 3)
    busy = traffic.active_shards(mix, seed, wanted)
    assert len(busy) == len(set(busy)) == 96
    assert [sum(wanted[s] == host for s in busy)
            for host in (1, 2, 3)] == [32, 32, 32]
    assert busy == traffic.active_shards(mix, seed, wanted)
    assert busy != traffic.active_shards(mix, seed + 1, wanted)


def test_every_seed_gets_the_same_work():
    """The case ``test_benchmark_files.py`` makes for the three committed
    mixes, for this one: same writes per block whatever the seed; same
    seed, same stream; a seed above 2**31 is fine; every write a new
    16-byte command, also for a shard id above 255."""
    params = load_json("traffic", "write16-hot96")
    block = params["mix_block"]

    def first(seed, n=5 * block):
        stream = traffic.op_stream(params, seed, shard=777, gidx=95,
                                   first_key=0)
        return [next(stream) for _ in range(n)]

    a, b, c = first(1), first(2**31 + 12345), first(1)
    assert a == c and a != b
    for ops in (a, b):
        assert all(kind == "write" for kind, _, _ in ops)
        keys = [k for _, k, _ in ops]
        assert len(set(keys)) == len(keys)
        for _, key, value in ops:
            assert len(key) == 7
            assert len(traffic.command(key, value)) == params["payload_bytes"]


# -- the two readers -----------------------------------------------------------

def registries():
    """-> (before, after): three engines' digests.  Before the window 4
    digests each while the idle lanes were still awake; inside it 10 each
    with 928 of an engine's 1,024 lanes asleep, 300 rounds and 6 wakes."""
    before = {"engine_fleet_lanes{what=occupied}": 3 * 4 * 1024,
              "engine_fleet_lanes{what=quiesced}": 0,
              "engine_quiesce_wakes": 5,
              "engine_round_us.count{phase=total}": 120}
    after = {"engine_fleet_lanes{what=occupied}": 3 * 14 * 1024,
             "engine_fleet_lanes{what=quiesced}": 3 * 10 * 928,
             "engine_quiesce_wakes": 11,
             "engine_round_us.count{phase=total}": 420}
    return before, after


def test_readers_on_a_known_registry():
    before, after = registries()
    v = view(registry_before=before, registry_after=after)
    assert layers.load_reader("quiesced_lanes_pct")(v) == pytest.approx(
        100.0 * 928 / 1024)
    assert layers.load_reader("quiesce_wakes_per_kround")(v) == pytest.approx(
        1000.0 * 6 / 300)
    # a sound window: nothing woke
    after["engine_quiesce_wakes"] = before["engine_quiesce_wakes"]
    assert layers.load_reader("quiesce_wakes_per_kround")(
        view(registry_before=before, registry_after=after)) == 0.0
    # quiesce off: lanes held, none asleep
    after["engine_fleet_lanes{what=quiesced}"] = 0
    assert layers.load_reader("quiesced_lanes_pct")(
        view(registry_before=before, registry_after=after)) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_nothing(name):
    """The parent commit's registry lacks both counters (it has the round
    timer's): the reader returns None and does not raise."""
    before, after = registries()
    old = [{k: v for k, v in r.items() if not k.startswith(COUNTERS)}
           for r in (before, after)]
    assert layers.load_reader(name)(
        view(registry_before=old[0], registry_after=old[1])) is None
    assert layers.load_reader(name)(view()) is None
    assert layers.load_reader(name)(
        view(registry_before={}, registry_after={})) is None
    # no digest, no round in the window: nothing to divide by
    assert layers.load_reader(name)(
        view(registry_before=before, registry_after=dict(before))) is None


# -- the cell, rehearsed -------------------------------------------------------

SOUND, LOST_WRITE = 2**31 + 35, 36


@pytest.fixture(scope="module")
def rehearsal():
    """Twelve shards of which three are busy, on the CPU backend: a sound
    traced episode, then a control; the program's registry at both ends."""
    before = telemetry.GLOBAL.snapshot()
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main([
            "--workload", CELL, "--seed", "35", "--seconds", "2",
            "--trace", "1", "--rehearse",
            "--episodes", f"{SOUND},{LOST_WRITE}:lost-write"]) == 0
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    return lines, before, telemetry.GLOBAL.snapshot()


def phase(lines, name, seed=None):
    return next(line for line in lines if line.get("phase") == name
                and seed in (None, line.get("seed")))


def test_the_rehearsal_is_correct_and_its_idle_replicas_sleep(rehearsal):
    lines, _, _ = rehearsal
    deployed = phase(lines, "deployed")
    assert (deployed["config"], deployed["shards"], deployed["replicas"],
            deployed["active_shards"], deployed["idle_shards"]) == (
        "fleet-1k", 12, 3, 3, 9)
    assert deployed["shards_led_by_host"] == {"1": 4, "2": 4, "3": 4}
    assert deployed["busy_leaders_by_host"] == {"1": 1, "2": 1, "3": 1}
    assert phase(lines, "start")["shard"] == {"quiesce": True}
    sound = phase(lines, "episode", SOUND)
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    # 27 of 27 idle replicas asleep at both ends of the window, and no
    # leader moved in it
    drained = phase(lines, "drained", SOUND)
    assert drained["quiesced_lanes_at_window_ends"] == [27, 27]
    assert drained["leaders_moved_since_warmup"] == 0
    # the idle groups' check, beside its limit, in the result's line too
    check = next(line for line in lines if line.get("phase") == "check"
                 and line["seed"] == SOUND
                 and line["check"] == "idle_groups_that_did_not_serve")
    assert (check["value"], check["limit"], check["of"]) == (0, 0, 9)
    assert list(sound)[-1] == "checks"
    assert sound["checks"]["idle_groups_that_did_not_serve"] == {
        "value": 0, "limit": 0}
    # every metric of the cell is in the line (a rehearsal prints no value)
    units = {name: sound["metrics"][name]["unit"] for name in NEW}
    assert units == {"quiesced_lanes_pct": "%",
                     "quiesce_wakes_per_kround": "wakes/kround"}
    # (the three device-trace metrics want a capture, which a chip run has)
    assert {"round_ms", "lanes_per_round", "lane_us", "retire_columnar_pct",
            "engine_steps_per_s", "acked_per_step", "admission_fill_pct",
            "start_replica_ms"} <= set(sound["metrics"])
    assert all(m["value"] is None for m in sound["metrics"].values())


def test_the_control_comes_out_not_correct(rehearsal):
    lines, _, _ = rehearsal
    control = phase(lines, "episode", LOST_WRITE)
    assert control["fault"] == "lost-write"
    assert control["correct"] is False and control["attempted"] > 0


def test_the_readers_read_the_rehearsals_registry(rehearsal):
    """The counters the two readers want are in the program's registry
    after a rehearsal, and give a number: most of the held lanes asleep
    over the process (27 of 36 once the idle groups are in), and a count
    of wakes that is whole and not negative (the idle groups' check wakes
    nine groups after each window)."""
    _, before, after = rehearsal
    for key in ("engine_fleet_lanes{what=occupied}",
                "engine_fleet_lanes{what=quiesced}", "engine_quiesce_wakes",
                "engine_quiesce_enters{how=own_clock}",
                "engine_quiesce_enters{how=peer}"):
        assert key in after, key
    v = view(registry_before=before, registry_after=after)
    pct = layers.load_reader("quiesced_lanes_pct")(v)
    assert pct is not None and 0.0 < pct <= 75.0
    wakes = layers.load_reader("quiesce_wakes_per_kround")(v)
    assert wakes is not None and wakes >= 0.0
    grew = (after["engine_quiesce_wakes"]
            - before.get("engine_quiesce_wakes", 0))
    assert grew == int(grew) and grew >= 9
    entered = sum(after[k] - before.get(k, 0) for k in after
                  if k.startswith("engine_quiesce_enters"))
    assert entered >= 27


def test_a_round_record_says_how_many_lanes_slept(rehearsal):
    records = [r for r in tracing.ROUNDS.rounds() if "lanes_quiesced" in r]
    assert records, "no round record carries lanes_quiesced"
    assert max(r["lanes_quiesced"] for r in records) == 9
    assert all(0 <= r["lanes_quiesced"] <= 12 for r in records)
