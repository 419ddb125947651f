"""The readers of the round timer and of the counters beside it, on the
hand-made run of ``test_benchmark_layers.py``: a known registry delta gives a
known value, a registry without the key (a program older than the
instrument) gives None; and the traced rehearsal prints every new name."""

import json

import pytest

from benchmark import layers, run
from test_benchmark_layers import view

PHASE_OF = {"round_ms": "total", "round_wait_ms": "wait",
            "round_stage_ms": "stage", "round_upload_ms": "upload",
            "round_fetch_ms": "fetch", "round_resolve_ms": "resolve",
            "round_save_ms": "save", "round_finish_ms": "finish"}
#: mean microseconds of each phase over the window's 50 rounds
PHASE_US = {"total": 200_000, "wait": 50_000, "stage": 20_000,
            "upload": 30_000, "fetch": 60_000, "resolve": 25_000,
            "save": 45_000, "finish": 20_000}


def registries():
    """-> (before, after): 10 rounds before the window, 50 inside it, the
    earlier ones ten times as slow (a cumulative reading would show)."""
    before, after = {}, {}
    for phase, us in PHASE_US.items():
        n0 = 9 if phase == "wait" else 10
        before[f"engine_round_us.count{{phase={phase}}}"] = n0
        before[f"engine_round_us.sum{{phase={phase}}}"] = 10.0 * us * n0
        after[f"engine_round_us.count{{phase={phase}}}"] = n0 + 50
        after[f"engine_round_us.sum{{phase={phase}}}"] = (
            10.0 * us * n0 + 50.0 * us)
    before.update({"engine_round_cpu_us.sum": 1e6,
                   "engine_round_cpu_us.count": 10,
                   "engine_props_staged": 100,
                   "engine_props_deferred": 7,
                   "engine_prop_slots_offered": 160,
                   "read_stage_wait_us.sum": 9e6,
                   "read_stage_wait_us.count": 3,
                   "device_crossing_us.sum{tag=input_up}": 4e6,
                   "device_crossing_us.count{tag=input_up}": 10,
                   "device_crossing_us.sum{tag=lazy_out}": 8e6,
                   "device_crossing_us.count{tag=lazy_out}": 100,
                   "xla_compile_us.sum{phase=none}": 30e6,
                   "xla_compile_us.count{phase=none}": 40})
    after.update({"engine_round_cpu_us.sum": 1e6 + 50 * 30_000,
                  "engine_round_cpu_us.count": 60,
                  "engine_props_staged": 100 + 390,
                  "engine_props_deferred": 7 + 110,
                  "engine_prop_slots_offered": 160 + 640,
                  "read_stage_wait_us.sum": 9e6 + 40 * 250_000,
                  "read_stage_wait_us.count": 43,
                  "nodehost_start_replica_us.sum{phase=total}": 144 * 3e5,
                  "nodehost_start_replica_us.count{phase=total}": 144,
                  "nodehost_start_replica_us.sum{phase=build}": 144 * 1e5,
                  "nodehost_start_replica_us.count{phase=build}": 144,
                  # 50 rounds: one upload of 3 ms and 19 pulls of 1 ms
                  # each; a tag first seen inside the window counts whole
                  "device_crossing_us.sum{tag=input_up}": 4e6 + 50 * 3_000,
                  "device_crossing_us.count{tag=input_up}": 10 + 50,
                  "device_crossing_us.sum{tag=lazy_out}": 8e6 + 900 * 1_000,
                  "device_crossing_us.count{tag=lazy_out}": 100 + 900,
                  "device_crossing_us.sum{tag=lt_rows}": 50 * 1_000,
                  "device_crossing_us.count{tag=lt_rows}": 50,
                  "xla_compile_us.sum{phase=none}": 30e6,
                  "xla_compile_us.count{phase=none}": 40,
                  "xla_compile_us.sum{phase=stage}": 12.5e6,
                  "xla_compile_us.count{phase=stage}": 144,
                  "engine_add_shard_lock_us.sum": 144 * 2.5e5,
                  "engine_add_shard_lock_us.count": 144})
    return before, after


WANT = {name: PHASE_US[phase] / 1e3 for name, phase in PHASE_OF.items()}
WANT.update({
    "round_oncpu_pct": 15.0,            # 30 ms on the CPU of 200 ms a round
    "admission_fill_pct": 100 * 390 / 640,
    "read_stage_wait_ms": 250.0,
    "start_replica_ms": 300.0,          # cumulative: set-up precedes the window
    "round_crossings": 20.0,            # 1,000 crossings in 50 rounds
    "crossing_ms": 1.1,                 # 150 + 900 + 50 ms over them
    "props_deferred_pct": 22.0,         # 110 put back of 500 looked at
    "setup_compile_s": 42.5,            # cumulative, every phase
    "add_shard_lock_ms": 250.0,         # cumulative
})
#: cumulative readers: they read the window's end alone
CUMULATIVE = ("start_replica_ms", "setup_compile_s", "add_shard_lock_ms")


def timed_view(**over):
    before, after = registries()
    return view(registry_before=before, registry_after=after, **over)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    assert layers.load_reader(name)(timed_view()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_the_instrument_reads_nothing(name):
    """The parent commit's registry holds none of these keys: the reader
    returns None and does not raise, and the line leaves the metric out."""
    assert layers.load_reader(name)(view()) is None
    assert layers.load_reader(name)(
        view(registry_before={}, registry_after={})) is None


def test_the_phases_sum_to_the_round_and_give_the_rate():
    """What PERF.md checks on every traced run: stage..finish add up to
    ``round_ms``, and ``1000 / (round_ms + round_wait_ms)`` is an engine's
    rounds per second (here 50 rounds of 250 ms: 4 a second)."""
    v = timed_view()
    got = {name: layers.load_reader(name)(v) for name in PHASE_OF}
    inside = sum(ms for name, ms in got.items()
                 if name not in ("round_ms", "round_wait_ms"))
    assert inside == pytest.approx(got["round_ms"])
    assert 1000 / (got["round_ms"] + got["round_wait_ms"]) == \
        pytest.approx(4.0)


def test_an_idle_window_reads_nothing():
    """No round, no staged proposal, no read in the window: the deltas'
    denominators are 0 and the readers leave the metrics out."""
    before, _ = registries()
    v = view(registry_before=before, registry_after=dict(before))
    for name in WANT:
        if name not in CUMULATIVE:
            assert layers.load_reader(name)(v) is None, name


def test_every_new_metric_has_its_entry():
    with open(run.ROOT + "/BENCHMARK.json") as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert set(WANT) <= set(entries)
    assert [n for n in entries][-len(WANT):] == [
        "round_ms", "round_wait_ms", "round_stage_ms", "round_upload_ms",
        "round_fetch_ms", "round_resolve_ms", "round_save_ms",
        "round_finish_ms", "round_oncpu_pct", "admission_fill_pct",
        "read_stage_wait_ms", "start_replica_ms", "round_crossings",
        "crossing_ms", "props_deferred_pct", "setup_compile_s",
        "add_shard_lock_ms"]
    assert entries["read_stage_wait_ms"]["workloads"] == [
        "upstream-48.mixed9to1"]
    assert {n for n in WANT if entries[n]["moves"] == "setup_s"} == set(
        CUMULATIVE)
    assert entries["admission_fill_pct"]["source"] == "program_counter"


def test_the_traced_rehearsal_prints_every_new_name(capsys):
    """The mixed cell holds every one: rehearsed small on the CPU backend
    with ``--trace 1``, its last line names each (values withheld, as for
    every metric of a rehearsal), and ``correct`` stays true."""
    assert run.main([
        "--workload", "upstream-48.mixed9to1", "--seed", str(2**31 + 25),
        "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert set(WANT) <= set(last["metrics"])
    units = {name: last["metrics"][name]["unit"] for name in WANT}
    assert units["round_ms"] == "ms" and units["round_oncpu_pct"] == "%"
    assert all(last["metrics"][name]["value"] is None for name in WANT)
