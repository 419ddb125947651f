"""The fourteen readers of what PR 37 added to the round timer (a CPU clock
at every phase boundary, parts inside the largest phases, the moments a
round's messages leave, the process's CPU time), on the hand-made run of
``test_benchmark_layers.py``: a known registry delta gives a known value, a
registry without the key (a program older than the instrument) gives None;
their entries, found by name; the readings close on one another as PERF.md
checks them on every traced run; and the traced rehearsal prints every name.
"""

import json

import pytest

from benchmark import layers, run
from test_benchmark_layers import view

ROUNDS = 50
#: mean microseconds a round, inside the window: host time of a phase ...
PHASE_US = {"total": 200_000, "stage": 20_000, "upload": 30_000,
            "fetch": 60_000, "resolve": 25_000, "save": 45_000,
            "finish": 20_000}
#: ... the engine thread's CPU time in it, which the timer reads by phase in
#: some rounds only (here every other: 25 of the window's 50) ...
PHASE_CPU_READ = 25
PHASE_CPU_US = {"stage": 9_000, "upload": 6_000, "fetch": 1_000,
                "resolve": 12_000, "save": 4_000, "finish": 18_000}
#: ... and the parts
PART_US = {"stage.reset": 1_500, "stage.tick": 500, "upload.release": 7_000,
           "upload.applied": 2_000, "resolve.send": 11_000,
           "finish.apply": 6_000, "finish.ack": 12_000}
#: the rounds of the window that sent, and the mean time to each mark
MARK_US = {"replicates_out": (20, 90_000), "responses_out": (40, 170_000)}
PROCESS_CPU_US = 9_500_000          # of a 10 s window: 95% of one core

WANT = {
    "round_stage_cpu_ms": 9.0, "round_upload_cpu_ms": 6.0,
    "round_fetch_cpu_ms": 1.0, "round_resolve_cpu_ms": 12.0,
    "round_save_cpu_ms": 4.0, "round_finish_cpu_ms": 18.0,
    "finish_apply_ms": 6.0, "finish_ack_ms": 12.0,
    "upload_release_ms": 7.0, "round_sweeps_ms": 4.0,
    "resolve_send_ms": 11.0, "round_replicates_out_ms": 90.0,
    "round_responses_out_ms": 170.0,
    "engine_cpu_share_pct": 100 * ROUNDS * 50_000 / PROCESS_CPU_US,
}
#: in ISSUE 37's order, which is the order of their entries (of its sixteen
#: ``finish_ack_cpu_ms`` went with the CPU-clock reads a row, and
#: ``process_oncpu_pct`` because it reads the runtime's native threads, not
#: the interpreter: PERF.md section 6, PR 37)
ORDER = (
    "round_stage_cpu_ms", "round_upload_cpu_ms", "round_fetch_cpu_ms",
    "round_resolve_cpu_ms", "round_save_cpu_ms", "round_finish_cpu_ms",
    "finish_apply_ms", "finish_ack_ms", "upload_release_ms",
    "round_sweeps_ms", "resolve_send_ms", "round_replicates_out_ms",
    "round_responses_out_ms", "engine_cpu_share_pct")
ONE_CHIP = ["upstream-48.write16", "one-shard.write16",
            "upstream-48.mixed9to1", "fleet.write16",
            "fleet-1k.write16-hot96"]
LISTED = ("resolve_send_ms", "round_replicates_out_ms",
          "round_responses_out_ms")


def registries():
    """-> (before, after): 10 rounds before the window, 50 inside it, the
    earlier ones ten times as slow (a cumulative reading would show)."""
    before, after = {}, {}

    def hist(name, label, us, n=ROUNDS, n0=10):
        for part, v0, v1 in (("count", n0, n0 + n),
                             ("sum", 10.0 * us * n0,
                              10.0 * us * n0 + float(us) * n)):
            before[f"{name}.{part}{label}"] = v0
            after[f"{name}.{part}{label}"] = v1

    def summed(name, label, us, n=ROUNDS, n0=10):
        """(the timer's plain sums: a callback gauge, no count of its own)"""
        hist(name, label, us, n, n0)
        del before[f"{name}.count{label}"], after[f"{name}.count{label}"]

    for phase, us in PHASE_US.items():
        hist("engine_round_us", f"{{phase={phase}}}", us)
    for phase, us in PHASE_CPU_US.items():
        summed("engine_round_phase_cpu_us", f"{{phase={phase}}}", us,
               n=PHASE_CPU_READ, n0=5)
    for part, us in PART_US.items():
        summed("engine_round_part_us", f"{{part={part}}}", us)
    for mark, (n, us) in MARK_US.items():
        hist("engine_round_mark_us", f"{{mark={mark}}}", us, n=n, n0=4)
    hist("engine_round_cpu_us", "", sum(PHASE_CPU_US.values()))
    before["process_cpu_us"] = 61_000_000
    after["process_cpu_us"] = 61_000_000 + PROCESS_CPU_US
    return before, after


def timed_view():
    before, after = registries()
    return view(registry_before=before, registry_after=after)


@pytest.mark.parametrize("name", ORDER)
def test_reader(name):
    assert set(WANT) == set(ORDER) and len(ORDER) == 14
    assert layers.load_reader(name)(timed_view()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ORDER)
def test_a_program_without_the_instrument_reads_nothing(name):
    """The parent commit's registry holds the phases and the round's CPU
    time and none of the new families: the reader returns None and does
    not raise, and the line leaves the metric out."""
    assert layers.load_reader(name)(view()) is None
    before, after = registries()
    new = ("engine_round_phase_cpu_us", "engine_round_part_us",
           "engine_round_mark_us", "process_cpu_us")
    parent = [{k: v for k, v in snap.items() if not k.startswith(new)}
              for snap in (before, after)]
    assert layers.load_reader(name)(view(
        registry_before=parent[0], registry_after=parent[1])) is None


def test_a_phases_cpu_time_is_its_share_of_every_rounds():
    """The rounds that read the CPU clock by phase give the shares, every
    round gives the CPU time they are shares of: rounds that were read
    and ran twice as long on the CPU move no phase's mean."""
    before, after = registries()
    for phase in PHASE_CPU_US:
        k = f"engine_round_phase_cpu_us.sum{{phase={phase}}}"
        after[k] = before[k] + 2 * (after[k] - before[k])
    v = view(registry_before=before, registry_after=after)
    for phase, us in PHASE_CPU_US.items():
        assert layers.load_reader(f"round_{phase}_cpu_ms")(v) \
            == pytest.approx(us / 1e3)


def test_an_idle_window_reads_nothing():
    """No round in the window: the means' denominators are 0 and their
    readers leave the metrics out."""
    before, _ = registries()
    v = view(registry_before=before, registry_after=dict(before))
    for name in ORDER:
        assert layers.load_reader(name)(v) is None, name


def test_the_readings_close_on_one_another():
    """What PERF.md checks on every traced run, on the older readers of
    the same registry: a part lies inside its phase; the marks in order;
    and the six phase CPU times sum to ``round_oncpu_pct`` of
    ``round_ms``, which they do by construction (shares of one whole)."""
    v = timed_view()
    got = {name: layers.load_reader(name)(v) for name in ORDER + (
        "round_ms", "round_oncpu_pct", "round_stage_ms", "round_upload_ms",
        "round_resolve_ms", "round_finish_ms")}
    assert sum(got[f"round_{p}_cpu_ms"] for p in PHASE_CPU_US) == \
        pytest.approx(got["round_oncpu_pct"] * got["round_ms"] / 100)
    assert got["finish_apply_ms"] + got["finish_ack_ms"] \
        <= got["round_finish_ms"]
    assert got["upload_release_ms"] <= got["round_upload_ms"]
    assert got["round_sweeps_ms"] \
        <= got["round_stage_ms"] + got["round_upload_ms"]
    assert got["resolve_send_ms"] <= got["round_resolve_ms"]
    assert got["round_replicates_out_ms"] <= got["round_responses_out_ms"] \
        <= got["round_ms"]
    assert got["engine_cpu_share_pct"] <= 100


def test_every_new_metric_has_its_entry():
    """Found by name, never by position: fourteen entries in the issue's
    order behind everything the benchmark had, layers spelled as the
    entries of the same layer spell them, and the three that the mesh
    engine has nothing to read for list the five one-chip cells."""
    with open(run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layer_of = {
        **dict.fromkeys(("round_stage_cpu_ms", "round_upload_cpu_ms",
                         "round_fetch_cpu_ms", "upload_release_ms",
                         "round_sweeps_ms", "engine_cpu_share_pct"),
                        entries["round_ms"]["layer"]),
        **dict.fromkeys(LISTED + ("round_resolve_cpu_ms",),
                        entries["round_resolve_ms"]["layer"]),
        "round_save_cpu_ms": entries["round_save_ms"]["layer"],
        **dict.fromkeys(("round_finish_cpu_ms", "finish_apply_ms",
                         "finish_ack_ms"),
                        entries["round_finish_ms"]["layer"])}
    for name in ORDER:
        want = {"name": name, "unit": "ms", "better": "lower",
                "source": "program_span", "layer": layer_of[name],
                "moves": "acked_ops_per_s"}
        if name == "engine_cpu_share_pct":
            want.update(unit="%", better="higher", source="program_counter")
        if name in LISTED:
            want["workloads"] = ONE_CHIP
        assert entries[name] == want, name
    names = list(entries)
    at = names.index(ORDER[0])
    assert tuple(names[at:at + len(ORDER)]) == ORDER
    assert at > names.index("retire_named_pct")
    cells = {w["name"]: w["chips"] for w in bench["workloads"]}
    assert sorted(ONE_CHIP) == sorted(c for c, n in cells.items() if n == 1)
    # each has a reader of its own, and nothing the benchmark had reads a
    # family this PR added
    for name in ORDER:
        assert callable(layers.load_reader(name))
    assert not {"finish_ack_cpu_ms", "process_oncpu_pct"} & set(entries)


def test_the_traced_rehearsal_prints_every_new_name(capsys):
    """One shard, rehearsed small on the CPU backend with ``--trace 1``:
    its last line names each of the fourteen (values withheld, as for every
    metric of a rehearsal), and ``correct`` stays true."""
    assert run.main([
        "--workload", "one-shard.write16", "--seed", str(2**31 + 37),
        "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert set(ORDER) <= set(last["metrics"])
    assert all(last["metrics"][name]["value"] is None for name in ORDER)
    assert last["metrics"]["engine_cpu_share_pct"]["unit"] == "%"
    assert last["metrics"]["finish_ack_ms"]["unit"] == "ms"
