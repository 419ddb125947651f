"""The command, rehearsed small on the CPU backend: the shape of its last
line, the control that has to come out not correct, and the refusals off
the chip.  No timing is asserted; nothing here describes a topology."""

import json

import pytest

from benchmark import run
from benchmark.deployment import FAULTS

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def lines_of(capsys, argv):
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return [json.loads(line) for line in out]


def test_last_line_shape(capsys):
    """A two-shard, two-second run of the traced path: the contract's keys,
    every per-layer metric of the cell that found something to read, no
    value and no device reading under a CPU run."""
    lines = lines_of(capsys, [
        "--workload", "upstream-48.write16", "--seed", str(2**31 + 7),
        "--seconds", "2", "--trace", "1", "--rehearse"])
    last = lines[-1]
    assert CONTRACT_KEYS <= set(last) and last["rehearsal"] is True
    assert last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert {"stage_wait_ms", "engine_steps_per_s", "acked_per_step",
            "dispatch_ms", "fsync_ms", "apply_ms",
            "fsyncs_per_kop"} <= set(last["metrics"])
    assert "device_idle_pct" not in last["metrics"]    # nothing to read here
    for metric in last["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] is None
    phases = [line.get("phase") for line in lines[:-1]]
    for phase in ("start", "deployed", "drained", "check", "window",
                  "traced", "done"):
        assert phase in phases
    window = next(line for line in lines if line.get("phase") == "window")
    assert window["write_latency_samples"] == window["acked_writes"] > 0
    assert all(row["retraces"] == 0 for row in window["compiles"].values())


@pytest.fixture(scope="module")
def episodes():
    """One deployment, one sound episode, then each control in turn (a
    control leaves the replicas damaged, so the sound one goes first)."""
    import io
    from contextlib import redirect_stdout

    spec = ",".join(["31"] + [f"{32 + i}:{f}" for i, f in enumerate(FAULTS)])
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main([
            "--workload", "upstream-48.mixed9to1", "--seed", "31",
            "--seconds", "3", "--trace", "0", "--rehearse",
            "--episodes", spec]) == 0
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    return {line["fault"]: line for line in lines
            if line.get("phase") == "episode"}


def test_sound_episode_is_correct(episodes):
    assert episodes[None]["correct"] is True
    assert {"acked_ops_per_s", "write_p95_ms", "read_p95_ms",
            "setup_s"} == set(episodes[None]["metrics"])


@pytest.mark.parametrize("fault", FAULTS)
def test_control_comes_out_not_correct(episodes, fault):
    """The rest of a run with the answers broken where they are produced
    (an acknowledged write dropped by every replica, by one replica, a
    lookup that returns the value it replaced): ``correct`` is false."""
    assert episodes[fault]["correct"] is False
    assert episodes[fault]["attempted"] > 0


def test_refuses_the_cpu_backend_without_the_switch():
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "one-shard.write16", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)


def test_refuses_fewer_chips_than_the_cell_asks_for(monkeypatch):
    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    one = [Chip()]
    monkeypatch.setattr(run.jax, "devices", lambda: one)
    assert run.require_device(1, False) is one
    with pytest.raises(SystemExit) as e:
        run.require_device(4, False)
    assert "4 chip" in str(e.value.code)


def test_refuses_an_unknown_cell():
    with pytest.raises(SystemExit):
        run.load_cell("no-such.cell")
