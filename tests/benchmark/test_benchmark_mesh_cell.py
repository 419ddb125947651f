"""The cell ``upstream-48-mesh4.write16``: its three per-layer readers and
the bytes function on the hand-made run of ``test_benchmark_layers.py``, and
the cell rehearsed small on forced host devices, sound and with a control."""

import json

import pytest

from benchmark import collective_bytes, layers, run
from test_benchmark_layers import view

MESH_CONFIG = {"step_entries": ["serve_step"],
               "step_programs": ["jit_jit_serve_step"], "expert": {},
               "mesh": {"g_size": 1, "replicas": 3, "n_local": 48}}


def mesh_view(**over):
    """100 steps of a 1x3 mesh in the capture (one call of the step program
    per chip and step), 6 ms of collectives over the three chips; 600 steps
    in the window, in which 12 + 6 + 0 messages met the host transport."""
    base = dict(
        config=MESH_CONFIG, chips=4, engines=1,
        tracker_before={"serve_step": {"calls": 100}},
        tracker_after={"serve_step": {"calls": 700}},
        registry_before={"engine_mesh_hub_msgs{way=sent}": 5,
                         "engine_mesh_hub_msgs{way=read_forward}": 0,
                         "engine_mesh_hub_msgs{way=stray_dropped}": 0},
        registry_after={"engine_mesh_hub_msgs{way=sent}": 17,
                        "engine_mesh_hub_msgs{way=read_forward}": 6,
                        "engine_mesh_hub_msgs{way=stray_dropped}": 0},
        capture={"window_s": 3.0, "busy_s": 0.03, "collective_s": 0.006,
                 "collective_calls": 5400, "devices_with_operations": 3,
                 "programs": {"jit_jit_serve_step": {"calls": 300,
                                                     "seconds": 0.45}}})
    base.update(over)
    return view(**base)


def test_collective_bytes_from_the_exchange_shapes():
    """One chip's out-lanes at 48 rows, K = 10, P = 5, E = 8: ``term``, seven
    response lanes, fifteen per-peer lanes and the two entry lanes, once
    from each of the two other chips."""
    one_chip = (48 * 4                                  # term
                + 6 * 48 * 10 * 4 + 48 * 10             # r_* (one bool)
                + 12 * 48 * 5 * 4 + 3 * 48 * 5          # s_* [G, P]
                + 48 * 5 * 8 * 4 + 48 * 5 * 8)          # s_ent_term, s_ent_cc
    kp = collective_bytes.kernel_params(MESH_CONFIG)
    assert (kp.inbox_cap, kp.num_peers, kp.msg_entries) == (10, 5, 8)
    assert collective_bytes.exchange_bytes_per_chip(kp, 48) == one_chip
    assert collective_bytes.received_per_step(MESH_CONFIG) == 2 * one_chip


def test_mesh_readers():
    run_ = mesh_view()
    assert layers.load_reader("mesh_hub_msgs_per_step")(run_) == \
        pytest.approx(18 / 600)
    # 6 ms over 300 chip-steps is 20 us a step and chip
    least_s = collective_bytes.received_per_step(MESH_CONFIG) / 200e9
    assert layers.load_reader("collective_roofline")(run_) == \
        pytest.approx(100 * least_s / 20e-6)
    assert layers.load_reader("collective_us_per_step")(run_) == \
        pytest.approx(60.0)


def test_mesh_readers_with_nothing_to_read():
    """A program older than the counter, a window without a step, a capture
    without collectives, no capture, a configuration with no mesh: the
    metric is left out."""
    hub = layers.load_reader("mesh_hub_msgs_per_step")
    roof = layers.load_reader("collective_roofline")
    assert hub(mesh_view(registry_before={}, registry_after={})) is None
    assert hub(mesh_view(tracker_after={"serve_step": {"calls": 100}})) is None
    assert hub(mesh_view(registry_before={})) == pytest.approx(23 / 600)
    quiet = dict(mesh_view().capture, collective_s=0.0)
    assert roof(mesh_view(capture=quiet)) is None
    assert roof(mesh_view(capture=None)) is None
    assert roof(view()) is None


def test_the_cell_and_its_metrics_have_their_entries():
    with open(run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "upstream-48-mesh4.write16")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "upstream-48-mesh4", "write16", 4)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("collective_us_per_step", "collective_roofline",
                 "mesh_hub_msgs_per_step"):
        assert entries[name]["workloads"] == [cell["name"]]
        assert entries[name]["moves"] == "acked_ops_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # appended, and nothing before them moved: the round timer's entries
    # stay one block in PR 25's order, with only this cell's three behind
    # (``test_every_new_metric_has_its_entry`` looks for that block at the
    # very end of the list, which held until a PR appended)
    names = list(entries)
    assert names[-3:] == ["collective_us_per_step", "collective_roofline",
                          "mesh_hub_msgs_per_step"]
    assert names[-20:-3] == [
        "round_ms", "round_wait_ms", "round_stage_ms", "round_upload_ms",
        "round_fetch_ms", "round_resolve_ms", "round_save_ms",
        "round_finish_ms", "round_oncpu_pct", "admission_fill_pct",
        "read_stage_wait_ms", "start_replica_ms", "round_crossings",
        "crossing_ms", "props_deferred_pct", "setup_compile_s",
        "add_shard_lock_ms"]


# -- the cell, rehearsed -------------------------------------------------------

@pytest.fixture(scope="module")
def mesh_episodes():
    """One mesh deployment of two shards on forced host devices: a sound
    traced episode, then a control."""
    import io
    from contextlib import redirect_stdout

    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main([
            "--workload", "upstream-48-mesh4.write16", "--seed", "41",
            "--seconds", "2", "--trace", "1", "--rehearse",
            "--episodes", f"{2**31 + 41},42:lost-write"]) == 0
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    return ({line["fault"]: line for line in lines
             if line.get("phase") == "episode"},
            next(line for line in lines if line.get("phase") == "deployed"))


def test_mesh_cell_rehearsal_is_correct(mesh_episodes):
    """State on three devices, six links resident, ``correct`` true, and
    the per-layer metrics that have something to read off the chip: the
    hub counter's and the round timer's among them, the device-trace
    readers left out as ``device_idle_pct`` is."""
    episodes, deployed = mesh_episodes
    assert len(deployed["state_devices"]) == 3
    assert set(deployed["link_classes"].values()) == {"resident"}
    assert len(deployed["link_classes"]) == 6
    sound = episodes[None]
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert {"mesh_hub_msgs_per_step", "engine_steps_per_s", "acked_per_step",
            "round_ms", "round_wait_ms", "round_stage_ms", "round_upload_ms",
            "round_fetch_ms", "round_resolve_ms", "round_save_ms",
            "round_finish_ms", "round_oncpu_pct", "round_crossings",
            "crossing_ms", "admission_fill_pct", "props_deferred_pct",
            "start_replica_ms", "add_shard_lock_ms",
            "setup_compile_s"} <= set(sound["metrics"])
    for name in ("collective_us_per_step", "collective_roofline",
                 "device_idle_pct", "step_kernel_us"):
        assert name not in sound["metrics"]


def test_mesh_cell_control_comes_out_not_correct(mesh_episodes):
    episodes, _ = mesh_episodes
    assert episodes["lost-write"]["correct"] is False
    assert episodes["lost-write"]["attempted"] > 0
