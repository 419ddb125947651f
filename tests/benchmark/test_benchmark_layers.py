"""The per-layer readers on a hand-made run: each reads its own source,
and a reader with nothing to read returns None."""

import pytest

from benchmark import layers, peaks


def view(**over):
    base = dict(
        config={"step_entries": ["step"], "step_programs": ["jit_step"]},
        traffic={}, chips=1, engines=3, device_kind="TPU v5 lite",
        window_s=10.0, acked_writes=2000, acked_reads=0,
        write_latencies_ms=[float(i) for i in range(1, 101)],
        tracker_before={"step": {"calls": 100}},
        tracker_after={"step": {"calls": 700}, "fleet_stats": {"calls": 60}},
        registry_before={"logdb.fsync_us.count": 10},
        registry_after={"logdb.fsync_us.count": 510},
        spans=[
            {"kind": "proposal", "stamps": [
                ("propose", 0), ("stage", 1000), ("dispatch", 4000),
                ("retire", 4500), ("hub_send", 4600), ("save", 5000),
                ("fsync", 9000), ("hub_recv", 9500), ("apply_queue", 9600),
                ("apply", 12000), ("ack", 12100)]},
            {"kind": "proposal", "stamps": [
                ("propose", 0), ("stage", 3000), ("dispatch", 8000),
                ("retire", 8500), ("save", 9000), ("fsync", 10000),
                ("apply_queue", 10100), ("apply", 11000), ("ack", 11100)]},
            {"kind": "read", "stamps": [
                ("read_propose", 0), ("read_quorum", 7000),
                ("read_serve", 7500)]},
        ],
        capture={"window_s": 3.0, "busy_s": 0.03, "collective_s": 0.0009,
                 "collective_calls": 90, "devices_with_operations": 1,
                 "programs": {"jit_step": {"calls": 90, "seconds": 0.018}}},
        step_bytes=12_900_000)
    base.update(over)
    return layers.RunView(**base)


WANT = {
    "client_write_p95_ms": 95.0,
    "stage_wait_ms": 2.0,            # median of 1.0 and 3.0
    "dispatch_ms": 4.5,              # (3.0+0.5) and (5.0+0.5)
    "fsync_ms": 2.95,                # (0.4+4.0) and (0.5+1.0)
    "apply_ms": 1.75,                # (0.1+2.4) and (0.1+0.9)
    "hub_ms": 0.6,                   # only the first span crossed the hub
    "read_quorum_ms": 7.0,
    "engine_steps_per_s": 20.0,      # 600 calls / 3 engines / 10 s
    "acked_per_step": 2000 / 600,
    "fsyncs_per_kop": 250.0,
    "collective_us_per_step": 10.0,
    "step_kernel_us": 200.0,
    "step_roofline": 100 * (12_900_000 / 819e9) / 200e-6,
    "device_idle_pct": 99.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    assert layers.load_reader(name)(view()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ["step_kernel_us", "step_roofline",
                                  "device_idle_pct",
                                  "collective_us_per_step"])
def test_no_capture_reads_nothing(name):
    assert layers.load_reader(name)(view(capture=None)) is None


def test_nothing_to_read_is_left_out():
    bare = view(spans=[], acked_writes=0, registry_after={},
                write_latencies_ms=[],
                tracker_after={"step": {"calls": 100}})
    for name in ("client_write_p95_ms", "stage_wait_ms", "hub_ms", "read_quorum_ms",
                 "fsyncs_per_kop", "acked_per_step"):
        assert layers.load_reader(name)(bare) is None


def test_peaks_table_and_step_bytes():
    import jax
    import numpy as np

    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
    args = (jax.ShapeDtypeStruct((1024, 8), np.int32),
            jax.ShapeDtypeStruct((1024,), np.bool_))
    outs = jax.ShapeDtypeStruct((1024, 2), np.int32)
    assert peaks.step_bytes_per_device(args, outs) == (
        1024 * 8 * 4 + 1024 + 1024 * 2 * 4)
    assert peaks.least_step_seconds(819, "TPU v5 lite") == pytest.approx(1e-9)
