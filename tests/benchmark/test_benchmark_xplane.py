"""The trace reduction on a small synthetic capture: busy union, program
time, collectives, and idle gaps attributed to the host's annotations."""

import pytest

from benchmark import xplane

CAPTURE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 16000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 16000000 duration_ps: 1000000 } }
  lines { id: 3 name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 1500000 } }
  event_metadata { key: 5 value { id: 5 name: "%all-reduce-start.7 = u32[] all-reduce-start(%x)" } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(123)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "%all-gather.2 = s32[3,8]{1,0} all-gather(s32[1,8] %p)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_fleet_stats(9)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "engine" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 19000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "kernel_engine.step" } }
  event_metadata { key: 2 value { id: 2 name: "kernel_engine.process_outputs" } }
  event_metadata { key: 3 value { id: 3 name: "something else" } }
}
'''
# device busy (us from 0): [1,5] [11,15] [17,18]; host events span [0,20]


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return xplane.reduce_capture(ProfileData.from_text_proto(CAPTURE), 1)


def test_busy_union_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(20e-6)
    assert reduced["busy_s"] == pytest.approx(9e-6)       # overlap counted once
    assert reduced["devices_with_operations"] == 1
    assert reduced["longest_gap_s"] == pytest.approx(6e-6)


def test_busy_is_averaged_over_the_chips_asked_for():
    from jax.profiler import ProfileData

    four = xplane.reduce_capture(ProfileData.from_text_proto(CAPTURE), 4)
    assert four["busy_s"] == pytest.approx(9e-6 / 4)


def test_program_time_and_calls(reduced):
    assert reduced["programs"]["jit_step"] == {
        "calls": 2, "seconds": pytest.approx(8e-6)}
    assert reduced["programs"]["jit_fleet_stats"]["calls"] == 1


def test_collectives(reduced):
    # one synchronous all-gather (2 us) and one asynchronous all-reduce span
    assert reduced["collective_calls"] == 2
    assert reduced["collective_s"] == pytest.approx(3.5e-6)
    assert reduced["breakdown"]["device_ops"][0] == [
        "fusion.1", pytest.approx(8e-6)]


def test_gaps_go_to_what_the_host_was_doing(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # idle: [0,1] [5,11] [15,17] [18,20]; step covers [0,8], outputs [8,10]
    assert gaps["kernel_engine.step"] == pytest.approx(4e-6)
    assert gaps["kernel_engine.process_outputs"] == pytest.approx(2e-6)
    assert gaps[xplane.UNATTRIBUTED] == pytest.approx(5e-6)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_a_capture_with_no_device_operation_is_refused():
    from jax.profiler import ProfileData

    host_only = CAPTURE[CAPTURE.index('planes { id: 2'):]
    with pytest.raises(ValueError):
        xplane.reduce_capture(ProfileData.from_text_proto(host_only), 1)


def test_interval_arithmetic():
    u = xplane.union([(5, 7), (1, 3), (2, 4), (9, 9)])
    assert u == [(1, 4), (5, 7)] and xplane.total(u) == 5
    assert xplane.intersect(u, [(0, 2), (3, 6)]) == [(1, 2), (3, 4), (5, 6)]
    assert xplane.subtract([(0, 10)], u) == [(0, 1), (4, 5), (7, 10)]
    assert xplane.subtract(u, [(0, 10)]) == []
    assert xplane.program_of("jit_step(42)") == "jit_step"
    assert xplane.op_of("%while.71 = (s32[]{:T(128)}) while(%t)") == "while.71"
    assert xplane.op_of("fusion.1") == "fusion.1"
