"""Hotspot chaos fault end-to-end (the ISSUE 18 observe→act closure).

``run_hotspot(seed)`` drives a 3-replica device-resident cluster with
two shards, makes one shard's state machine pathologically slow to
apply under a 100:1 skewed write load, and requires the elastic control
plane to close the loop on its own: the step-latency EWMA trips the
host-hot gate, the fleet controller plans a leadership transfer for the
hot shard, the NodeHost issues it, and leadership actually leaves the
initial leader — all while the convergence oracle holds (zero acked
loss, equal journals, leaderless gauge drained, invariant probes
clean).

The scenario regression-covers two load-dependent liveness bugs this
closure flushed out: the kernel's campaign gate must not refuse
elections merely because apply backpressure keeps committed > applied
(core/kernel.py _campaign), and an armed-then-aborted leader transfer
must re-arm from the sticky lease instead of being lost
(engine/kernel_engine.py _stage_lane).

Budget: ~22 s per seed; two fixed seeds ride tier-1 as ``chaos_fast``.
"""

import os
import threading
import time

import pytest

from dragonboat_tpu.chaos import run_hotspot

FAST_SEEDS = (11, 23)


@pytest.mark.chaos_fast
@pytest.mark.parametrize("seed", FAST_SEEDS)
def test_hotspot_drains_and_converges(seed, one_core):
    r = run_hotspot(seed)
    assert r.report.ok, (seed, r.report.failures)
    assert r.transfers, (seed, "controller never planned a transfer")
    assert r.final_leader != r.initial_leader, (seed, r.final_leader)
    assert r.acked_count > 0, seed
    # every transfer decision carries its evidence row (the flight
    # record IS the audit trail the doctor replays)
    for t in r.transfers:
        ev = t.get("evidence", {})
        assert {"obs", "lane", "score", "lag", "streak",
                "term"} <= set(ev), (seed, t)


def test_one_core_pin_is_lifted_from_threads_born_under_it():
    """``one_core``'s teardown must reach threads that were started while
    the pin held (they inherit it, and sched_setaffinity on the caller
    alone would leave them on one core for the rest of the xdist worker's
    life)."""
    import conftest

    before = os.sched_getaffinity(0)
    born, stop = [], threading.Event()
    conftest._set_affinity_all_threads({min(before)})
    try:
        t = threading.Thread(target=lambda: (
            born.append(threading.get_native_id()), stop.wait()))
        t.start()
        while not born:
            pass
        assert os.sched_getaffinity(born[0]) == {min(before)}
    finally:
        conftest._set_affinity_all_threads(before)
    try:
        assert os.sched_getaffinity(born[0]) == before
        assert os.sched_getaffinity(0) == before
    finally:
        stop.set()
        t.join()


def test_hosts_a_failed_test_left_open_are_closed(tmp_path):
    """What conftest does after a FAILED test: every NodeHost still open
    is closed (a host left running keeps its engine, tick and apply
    threads in the worker for good, and the tests after it crawl), one
    already closed is left alone, and a second sweep finds nothing."""
    import conftest

    from dragonboat_tpu.config import NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost

    tag = time.monotonic_ns()
    left_open = NodeHost(NodeHostConfig(raft_address=f"left-open-{tag}",
                                        rtt_millisecond=5))
    closed = NodeHost(NodeHostConfig(raft_address=f"closed-{tag}",
                                     rtt_millisecond=5))
    try:
        closed.close()
        assert conftest._close_hosts_left_open() == 1
        assert left_open._stopped
        assert conftest._close_hosts_left_open() == 0
    finally:
        if not left_open._stopped:
            left_open.close()


def test_files_are_handed_out_in_collection_order(request):
    """Under ``--dist loadfile`` conftest keeps xdist from re-sorting the
    files by their number of tests, so the order conftest gives the
    collection (the long big-shape files first, this file last) is the
    order the workers take them in."""
    import conftest

    opt = request.config.option
    if hasattr(opt, "loadscopereorder"):        # xdist is loaded
        assert opt.loadscopereorder is False

    class Item:
        def __init__(self, path):
            import pathlib

            self.path = pathlib.Path(path)
            self.nodeid = path

    items = [Item(p) for p in (
        "tests/test_chaos_hotspot.py", "tests/test_few.py",
        "tests/test_many.py", "tests/test_many.py", "tests/test_many.py",
        "tests/test_few.py", "tests/benchmark/test_benchmark_rehearsal.py",
        "tests/test_zz_mesh_scale.py")]
    conftest.pytest_collection_modifyitems(None, request.config, items)
    assert [it.path.stem for it in items] == [
        "test_benchmark_rehearsal", "test_zz_mesh_scale",
        "test_many", "test_many", "test_many", "test_few", "test_few",
        "test_chaos_hotspot"]
