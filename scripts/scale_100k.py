#!/usr/bin/env python
"""100k-group scale proof (VERDICT r4 item 4 / BASELINE config #3 shape).

Measures, with real allocations rather than projections:

  A. the batched kernel SoA state at 100k groups x 3 replicas
     (300k lanes): build time, device/host bytes, per-step time;
  B. the host side at 100k device-resident shards on ONE NodeHost:
     admission rate (batched lane injection), host-book bytes per lane
     (tracemalloc over a 10k slice), RSS, injection-flush time, idle
     staging scan time, and staging time under a proposal wave.

Each phase prints one JSON line (PHASE_A / PHASE_B); partial runs still
yield data.  Both rungs carry the capacity triple —
``predicted_bytes`` (contracts-derived model, capacity.py),
``measured_bytes`` (live tree bytes), ``max_g_at_budget`` (largest G
fitting the device HBM limit / SCALE_BUDGET_BYTES) — so a sweep shows
the model tracking reality rung by rung.  Run on an idle box:
`python scripts/scale_100k.py [--groups N]`.
"""

import json
import os
import resource
import sys
import time
import tracemalloc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GROUPS = 100_000
if "--groups" in sys.argv:
    GROUPS = int(sys.argv[sys.argv.index("--groups") + 1])
STEPS = int(os.environ.get("SCALE_STEPS", "5"))


def _enable_compile_cache() -> None:
    """Persistent compile cache keyed at capacity shapes: the 100k-lane
    step executable compiled once per box (the r4 measurement paid a
    479 s first-step compile on every run).  Counts artifacts BEFORE
    enabling so the log line says whether this run starts cold or rides
    a warm cache."""
    from dragonboat_tpu import hostenv

    artifacts = hostenv.cache_entry_count()
    cache_dir = hostenv.enable_compile_cache()
    if cache_dir is None:
        print("SCALE compile_cache: vetoed "
              "(DRAGONBOAT_TPU_COMPILE_CACHE=0)", flush=True)
    else:
        print(f"SCALE compile_cache: {'warm' if artifacts else 'cold'} "
              f"({artifacts} artifact(s)) dir={cache_dir}", flush=True)


def _budget_bytes(capacity_mod) -> int:
    """Device HBM limit as the backend reports it.  The CPU backend
    reports none: there SCALE_BUDGET_BYTES stands in (default 16 GiB,
    one v5e chip's HBM, so the headroom arithmetic can be rehearsed).
    On any other backend an unknown limit is an error, not a guess."""
    import jax

    for row in capacity_mod.device_memory_stats():
        if row.get("bytes_limit"):
            return int(row["bytes_limit"])
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{jax.default_backend()} device reports no bytes_limit; "
            "refusing to assume a memory budget")
    return int(os.environ.get("SCALE_BUDGET_BYTES", str(16 << 30)))


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def phase_a() -> None:
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()

    from dragonboat_tpu import capacity
    from dragonboat_tpu.bench_loop import bench_params, make_cluster, run_steps
    from dragonboat_tpu.core.kstate import empty_inbox

    kp = bench_params(3)
    t0 = time.time()
    state = make_cluster(kp, GROUPS, 3)
    box = empty_inbox(kp, state.term.shape[0])
    jax.block_until_ready(state.term)
    build_s = time.time() - t0
    # contracts-derived model vs what the trees actually hold: the two
    # must agree (test_capacity pins <1%); the rung records both
    lanes = int(state.term.shape[0])
    classes = ("ShardState", "Inbox")
    predicted = capacity.predict_bytes(kp, lanes, classes)
    state_bytes = capacity.measure_tree_bytes(state)
    box_bytes = capacity.measure_tree_bytes(box)
    budget = _budget_bytes(capacity)
    max_g = capacity.max_g_for_budget(kp, budget, classes)
    # iters is a static jit arg: warm the EXACT executable we measure —
    # through CompileTracker, so the rung itself proves the steady-state
    # contract (one compile at this geometry, zero retraces after)
    tracked = capacity.TRACKER.wrap("scale_run_steps", run_steps)
    t0 = time.time()
    state, box = tracked(kp, 3, STEPS, True, True, state, box)
    jax.block_until_ready(state.term)
    compile_s = time.time() - t0
    t0 = time.time()
    state, box = tracked(kp, 3, STEPS, True, True, state, box)
    jax.block_until_ready(state.term)
    dt = time.time() - t0
    tstats = tracked.stats()
    assert tstats["compiles"] == 1 and tstats["retraces"] == 0, (
        f"scale rung retraced: {tstats}")
    print("PHASE_A " + json.dumps({
        "groups": GROUPS, "lanes": GROUPS * 3,
        "platform": jax.devices()[0].platform,
        "build_s": round(build_s, 1),
        "state_mb": round(state_bytes / 1e6, 1),
        "inbox_mb": round(box_bytes / 1e6, 1),
        "predicted_bytes": predicted,
        "measured_bytes": state_bytes + box_bytes,
        "max_g_at_budget": max_g,
        "compile_s": round(compile_s, 1),
        "step_ms": round(dt / STEPS * 1e3, 1),
        "dispatch_compiles": tstats["compiles"],
        "dispatch_retraces": tstats["retraces"],
        "rss_gb": round(rss_gb(), 2),
    }), flush=True)
    del state, box


def phase_b() -> None:
    import numpy as np

    from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class NullSM(IStateMachine):
        """Minimal SM: the measurement targets the books, not the RSM."""

        def __init__(self, shard_id, replica_id):
            self.n = 0

        def update(self, entry):
            self.n += 1
            return Result(value=self.n)

        def lookup(self, q):
            return self.n

        def save_snapshot(self, w, files, done):
            w.write(b"\x00" * 4)

        def recover_from_snapshot(self, r, files, done):
            r.read(4)

    _enable_compile_cache()
    expert = ExpertConfig()
    expert.kernel_capacity = GROUPS
    # no node_host_dir -> MemLogDB: the measurement targets the host
    # books and the staging scan, not storage
    nh = NodeHost(NodeHostConfig(raft_address="scale-1", rtt_millisecond=5,
                                 expert=expert), auto_run=False)
    base_cfg = dict(election_rtt=10, heartbeat_rtt=1)

    def admit(lo: int, hi: int) -> float:
        t0 = time.time()
        for sid in range(lo, hi):
            nh.start_replica({1: "scale-1"}, False, NullSM, Config(
                shard_id=sid, replica_id=1, device_resident=True,
                **base_cfg))
        return time.time() - t0

    # warm slice to settle dict shapes, then a traced slice for the
    # bytes/lane number, then the untraced remainder (tracemalloc ~2x)
    head = max(2, min(5_000, GROUPS // 4))
    traced = max(2, min(10_000, GROUPS // 2))
    admit_head_s = admit(1, head + 1)
    tracemalloc.start()
    s0, _ = tracemalloc.get_traced_memory()
    t_traced = admit(head + 1, head + traced + 1)
    s1, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    bytes_per_lane = (s1 - s0) / traced
    t_rest = admit(head + traced + 1, GROUPS + 1)
    n_shards = len(nh.nodes)
    # the traced slice runs ~2x slow under tracemalloc: exclude it from
    # BOTH sides of the rate instead of inflating the numerator
    admit_rate = (n_shards - traced) / (admit_head_s + t_rest + 1e-9)

    eng = nh.kernel_engine

    def tick_all():
        # the PRODUCTION tick round: one shared-clock advance + one
        # engine-wide pending tick (consumed as a vectorized broadcast
        # at the next step) — not a per-lane Python walk
        nh._do_tick_round()

    # first kernel call: flushes EVERY queued injection at once AND
    # compiles the step executable at this capacity
    tick_all()
    t0 = time.time()
    eng.step_all()
    flush_compile_s = time.time() - t0
    # election pump: single-member shards campaign once their election
    # timer fires; the engine sees ticks only when the host ticks nodes
    from dragonboat_tpu.core import params as KP

    leaders = 0
    pump_rounds = 0
    t_pump = time.time()
    for _ in range(40):
        pump_rounds += 1
        tick_all()
        eng.step_all()
        leaders = int((np.asarray(eng.state.role) == KP.LEADER).sum())
        if leaders >= n_shards:
            break
    pump_s = time.time() - t_pump
    idle = []
    for _ in range(5):
        t0 = time.time()
        eng.step_all()
        idle.append(time.time() - t0)

    # proposal wave on 1k shards through the real client path
    waves = 0
    for sid in range(1, 1001):
        sess = nh.get_noop_session(sid)
        try:
            nh.propose(sess, b"k=1", timeout_s=30)
            waves += 1
        except Exception:
            pass
    stage_t0 = time.time()
    eng.step_all()
    eng.step_all()
    wave_steps_s = time.time() - stage_t0
    committed = int(np.asarray(eng.state.committed)[:n_shards].sum())
    # the rung ran entirely through the unified dispatch seam
    # (engine/dispatch.py): its active tracked entry must show exactly
    # one compile at this capacity and zero steady-state retraces
    active = "step_donated" if eng.pipeline_depth > 0 else "step"
    dstats = eng._cap_entries[active].stats()
    assert dstats["compiles"] == 1 and dstats["retraces"] == 0, (
        f"dispatch entry {active!r} retraced at scale: {dstats}")
    # same model the engine's /debug/capacity serves: classes + trees
    # come from the engine so the rung and the endpoint can't diverge
    from dragonboat_tpu import capacity

    classes = eng._capacity_model_classes()
    predicted = capacity.predict_bytes(
        eng.kp, eng.capacity, classes, capacity.PACKED_RESIDENT)
    measured = capacity.measure_tree_bytes(*eng._capacity_trees())
    max_g = capacity.max_g_for_budget(
        eng.kp, _budget_bytes(capacity), classes)
    print("PHASE_B " + json.dumps({
        "shards": n_shards,
        "predicted_bytes": predicted,
        "measured_bytes": measured,
        "max_g_at_budget": max_g,
        "admit_per_s": round(admit_rate),
        "bytes_per_lane_host_books": round(bytes_per_lane),
        "rss_gb": round(rss_gb(), 2),
        "injection_flush_plus_compile_s": round(flush_compile_s, 2),
        "election_pump_rounds": pump_rounds,
        "election_pump_s": round(pump_s, 1),
        "leaders": leaders,
        "idle_scan_step_ms": round(1e3 * sum(idle) / max(len(idle), 1), 1),
        "proposals_queued": waves,
        "wave_2steps_s": round(wave_steps_s, 3),
        "committed_total": committed,
        "dispatch_entry": active,
        "dispatch_compiles": dstats["compiles"],
        "dispatch_retraces": dstats["retraces"],
    }), flush=True)
    nh.close()


if __name__ == "__main__":
    which = os.environ.get("SCALE_PHASE", "ab")
    if "a" in which:
        phase_a()
    if "b" in which:
        phase_b()
