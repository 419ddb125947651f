#!/usr/bin/env python
"""Benchmark: sustained replicated writes/sec across raft groups on TPU.

BASELINE config #2 shape: N groups × 3 replicas, 16B payloads, vmapped step
loop with on-device message routing; every write is a full raft round
(leader append → replicate → quorum ack → commit) with instant-apply RSM
feedback and device-side log compaction.  The LAST stdout line is the
record; an earlier provisional line may precede it (emitted after phase A
so an externally killed slow run still records the headline).  The bench
runs on whatever backend jax reports, at the scale asked for, and a
failure exits non-zero with its traceback.

Baseline: the reference's 9M writes/s peak (3× 22-core Xeon servers,
BASELINE.md) — vs_baseline is measured/9e6.

Phases (one JSON line carries all of them): A headline write throughput
(uninstrumented, MEDIAN-OF-3 timed windows with a cross-phase
contention verdict — a noisy box inflates a window, it must not inflate
the record), A2 commit-latency percentiles (stamp-ring instrumented
loop, leader-side release), B 9:1 ReadIndex:write PERMIT capacity
(secondary diagnostic), B2 9:1 mix with reads SERVED against the
device-resident state machine (THE config-#3 number —
read_accounting: "served"; BENCH_SERVED=0 skips), C 10k-shard election
storm with randomized drops + pre-vote (config #4), D
membership-change wave + device log compaction under load (config #5:
every group commits a CC mid-stream; BENCH_CC=0 skips,
BENCH_CC_ROUNDS sets the wave count), E config #1 single-shard
datapoint (one 3-replica shard at G=1, vs the reference's 1.25M w/s
single-shard peak; BENCH_CONFIG1=0 skips).  BENCH_TIME_BUDGET (default
2400 s) soft-bounds the run: a phase that would overrun is skipped
with a note in the record, never silently truncated.

Env knobs: BENCH_GROUPS (default 8192 on device, 1024 on the CPU
backend — one core crunches the batch serially, so scale only slows the
same measurement), BENCH_STEPS (default 200), BENCH_CHUNK (device-launch
chunking under the ~60 s watchdog),
BENCH_LAT_STEPS / BENCH_MIXED_STEPS (phase lengths),
BENCH_MIXED_WRITE_WIDTH (phase B write lanes; default full batch width —
the 9:1 ratio rides the per-ctx read batch, capped at 9 reads per
committed write),
BENCH_STORM=0 (skip phase C), BENCH_STORM_GROUPS / BENCH_STORM_STEPS /
BENCH_STORM_DROP (storm shape), BENCH_DEVICE_SM=1 (full data path:
committed writes applied to the device-resident KV state machine by the
fused rsm-apply kernel, rsm/device_kv.py), BENCH_PALLAS=1 (with
BENCH_DEVICE_SM: route the apply through the pallas block kernel,
rsm/device_kv_pallas.py), BENCH_TELEMETRY=1 (standalone mode: A-B
overhead of the device-side fleet_stats telemetry reduction at the
engine's decimation cadence — see run_telemetry_ab), BENCH_HEALTH=1
(standalone mode: interleaved A-B overhead of the fleet_health anomaly
pass + O(K) report fetch on top of the fleet_stats baseline — see
run_health_ab), BENCH_PIPELINE=1
(standalone mode: interleaved A-B of the serial vs fused depth-1
pipelined step loops with commit-latency percentiles per arm — see
run_pipeline_ab), BENCH_TRACE=1 (standalone mode: interleaved A-B
overhead of proposal-lifecycle tracing at default 1/64 sampling on the
full serving path — see run_trace_ab), BENCH_FABRIC=1 (standalone
mode: interleaved A-B overhead of the fabric observability stack —
per-link transport telemetry + trace propagation + hop census on top
of lifecycle tracing — see run_fabric_ab), BENCH_CAPACITY=1 (standalone
mode: interleaved A-B overhead of the capacity rail — compile-tracker
wrappers + tree-bytes walk + snapshot assembly — on top of the
stats+health path — see run_capacity_ab), BENCH_SAFETY=1 (standalone
mode: interleaved A-B overhead of the runtime invariant probe —
check_invariants + digest carry + O(NI) report fetch — on top of the
stats+health path — see run_safety_ab), BENCH_TRANSFER=1 (standalone
mode: interleaved A-B overhead of the transfer-guard rail —
capacity.METER tag counters + scoped jax.transfer_guard around the
dispatch seam — see run_transfer_ab), BENCH_ELASTIC=1 (standalone
mode: the elastic control plane's two closing numbers — skew-vs-uniform
acked throughput with the fleet controller on, and the masked-quiesce
step-time reduction at 90% cold — see run_elastic_ab),
BENCH_FABRIC_RESIDENT=1 (standalone mode: the round-17 tentpole's A-B
— co-located consensus over the in-step collective vs round-tripped
through the host hub's route() staging, on the serving loop, with
compile telemetry pinning compiles=1/retraces=0 on the resident entry
— see run_fabric_resident_ab).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from dragonboat_tpu.hostenv import enable_compile_cache  # noqa: E402

BASELINE_WPS = 9e6
# BASELINE config #1: ONE 3-replica shard, 16B payloads — the
# reference's single-shard peak (BASELINE.md)
CONFIG1_BASELINE_WPS = 1.25e6


def emit(result: dict) -> None:
    print(json.dumps(result))


def run_bench() -> None:
    import jax

    enable_compile_cache()

    platform = jax.devices()[0].platform
    default_groups = "8192" if platform != "cpu" else "1024"
    groups = int(os.environ.get("BENCH_GROUPS", default_groups))
    steps = int(os.environ.get("BENCH_STEPS", "200"))
    _measure(platform, groups, steps)


def _pctile(hist, q: float):
    """Percentile (in steps) from the latency bucket histogram."""
    import numpy as np

    h = np.asarray(hist, np.int64)
    c = h.cumsum()
    if c[-1] == 0:
        return None
    return int(np.searchsorted(c, q * c[-1], side="left"))


def _run_storm(platform: str) -> dict:
    """BASELINE config #4: election storm with randomized drops +
    pre-vote across BENCH_STORM_GROUPS shards — 10k by default on every
    platform (the CPU fallback pays the wall cost; shrinking the config
    made r3's number incomparable to the baseline).  ``platform`` rides
    into the record for provenance."""
    import time as _t

    import numpy as np

    from dragonboat_tpu.bench_loop import (
        bench_params,
        make_cluster,
        run_steps,
        run_steps_storm,
    )
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.core.kstate import empty_inbox
    import jax.numpy as jnp

    replicas = 3
    # config #4 says 10k shards; the CPU fallback pays the wall cost
    # rather than shrinking the config (VERDICT r3 weak #5)
    g = int(os.environ.get("BENCH_STORM_GROUPS", "10000"))
    storm_steps = int(os.environ.get("BENCH_STORM_STEPS", "30"))
    drop_p = float(os.environ.get("BENCH_STORM_DROP", "0.25"))
    kp = bench_params(replicas)
    state = make_cluster(kp, g, replicas)
    # pre-vote everywhere: failed campaigns must not inflate terms
    state = state._replace(pre_vote=jnp.ones_like(state.pre_vote))
    box = empty_inbox(kp, state.term.shape[0])

    # compile the recovery-loop executable BEFORE the timed window (a
    # first-call jit would otherwise inflate recovery_ms); 10 pre-storm
    # ticks are semantically part of the cold start
    chunk = 10
    state, box = run_steps(kp, replicas, chunk, True, False, state, box)
    state.term.block_until_ready()

    # cold start under drops IS the storm: g simultaneous campaigns
    state, box = run_steps_storm(kp, replicas, storm_steps, drop_p, 42,
                                 state, box)
    state.term.block_until_ready()
    role = np.asarray(state.role).reshape(-1, replicas)
    storm_coverage = float((role == KP.LEADER).sum(axis=1).clip(0, 1).mean())

    # clean network: measure steps (and wall) to one leader everywhere
    t0 = _t.time()
    recovered_steps = None
    done = 0
    while done < 400:
        state, box = run_steps(kp, replicas, chunk, True, False, state, box)
        done += chunk
        role = np.asarray(state.role).reshape(-1, replicas)
        if ((role == KP.LEADER).sum(axis=1) == 1).all():
            recovered_steps = done
            break
    dt = _t.time() - t0
    step_ms = dt / max(done, 1) * 1e3
    # recovery is only complete at EXACTLY one leader per group
    post_cov = float(((role == KP.LEADER).sum(axis=1) == 1).mean())
    return {
        "groups": g,
        "platform": platform,
        "storm_steps": storm_steps,
        "drop_p": drop_p,
        "leader_coverage_after_storm": round(storm_coverage, 4),
        "post_recovery_coverage": round(post_cov, 4),
        "recovered": recovered_steps is not None,
        "recovery_steps": recovered_steps,
        # null when the cluster never reached one-leader-everywhere — a
        # 400-step timeout must not read as an achieved latency
        "recovery_ms": (round(step_ms * recovered_steps, 1)
                        if recovered_steps is not None else None),
        "recovery_step_ms": round(step_ms, 2),
        **({} if recovered_steps is not None
           else {"timed_out_after_steps": done}),
    }


def _run_served(replicas: int, groups: int, mixed_steps: int,
                write_width: int, chunk: int) -> dict:
    """Phase B2: the 9:1 mix with every read EXECUTED against the
    device-resident table (run_steps_mixed_sm) — a fresh device-SM
    cluster at the bench G, its own warmup, its own timed window.
    Standalone so the main-phase state is untouched and a failure here
    cannot poison the rest of the record."""
    import numpy as np
    import jax.numpy as jnp

    from dragonboat_tpu.bench_loop import (
        elect_all,
        make_cluster,
        make_device_sm,
        run_steps_mixed_sm,
        sm_params,
    )
    from dragonboat_tpu.core import params as KP

    kp = sm_params(replicas)
    state = make_cluster(kp, groups, replicas)
    state, box = elect_all(kp, replicas, state)
    lead = np.asarray(state.role) == KP.LEADER
    kv, kv_state = make_device_sm(groups, replicas)
    WW = max(1, min(kp.proposal_cap, write_width))
    rd = jnp.asarray(0, jnp.int32)
    acc = jnp.asarray(0, jnp.int32)
    rej = jnp.asarray(0, jnp.int32)
    now = 0

    def run(iters):
        nonlocal state, box, kv_state, rd, acc, rej, now
        state, box, kv_state, rd, acc, rej = run_steps_mixed_sm(
            kp, replicas, kv, iters, WW, jnp.asarray(now, jnp.int32),
            state, box, kv_state, rd, acc, rej)
        now += iters

    def committed() -> int:
        return int(np.asarray(state.committed)[lead].astype(np.int64).sum())

    # warm the exact chunk/remainder executables outside the window
    run(min(chunk, mixed_steps))
    if mixed_steps % chunk:
        run(mixed_steps % chunk)
    state.committed.block_until_ready()
    c0, r0 = committed(), int(np.asarray(rd))
    t0 = time.time()
    done = 0
    while done < mixed_steps:
        n = min(chunk, mixed_steps - done)
        run(n)
        done += n
    state.committed.block_until_ready()
    dt = time.time() - t0
    writes = committed() - c0
    served = (int(np.asarray(rd)) - r0) * 9 * WW
    # the declared mix is 9:1 — lookups beyond 9 per committed write
    # are executed but do not count toward the mixed number
    reads_ops = min(served, 9 * writes)
    ops = (writes + reads_ops) / dt
    return {
        "read_accounting": "served",
        "ops_per_s": round(ops),
        "writes_per_s": round(writes / dt),
        "reads_served_per_s": round(served / dt),
        "read_checksum": int(np.asarray(acc)),
        "sm_rejected_writes": int(np.asarray(rej)),
        "steps": mixed_steps,
        "step_ms": round(dt / mixed_steps * 1e3, 3),
        "table": "direct-mapped",
        "vs_baseline_mixed": round(ops / 11e6, 4),
    }


def _run_single_shard(replicas: int, steps: int) -> dict:
    """BASELINE config #1: one 3-replica shard, 16B payloads.  The [G]
    batch parallelism that carries the headline cannot help at G=1 —
    this datapoint isolates per-shard pipeline depth (proposal_cap
    writes per device step) against the reference's 1.25M writes/s
    single-shard peak.  Standalone cluster so the main-phase state is
    untouched and a failure here cannot poison the rest of the record."""
    import numpy as np

    from dragonboat_tpu.bench_loop import (
        bench_params,
        elect_all,
        make_cluster,
        run_steps,
    )
    from dragonboat_tpu.core import params as KP

    kp = bench_params(replicas)
    state = make_cluster(kp, 1, replicas)
    state, box = elect_all(kp, replicas, state)
    lead = np.asarray(state.role) == KP.LEADER

    def run(iters):
        nonlocal state, box
        state, box = run_steps(kp, replicas, iters, True, True, state, box)

    def committed() -> int:
        return int(np.asarray(state.committed)[lead].astype(np.int64).sum())

    # G=1 launches are tiny; one fixed chunk keeps the jit-variant count
    # (and so the warmup compile cost) at exactly two executables
    chunk = 25
    run(min(chunk, steps))
    if steps % chunk:
        run(steps % chunk)
    state.committed.block_until_ready()
    c0 = committed()
    t0 = time.time()
    done = 0
    while done < steps:
        n = min(chunk, steps - done)
        run(n)
        done += n
    state.committed.block_until_ready()
    dt = time.time() - t0
    writes = committed() - c0
    wps = writes / dt
    return {
        "groups": 1,
        "steps": steps,
        "step_ms": round(dt / steps * 1e3, 3),
        "writes": writes,
        "writes_per_s": round(wps),
        "vs_baseline_config1": round(wps / CONFIG1_BASELINE_WPS, 4),
    }


def _measure(platform: str, groups: int, steps: int) -> None:
    import numpy as np

    from dragonboat_tpu.bench_loop import (  # noqa: F401
        bench_params,
        elect_all,
        lat_init,
        make_cluster,
        run_steps,
        run_steps_lat,
    )
    from dragonboat_tpu.core import params as KP

    replicas = 3
    device_sm = os.environ.get("BENCH_DEVICE_SM") == "1"
    if device_sm:
        from dragonboat_tpu.bench_loop import sm_params

        kp = sm_params(replicas)
    else:
        kp = bench_params(replicas)

    import jax.numpy as jnp

    t_build = time.time()
    # soft wall budget: the driver/watcher runs this under an external
    # timeout — a phase that would overrun it must be skipped WITH a
    # note rather than silently truncating the record (VERDICT r4: the
    # artifact is the scoreboard)
    budget_s = float(os.environ.get("BENCH_TIME_BUDGET", "2400"))

    def time_left(margin_s: float) -> bool:
        return (time.time() - t_build) < (budget_s - margin_s)
    state = make_cluster(kp, groups, replicas)
    state, box = elect_all(kp, replicas, state)
    lead = np.asarray(state.role) == KP.LEADER
    assert lead.reshape(-1, replicas).any(axis=1).all()
    sm_rejects = []   # device arrays: no per-chunk host sync in the
    # timed loop (the plain path measures with async dispatch overlap)
    if device_sm:
        from dragonboat_tpu.bench_loop import make_device_sm, run_steps_sm

        # BENCH_PALLAS=1 flips the apply to the fused pallas kernel
        # (VMEM-resident table block; interpret-mode off-TPU)
        kv, kv_state = make_device_sm(
            groups, replicas,
            use_pallas=os.environ.get("BENCH_PALLAS") == "1")

        def run_steps(kp_, r_, n_, tick_, prop_, st_, bx_):
            nonlocal kv_state
            st_, bx_, kv_state, rej = run_steps_sm(
                kp_, r_, kv, n_, tick_, prop_, st_, bx_, kv_state)
            sm_rejects.append(rej)
            return st_, bx_

    B = kp.proposal_cap
    now = 0
    if not device_sm:
        # latency instrumentation state — only the non-SM phases use it,
        # and the [G, log_cap] stamp ring is real HBM at device scale
        stamp, hist, reads = lat_init(kp, state.term.shape[0])

    def lat_run(iters, width, do_reads, tick, propose):
        nonlocal state, box, stamp, hist, reads, now
        state, box, stamp, hist, reads = run_steps_lat(
            kp, replicas, iters, width, do_reads, tick, propose,
            jnp.asarray(now, jnp.int32), state, box, stamp, hist, reads)
        now += iters

    def committed():
        return np.asarray(state.committed)[lead].astype(np.int64).sum()

    def timed_window(run_fn, total, snap=None):
        """Warm the exact chunk/remainder executables, call ``snap`` to
        capture pre-window baselines, then run ``total`` steps in
        watchdog-safe chunks (one long device launch can trip the ~60 s
        TPU watchdog).  Returns (warmup_s, window_s).  ONE helper so the
        three phases cannot drift in methodology."""
        tw = time.time()
        run_fn(min(chunk, total))
        if total % chunk:
            run_fn(total % chunk)
        state.term.block_until_ready()
        warm_s = time.time() - tw
        if snap is not None:
            snap()
        t0 = time.time()
        done = 0
        while done < total:
            n = min(chunk, total - done)
            run_fn(n)
            done += n
        state.committed.block_until_ready()
        return warm_s, time.time() - t0

    # Default chunk scales inversely with G to keep every device launch
    # well under the ~60 s TPU watchdog; iters is a static jit arg, so
    # timed_window warms exactly the chunk/remainder variants it runs
    default_chunk = max(2, min(25, (25 * 1024) // max(groups, 1)))
    chunk = max(1, int(os.environ.get("BENCH_CHUNK", str(default_chunk))))

    # ---- phase A: write-only throughput (the headline metric runs the
    # UNinstrumented loop; latency capture is a separate phase below —
    # its stamp/histogram one-hots roughly double the step cost).
    # Measured as MEDIAN-OF-3 windows: one long window has no defense
    # against a transiently noisy box (the r2->r4 headline decline was
    # measurement contention, not code — PERF.md), and the lower-middle
    # median discards a single inflated window while never inventing a
    # number faster than a window actually measured. ----
    def plain_run(iters):
        nonlocal state, box
        state, box = run_steps(kp, replicas, iters, True, True, state, box)

    snaps = {}
    windows: list[dict] = []
    wsteps = max(20, steps // 3)

    def run_a_window():
        def snap():
            sm_rejects.clear()  # warmup rejects are outside the window
            snaps["c0"] = committed()

        warm, dtw = timed_window(plain_run, wsteps, snap)
        # accumulate in-window rejects across windows (the clear above
        # discards only warmup-segment rejects)
        snaps["rej"] = snaps.get("rej", 0) + sum(int(r) for r in sm_rejects)
        w = int(committed() - snaps["c0"])
        windows.append({
            "steps": wsteps,
            "wall_s": round(dtw, 3),
            "step_ms": round(dtw / wsteps * 1e3, 3),
            "writes": w,
            "writes_per_s": round(w / dtw),
        })
        return warm

    def median_window() -> dict:
        # lower-middle: contention only ever inflates a window, so ties
        # break toward the measurement the box actually achieved
        ws = sorted(windows, key=lambda r: r["step_ms"])
        return ws[(len(ws) - 1) // 2]

    t0 = time.time()
    compile_s = run_a_window()
    for _ in range(2):
        run_a_window()
    med = median_window()
    writes = sum(w["writes"] for w in windows)
    dt = sum(w["wall_s"] for w in windows)
    wps = med["writes_per_s"]
    step_ms = med["step_ms"]

    # provisional record: if a slow run is killed externally in a
    # later phase, the LAST stdout line is still a valid measurement of
    # the headline instead of nothing (the complete line below
    # supersedes it on a full run)
    _sm_note = ", device-SM apply" if device_sm else ""
    emit({
        "metric": (f"replicated writes/sec, {groups} groups x 3 replicas, "
                   f"16B{_sm_note} (provisional: phase A only)"),
        "value": round(wps),
        "unit": "writes/s",
        "vs_baseline": round(wps / BASELINE_WPS, 4),
        "detail": {"platform": platform, "groups": groups,
                   "provisional": "later phases may still be running"},
    })

    detail = {
        "platform": platform,
        "groups": groups,
        "steps": len(windows) * wsteps,
        "wall_s": round(dt, 3),
        "step_ms": round(step_ms, 3),
        "writes": writes,
        "writes_per_group_step": round(
            med["writes"] / med["steps"] / groups, 2),
        "headline_policy": "lower-median of timed windows",
        "headline_windows": windows,
        "warmup_steps_s": round(compile_s, 1),
        "total_setup_s": round(t0 - t_build + compile_s, 1),
    }
    if device_sm:
        detail["sm_rejected_writes"] = int(snaps.get("rej", 0))
        detail["sm_apply"] = ("pallas" if kv.use_pallas else
                              ("range" if not kv.hash_keys else "scan"))
        # ---- device-SM phase B: the same served-read mix the default
        # bench records — ONE implementation (_run_served) so the two
        # modes cannot drift in accounting or record schema ----
        mixed_steps = int(os.environ.get(
            "BENCH_MIXED_STEPS", str(max(40, steps // 2))))
        WW = max(1, min(B, int(os.environ.get(
            "BENCH_MIXED_WRITE_WIDTH", str(B)))))
        try:
            detail["mixed_9to1_served"] = _run_served(
                replicas, groups, mixed_steps, WW, chunk)
        except Exception as e:
            detail["mixed_9to1_served"] = {"error": repr(e)[-300:]}
    else:
        # ---- phase A2: commit-latency percentiles (instrumented loop) ----
        lat_steps = int(os.environ.get("BENCH_LAT_STEPS",
                                       str(max(40, steps // 2))))

        def snap_lat():
            snaps["hist0"] = np.asarray(hist).astype(np.int64)

        _, dtL = timed_window(
            lambda n: lat_run(n, B, False, True, True), lat_steps, snap_lat)
        lat_step_ms = dtL / lat_steps * 1e3
        histA = np.asarray(hist).astype(np.int64) - snaps["hist0"]
        lat_ms = {}
        for name, q in (("p50", 0.50), ("p99", 0.99), ("p99.9", 0.999)):
            p = _pctile(histA, q)
            # latency in instrumented steps, scaled to the HEADLINE
            # step_ms: the pipeline depth (steps) is what the kernel
            # determines; the production step cost is the uninstrumented
            # one
            lat_ms[name] = (round(p * step_ms, 3) if p is not None
                            else None)
        # resolution is one device step: a release in the proposing step
        # reports 0 buckets -> "< step_ms"
        lat_ms["resolution_ms"] = round(step_ms, 3)
        lat_ms["instrumented_step_ms"] = round(lat_step_ms, 3)
        detail["commit_latency_ms"] = lat_ms

        # ---- phase B: 9:1 read:write mix over ReadIndex (config #3) —
        # measured on the UNinstrumented mixed loop (run_steps_mixed):
        # reads are counted by the completed-ctx carry, not the stamp
        # ring, so the number is apples-to-apples with phase A ----
        from dragonboat_tpu.bench_loop import run_steps_mixed

        mixed_steps = int(os.environ.get(
            "BENCH_MIXED_STEPS", str(max(40, steps // 2))))
        # writes keep the full batch width: the 9:1 ratio is carried by
        # the read batch behind each ReadIndex ctx (raft.go ReadIndex
        # batching serves every read queued at confirmation time), and
        # ctx confirmation throughput (~1/group/step, one piggybacked
        # heartbeat round) is independent of the write width — narrowing
        # writes only shrank both terms of the mix
        WW = max(1, min(B, int(os.environ.get("BENCH_MIXED_WRITE_WIDTH",
                                              str(B)))))

        def mixed_run(iters):
            nonlocal state, box, reads, now
            state, box, reads = run_steps_mixed(
                kp, replicas, iters, WW, jnp.asarray(now, jnp.int32),
                state, box, reads)
            now += iters

        def snap_mixed():
            snaps["reads0"], snaps["cB0"] = int(np.asarray(reads)), committed()

        _, dtB = timed_window(mixed_run, mixed_steps, snap_mixed)
        writes_b = int(committed() - snaps["cB0"])
        ctx = int(np.asarray(reads)) - snaps["reads0"]
        # one ReadIndex ctx serves the read batch queued behind it
        # (raft.go ReadIndex batching); 9:1 mix => 9 reads per write
        read_batch = 9 * WW
        reads_ops = min(ctx * read_batch, 9 * writes_b)
        mixed_ops = (writes_b + reads_ops) / dtB
        mixed_step_ms = dtB / mixed_steps * 1e3
        # SECONDARY diagnostic: reads here are ReadIndex PERMITS
        # (confirmed-ctx batch capacity, capped at 9 per committed
        # write), NOT executed lookups — the recorded config-#3 number
        # is mixed_9to1_served below, where every counted read is a real
        # table lookup.  No vs_baseline field here on purpose: permit
        # capacity must not be comparable against the reference's 11M
        # served ops/s.
        detail["mixed_9to1_permits"] = {
            "read_accounting": "permits",
            "ops_per_s": round(mixed_ops),
            "writes_per_s": round(writes_b / dtB),
            "read_ctx_per_s": round(ctx / dtB),
            "read_batch_per_ctx": read_batch,
            "steps": mixed_steps,
            "step_ms": round(mixed_step_ms, 3),
        }

        # ---- cross-phase consistency: the mixed loop runs the SAME
        # kernel plus ReadIndex work, so write-only step_ms above mixed
        # step_ms by >15% means phase A was measured on a contended box
        # (exactly r4's self-contradicting record).  Re-measure phase A
        # once and let the median absorb the inflated windows. ----
        contended = step_ms > 1.15 * mixed_step_ms
        if contended:
            run_a_window()
            med = median_window()
            writes = sum(w["writes"] for w in windows)
            dt = sum(w["wall_s"] for w in windows)
            wps = med["writes_per_s"]
            step_ms = med["step_ms"]
            detail.update(
                steps=len(windows) * wsteps,
                wall_s=round(dt, 3), step_ms=round(step_ms, 3),
                writes=writes,
                writes_per_group_step=round(
                    med["writes"] / med["steps"] / groups, 2))
        detail["contention"] = {
            "write_only_vs_mixed_step": round(
                step_ms / max(mixed_step_ms, 1e-9), 3),
            "detected": bool(contended),
            "extra_windows_measured": len(windows) - 3,
        }

        # ---- phase D: membership-change wave + compaction under load
        # (config #5, kernel rendition): every group commits a config
        # change mid-stream while the write pipeline and the device ring
        # compaction keep running; the host clears the one-in-flight
        # gate after each wave, as the engine's CC apply does ----
        if os.environ.get("BENCH_CC", "1") == "1":
            from dragonboat_tpu.bench_loop import cc_step

            cc_rounds = max(1, int(os.environ.get("BENCH_CC_ROUNDS", "3")))
            cc_period = max(4, chunk)
            # warm BOTH executables outside the window (iters is a
            # static jit arg: cc_period-1 is a fresh run_steps variant)
            state, box, acc0, idx0 = cc_step(kp, replicas, state, box)
            state, box = run_steps(kp, replicas, cc_period - 1,
                                   True, True, state, box)
            state.term.block_until_ready()
            snap0 = int(np.asarray(state.snap_index)[lead]
                        .astype(np.int64).sum())
            cD0 = committed()
            waves = []
            tD = time.time()
            for _ in range(cc_rounds):
                # gate release: the engine does this when the CC applies
                state = state._replace(
                    pending_cc=jnp.zeros_like(state.pending_cc))
                state, box, acc, idx = cc_step(kp, replicas, state, box)
                waves.append((acc, idx))
                state, box = run_steps(kp, replicas, cc_period - 1,
                                       True, True, state, box)
            state.committed.block_until_ready()
            dtD = time.time() - tD
            writes_d = int(committed() - cD0)
            committed_now = np.asarray(state.committed)
            cc_done = cc_acc = 0
            for acc, idx in waves:
                # prop_accepted is only ever set on the at-step leader
                # row — no extra role mask (a stale leadership snapshot
                # would undercount groups whose leader moved)
                a = np.asarray(acc)
                cc_acc += int(a.sum())
                cc_done += int((a & (committed_now >= np.asarray(idx))).sum())
            snap1 = int(np.asarray(state.snap_index)[lead]
                        .astype(np.int64).sum())
            total_d = cc_rounds * cc_period
            detail["membership_wave"] = {
                "rounds": cc_rounds,
                "cc_accepted": cc_acc,
                "cc_committed": cc_done,
                "writes_per_s": round(writes_d / dtD),
                "step_ms": round(dtD / total_d * 1e3, 3),
                # throughput under the wave vs the write-only phase A
                "vs_write_only": round((writes_d / dtD) / max(wps, 1), 3),
                # device-side log compaction kept running under load
                "compaction_floor_advance": snap1 - snap0,
            }

        # ---- phase E: config #1 single-shard datapoint — the G=1
        # write throughput every other phase deliberately avoids
        # (batching across groups is the whole thesis; this measures
        # what ONE shard gets) ----
        if os.environ.get("BENCH_CONFIG1", "1") != "1":
            detail["config1_single_shard"] = {"skipped": "BENCH_CONFIG1=0"}
        elif not time_left(120):
            detail["config1_single_shard"] = {
                "skipped": "time budget exhausted before config-1 phase"}
        else:
            try:
                detail["config1_single_shard"] = _run_single_shard(
                    replicas, max(50, steps))
            except Exception as e:  # must not cost the whole record
                detail["config1_single_shard"] = {"error": repr(e)[-300:]}

        # ---- phase B2: 9:1 mix with reads SERVED — the recorded
        # config-#3 number.  A fresh device-SM cluster at the same G:
        # payloads ride the replicated lv ring into the range apply, and
        # every counted read is an EXECUTED slot-scan lookup against the
        # device-resident table, checksum-folded so XLA cannot elide it
        # (bench_loop.run_steps_mixed_sm).  Direct-mapped table: raft
        # applies a contiguous index window, which is also the
        # reference's bench-SM shape (kvtest-style fixed keyspace);
        # hashed-table serving exists and is differential-tested, but
        # its probing apply measures the hash scheme, not the mix. ----
        if os.environ.get("BENCH_SERVED", "1") != "1":
            detail["mixed_9to1_served"] = {"skipped": "BENCH_SERVED=0"}
        elif not time_left(180):
            detail["mixed_9to1_served"] = {
                "skipped": "time budget exhausted before served phase"}
        else:
            try:
                detail["mixed_9to1_served"] = _run_served(
                    replicas, groups, mixed_steps, WW, chunk)
            except Exception as e:  # must not cost the whole record
                detail["mixed_9to1_served"] = {"error": repr(e)[-300:]}

        # ---- phase C: 10k-shard election storm (config #4) ----
        if os.environ.get("BENCH_STORM", "1") == "1":
            if time_left(240):
                try:
                    detail["election_storm"] = _run_storm(platform)
                except Exception as e:  # failure must not cost the run
                    detail["election_storm"] = {"error": repr(e)[-300:]}
            else:
                detail["election_storm"] = {
                    "skipped": "time budget exhausted before storm phase"}

    sm_note = ", device-SM apply" if device_sm else ""
    emit({
        "metric": (f"replicated writes/sec, {groups} groups x 3 replicas, "
                   f"16B{sm_note}"),
        "value": round(wps),
        "unit": "writes/s",
        "vs_baseline": round(wps / BASELINE_WPS, 4),
        "detail": detail,
    })


def run_serve_bench() -> None:
    """BENCH_SERVE=1: the SERVING-PATH benchmark — clients propose
    through the real NodeHost API into device-resident shards across
    three in-process hosts (chan transport), every write a full raft
    round ending in one batched fsync.  This is the apples-to-apples
    shape of the reference's own benchmark (3 servers, client sessions,
    full stack) — the kernel-only phases above measure the device
    ceiling; this measures the product.

    Two payload phases: 16B uncompressed (the headline shape), then
    1024B with entry_compression="snappy" on a second shard set — the
    r4 entry-compression codec measured on the path that actually
    invokes it (node.propose encodes at propose time, node.py:301).

    Knobs: BENCH_SERVE_SHARDS (default 32), BENCH_SERVE_SECONDS (5),
    BENCH_SERVE_WINDOW (pipelined proposals per shard, 32),
    BENCH_SERVE_1024_SHARDS (default min(8, shards); 0 skips the
    compressed-payload phase)."""
    import shutil
    import tempfile
    import threading
    import time as _t

    from dragonboat_tpu.client import Session
    from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class NullSM(IStateMachine):
        """16B-payload sink (the reference benchmark SM records nothing)."""

        def __init__(self, *a):
            self.n = 0

        def update(self, entry):
            self.n += 1
            return Result(value=self.n)

        def lookup(self, q):
            return self.n

        def save_snapshot(self, w, files, done):
            w.write(b"\x00")

        def recover_from_snapshot(self, r, files, done):
            r.read(1)

    n_shards = int(os.environ.get("BENCH_SERVE_SHARDS", "32"))
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", "5"))
    window = int(os.environ.get("BENCH_SERVE_WINDOW", "32"))
    n_comp = int(os.environ.get("BENCH_SERVE_1024_SHARDS",
                                str(min(8, n_shards))))
    shards = tuple(range(1, n_shards + 1))
    # the compressed-payload shard set rides the same hosts under its
    # own shard ids; both sets exist from startup (one election wait)
    comp_shards = tuple(range(n_shards + 1, n_shards + 1 + n_comp))
    addrs = {1: "sv-1", 2: "sv-2", 3: "sv-3"}
    ex = ExpertConfig(kernel_log_cap=128,
                      kernel_capacity=n_shards + n_comp,
                      kernel_apply_batch=32, kernel_compaction_overhead=16)
    hosts = {}
    # REAL durability: each host gets a tan LogDB on disk so every write
    # ends in an actual batched fsync (an empty node_host_dir would fall
    # back to the in-memory LogDB and void the durability claim)
    root = tempfile.mkdtemp(prefix="dbtpu-serve-")
    try:
        for rid, addr in addrs.items():
            nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=2, expert=ex,
                node_host_dir=os.path.join(root, f"nh{rid}")))
            hosts[rid] = nh
            for sid in shards:
                nh.start_replica(addrs, False, NullSM, Config(
                    shard_id=sid, replica_id=rid, election_rtt=10,
                    heartbeat_rtt=2, device_resident=True))
            for sid in comp_shards:
                nh.start_replica(addrs, False, NullSM, Config(
                    shard_id=sid, replica_id=rid, election_rtt=10,
                    heartbeat_rtt=2, device_resident=True,
                    entry_compression="snappy"))
        all_shards = shards + comp_shards
        deadline = _t.time() + 120
        elected = 0
        while _t.time() < deadline:
            elected = sum(1 for s in all_shards
                          if any(hosts[r].get_leader_id(s)[1]
                                 for r in addrs))
            if elected == len(all_shards):
                break
            _t.sleep(0.1)

        def measure_window(sids: tuple, payload: bytes,
                           run_s: float) -> dict:
            done = threading.Event()
            counts = [0] * len(sids)
            lats: list[list[float]] = [[] for _ in sids]

            def writer(i: int, sid: int) -> None:
                # steady pipelined client: the window stays FULL — one
                # new proposal is issued as each oldest completes (no
                # batch barrier); the leader host is re-resolved on
                # failures
                from collections import deque

                sess = Session.new_noop_session(sid)

                def leader_host():
                    lid, ok = hosts[1].get_leader_id(sid)
                    return hosts[lid if ok and lid in hosts else 1]

                futs: deque = deque()
                while not done.is_set():
                    try:
                        nh = leader_host()
                        while len(futs) < window:
                            futs.append((nh.propose(sess, payload,
                                                    timeout_s=10.0),
                                         _t.time()))
                        f, t0 = futs.popleft()
                        f.get(10.0)
                        counts[i] += 1
                        lats[i].append(_t.time() - t0)
                    except Exception:
                        futs.clear()   # window poisoned by a leader move
                        _t.sleep(0.02)

            threads = [threading.Thread(target=writer, args=(i, sid),
                                        daemon=True)
                       for i, sid in enumerate(sids)]
            t_start = _t.time()
            for t in threads:
                t.start()
            _t.sleep(run_s)
            # snapshot the window BEFORE done/join: the drain tail
            # (writers blocked in f.get timeouts) must not dilute the
            # steady-state rate
            wall = _t.time() - t_start
            total = sum(counts)
            done.set()
            for t in threads:
                t.join(timeout=15)
            all_lats = sorted(x for li in lats for x in li)

            def pct(q):
                return (round(all_lats[int(q * (len(all_lats) - 1))]
                              * 1e3, 2) if all_lats else None)

            return {
                "shards": len(sids),
                "seconds": round(wall, 2),
                "writes": total,
                "writes_per_s": round(total / wall),
                "client_latency_ms": {"p50": pct(0.50), "p99": pct(0.99)},
            }

        main_rec = measure_window(shards, b"x" * 16, seconds)
        detail = {
            "mode": "serve",
            "shards": n_shards,
            "window": window,
            "seconds": main_rec["seconds"],
            "writes": main_rec["writes"],
            "elected": elected,
            "client_latency_ms": main_rec["client_latency_ms"],
        }
        # ---- 1024B payload phase: large writes through the snappy
        # entry-compression codec (node.propose encodes; the 16B phase
        # never invokes it — 1024B is the shape compression exists for)
        if n_comp > 0:
            comp_rec = measure_window(comp_shards, b"x" * 1024, seconds)
            comp_rec["payload_bytes"] = 1024
            comp_rec["entry_compression"] = "snappy"
            detail["payload_1024"] = comp_rec
        wps = main_rec["writes"] / main_rec["seconds"]
        emit({
            "metric": (f"serving-path writes/sec, {n_shards} shards x 3 "
                       f"replicas, 16B, window {window}"),
            "value": round(wps),
            "unit": "writes/s",
            "vs_baseline": round(wps / BASELINE_WPS, 4),
            "detail": detail,
        })
    finally:
        for nh in hosts.values():
            nh.close()
        shutil.rmtree(root, ignore_errors=True)


def run_telemetry_ab() -> None:
    """BENCH_TELEMETRY=1: A-B overhead of the device-side fleet_stats
    reduction (core/fleet.py) at the engine's decimation cadence.

    Arm A runs the plain bench loop in ``every``-step launches; arm B
    runs the identical launches plus one jitted ``fleet_stats`` call and
    its host fetch per launch — exactly what KernelEngine's
    ``_collect_fleet_stats`` adds every ``fleet_stats_every`` steps.
    Arms are interleaved A,B,A,B,... (median-of-3 per arm) so box drift
    lands on both.  Knobs: BENCH_TELEM_GROUPS (default 10000),
    BENCH_TELEM_STEPS (120), BENCH_TELEM_EVERY (10)."""
    import numpy as np  # noqa: F401

    import jax

    from dragonboat_tpu.bench_loop import (
        bench_params,
        elect_all,
        make_cluster,
        run_steps,
    )
    from dragonboat_tpu.core import fleet

    platform = jax.devices()[0].platform
    replicas = 3
    g = int(os.environ.get("BENCH_TELEM_GROUPS", "10000"))
    steps = int(os.environ.get("BENCH_TELEM_STEPS", "120"))
    every = max(1, int(os.environ.get("BENCH_TELEM_EVERY", "10")))
    kp = bench_params(replicas)
    state = make_cluster(kp, g, replicas)
    state, box = elect_all(kp, replicas, state)

    def window(with_stats: bool) -> float:
        nonlocal state, box
        t0 = time.time()
        done = 0
        while done < steps:
            state, box = run_steps(kp, replicas, every, True, True,
                                   state, box)
            done += every
            if with_stats:
                fleet.stats_to_dict(fleet.fleet_stats(state, box.from_))
        state.term.block_until_ready()
        return time.time() - t0

    # warm both executables (run_steps at `every`, fleet_stats) outside
    # the timed windows
    window(True)
    a_walls, b_walls = [], []
    for _ in range(3):
        a_walls.append(window(False))
        b_walls.append(window(True))
    a = sorted(a_walls)[1]
    b = sorted(b_walls)[1]
    overhead_pct = (b - a) / a * 100.0
    emit({
        "metric": (f"fleet_stats step-latency overhead, {g} groups x "
                   f"{replicas} replicas, decimation N={every}"),
        "value": round(overhead_pct, 2),
        "unit": "% vs uninstrumented step",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "groups": g,
            "replicas": replicas,
            "steps_per_arm_window": steps,
            "decimation_every": every,
            "plain_wall_s": [round(x, 3) for x in a_walls],
            "telemetry_wall_s": [round(x, 3) for x in b_walls],
            "plain_step_ms": round(a / steps * 1e3, 3),
            "telemetry_step_ms": round(b / steps * 1e3, 3),
            "policy": "median-of-3 interleaved windows per arm",
        },
    })


def run_health_ab() -> None:
    """BENCH_HEALTH=1: interleaved A-B overhead of the device-side
    fleet_health pass (core/health.py) on top of the fleet_stats
    baseline, at the engine's decimation cadence.

    Arm A is the pre-health production path: the bench loop in
    ``every``-step launches plus one fleet_stats call + fetch per launch.
    Arm B adds exactly what KernelEngine._collect_health adds — one
    jitted ``fleet_health`` call carrying the HealthDigest between
    launches, plus its O(K) report fetch.  Arms interleave A,B,A,B,...
    (median-of-3 per arm) so box drift lands on both.  Knobs:
    BENCH_HEALTH_GROUPS (default 10000), BENCH_HEALTH_STEPS (120),
    BENCH_HEALTH_EVERY (10)."""
    import jax

    from dragonboat_tpu.bench_loop import (
        bench_params,
        elect_all,
        make_cluster,
        run_steps,
    )
    from dragonboat_tpu.core import fleet, health

    platform = jax.devices()[0].platform
    replicas = 3
    g = int(os.environ.get("BENCH_HEALTH_GROUPS", "10000"))
    steps = int(os.environ.get("BENCH_HEALTH_STEPS", "120"))
    every = max(1, int(os.environ.get("BENCH_HEALTH_EVERY", "10")))
    kp = bench_params(replicas)
    state = make_cluster(kp, g, replicas)
    state, box = elect_all(kp, replicas, state)
    num_lanes = int(state.term.shape[0])
    digest = health.empty_digest(num_lanes)

    def window(with_health: bool) -> float:
        nonlocal state, box, digest
        t0 = time.time()
        done = 0
        while done < steps:
            state, box = run_steps(kp, replicas, every, True, True,
                                   state, box)
            done += every
            fleet.stats_to_dict(fleet.fleet_stats(state, box.from_))
            if with_health:
                report, digest = health.fleet_health(state, box.from_,
                                                     digest)
                health.report_to_dict(report)
        state.term.block_until_ready()
        return time.time() - t0

    # warm all executables (run_steps, fleet_stats, fleet_health)
    # outside the timed windows
    window(True)
    a_walls, b_walls = [], []
    for _ in range(3):
        a_walls.append(window(False))
        b_walls.append(window(True))
    a = sorted(a_walls)[1]
    b = sorted(b_walls)[1]
    overhead_pct = (b - a) / a * 100.0
    emit({
        "metric": (f"fleet_health step-latency overhead, {g} groups x "
                   f"{replicas} replicas, decimation N={every}"),
        "value": round(overhead_pct, 2),
        "unit": "% vs fleet_stats-only step",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "groups": g,
            "replicas": replicas,
            "steps_per_arm_window": steps,
            "decimation_every": every,
            "stats_only_wall_s": [round(x, 3) for x in a_walls],
            "health_wall_s": [round(x, 3) for x in b_walls],
            "stats_only_step_ms": round(a / steps * 1e3, 3),
            "health_step_ms": round(b / steps * 1e3, 3),
            "top_k": health.DEFAULT_TOP_K,
            "policy": "median-of-3 interleaved windows per arm",
        },
    })


def run_transfer_ab() -> None:
    """BENCH_TRANSFER=1: interleaved A-B overhead of the transfer-guard
    rail (capacity.METER + jax.transfer_guard) on the engine dispatch
    seam.

    Arm A drives SerialDispatch + the round's staging + its one packed
    download bare; arm B runs the identical loop inside
    ``METER.guard()`` — every declared crossing then enters a scoped
    ``transfer_guard("allow")`` and bumps its tag counter, which is
    exactly what the transfer lint pass's dynamic leg and the guarded
    differential tests add on top of production.  Arms interleave
    A,B,A,B,... (median-of-3 per arm) so cluster drift lands on both.
    The detail block carries the static per-step ledger bytes at this
    geometry plus the observed METER tag counts, tying the measured
    loop to the transfer_ledger crossing inventory.  Knobs:
    BENCH_TRANSFER_GROUPS (default 2048), BENCH_TRANSFER_STEPS (200).
    Expected: noise floor — the rail is a dict bump and a context
    manager per crossing."""
    import contextlib

    import jax
    import numpy as np

    from dragonboat_tpu import capacity
    from dragonboat_tpu.analysis import transfer as transfer_pass
    from dragonboat_tpu.bench_loop import bench_params, make_cluster
    from dragonboat_tpu.engine import kernel_engine as _ke
    from dragonboat_tpu.engine.dispatch import SerialDispatch

    platform = jax.devices()[0].platform
    replicas = 3
    g = int(os.environ.get("BENCH_TRANSFER_GROUPS", "2048"))
    steps = int(os.environ.get("BENCH_TRANSFER_STEPS", "200"))
    kp = bench_params(replicas)
    state = make_cluster(kp, g, replicas)
    lanes = int(state.term.shape[0])
    disp = SerialDispatch(kp)
    staging = _ke._RoundStaging(kp, lanes)

    def window(guarded: bool) -> float:
        nonlocal state
        ctx = (capacity.METER.guard() if guarded
               else contextlib.nullcontext())
        t0 = time.time()
        with ctx:
            for _ in range(steps):
                state, down = disp.dispatch(state, staging, donate=False)
                with capacity.METER.sanctioned("round_down"):
                    np.asarray(down)
        state.term.block_until_ready()
        return time.time() - t0

    window(True)  # warm every compile and the guard path itself
    capacity.METER.reset()
    a_walls, b_walls = [], []
    for _ in range(3):
        a_walls.append(window(False))
        b_walls.append(window(True))
    a = sorted(a_walls)[1]
    b = sorted(b_walls)[1]
    overhead_pct = (b - a) / a * 100.0
    cfg = dict(transfer_pass.DEFAULT_CONFIG)
    cfg.update(num_groups=lanes, num_peers=kp.num_peers,
               log_cap=kp.log_cap, inbox_cap=kp.inbox_cap,
               msg_entries=kp.msg_entries, proposal_cap=kp.proposal_cap,
               readindex_cap=kp.readindex_cap,
               inline_payloads=bool(kp.inline_payloads))
    ledger = transfer_pass.build_ledger(
        os.path.dirname(os.path.abspath(__file__)), cfg=cfg)
    emit({
        "metric": (f"transfer-guard rail step-latency overhead, "
                   f"{g} groups x {replicas} replicas"),
        "value": round(overhead_pct, 2),
        "unit": "% vs unguarded dispatch loop",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "groups": g,
            "replicas": replicas,
            "steps_per_arm_window": steps,
            "plain_wall_s": [round(x, 3) for x in a_walls],
            "guarded_wall_s": [round(x, 3) for x in b_walls],
            "plain_step_ms": round(a / steps * 1e3, 3),
            "guarded_step_ms": round(b / steps * 1e3, 3),
            "meter_counts_all_windows": capacity.METER.counts(),
            "ledger_per_step_serial": ledger["per_step"]["serial"],
            "policy": "median-of-3 interleaved windows per arm",
        },
    })


def run_elastic_ab() -> None:
    """BENCH_ELASTIC=1: the elastic control plane's two closing numbers
    (ROADMAP item 4) in one artifact.

    Leg 1 — controller under 100:1 skew.  Three arms on the chaos
    hotspot harness (3 in-process NodeHosts, 2 device-resident shards,
    the slow-apply HotspotKV SM): uniform load with the controller ON,
    100:1 skew with the controller OFF (reference), 100:1 skew with
    the controller ON.  Each arm is its own cluster (the controller is
    an ExpertConfig bit) pumped async for one fixed wall window then
    drained; acked throughput counts resolved-completed futures over
    the pump+drain wall.  The headline value is skew-on/uniform (the
    acceptance bar: within ~15% of uniform).  The skew-off reference
    can EXCEED uniform in this harness: all three hosts share one
    process (and the GIL), so concentrating every proposal on one
    shard pipelines the slow apply back to back while uniform pays
    cross-shard staging on both — it is reported to show the harness
    ceiling, not as a bar the controller must beat.  Transfers per arm
    come from the flight recorder (CONTROL_TRANSFER records).

    Leg 2 — masked quiesce at 90% cold.  3 NodeHosts x
    BENCH_ELASTIC_SHARDS device-resident shards on one kernel; 10% of
    the shards carry continuous pipelined writers, the rest idle.  Arm
    A starts every shard with Config.quiesce=False (cold leaders keep
    heartbeating); arm B starts the cold 90% with Config.quiesce=True
    and waits for the fleet.quiesced_shards gauge to report every cold
    lane masked on every host (leaders included — heartbeats neither
    wake nor defer the masked form).  Arms run on separate sequential
    clusters (quiesce is a start-time Config bit); median-of-3 windows
    per arm read the engines' own step counters.  The saving is the
    host seam — fewer staged/emitted messages per engine round — and
    in this harness the engine thread is tick-saturated in BOTH arms
    (steps take ~10x the tick interval, so ticks coalesce and duty
    pegs at ~one core per host), which means the saving surfaces as
    cheaper per-step time, not lower duty: the headline is median
    per-step ms reduction, with duty/steps/writes in the detail.
    Knobs: BENCH_ELASTIC_PUMP_S (12), BENCH_ELASTIC_SHARDS (20),
    BENCH_ELASTIC_SECONDS (per quiesce window, 4),
    BENCH_ELASTIC_WINDOW (pipelined proposals per hot shard, 16)."""
    import shutil
    import tempfile
    import threading
    import time as _t
    from collections import deque
    from random import Random

    import jax

    from dragonboat_tpu import flight, telemetry
    from dragonboat_tpu.chaos.runner import (
        _Cluster, HotspotKV, HOTSPOT_HOT_EWMA_US, HOTSPOT_MAX_PENDING,
        HOTSPOT_SKEW)
    from dragonboat_tpu.client import Session
    from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import IStateMachine, Result

    platform = jax.devices()[0].platform
    pump_s = float(os.environ.get("BENCH_ELASTIC_PUMP_S", "12"))
    n_shards = int(os.environ.get("BENCH_ELASTIC_SHARDS", "20"))
    seconds = float(os.environ.get("BENCH_ELASTIC_SECONDS", "4"))
    window = int(os.environ.get("BENCH_ELASTIC_WINDOW", "16"))
    seed = 11

    # -- leg 1: controller A/B under skew --------------------------------

    def leg1_arm(name: str, controller_on: bool, skew: bool) -> dict:
        rng = Random(seed)
        shards = (1, 2)
        hot, cold = 1, 2
        overrides = dict(
            fleet_stats_every=5,
            control_enabled=controller_on, control_hysteresis=2,
            control_cooldown_obs=8, control_max_transfers=1,
            control_seed=seed, control_hot_ewma_us=HOTSPOT_HOT_EWMA_US)
        cluster = _Cluster(seed=seed, n=3, device_resident=True,
                           expert_overrides=overrides, shards=shards,
                           sm_cls=HotspotKV)
        pending: list = []

        def fire(sid: int, cmd: bytes) -> None:
            rids = cluster.live_rids()
            nh = cluster.hosts[rids[len(pending) % len(rids)]]
            try:
                rs = nh.propose(nh.get_noop_session(sid), cmd,
                                timeout_s=30.0)
            except Exception:
                return      # book full / not ready: a drop, not an ack
            pending.append(rs)

        def unresolved() -> int:
            return sum(1 for rs in pending if not rs._event.is_set())

        def max_ewma() -> int:
            return max((int(cluster.hosts[rid].events.metrics.snapshot()
                            .get("engine.kernel_step.ewma_us", 0))
                        for rid in cluster.live_rids()), default=0)

        try:
            cluster.start()
            for sid in shards:
                assert cluster.propose(f"g{sid}=1".encode(), timeout=45.0,
                                       shard=sid), f"shard {sid} stuck"
            # let the jit-compile EWMA spike decay so the controller's
            # warmup guard is not what the arms measure
            deadline = _t.time() + 60.0
            while (max_ewma() >= HOTSPOT_HOT_EWMA_US
                   and _t.time() < deadline):
                _t.sleep(0.25)
            start_seq = flight.RECORDER.next_seq
            t0 = _t.time()
            i = 0
            while _t.time() - t0 < pump_s:
                if unresolved() < HOTSPOT_MAX_PENDING:
                    if skew:
                        batch = [hot] * HOTSPOT_SKEW + [cold]
                    else:
                        batch = [hot, cold] * (HOTSPOT_SKEW // 2)
                    rng.shuffle(batch)
                    for sid in batch:
                        if _t.time() - t0 >= pump_s:
                            break
                        fire(sid, f"h{sid}i{i}=v".encode())
                        i += 1
                _t.sleep(0.02)
            deadline = _t.time() + 60.0
            while unresolved() and _t.time() < deadline:
                _t.sleep(0.1)
            wall = _t.time() - t0
            acked = sum(1 for rs in pending if rs.wait(0).completed())
            transfers = sum(
                1 for r in flight.RECORDER.tail()
                if r["seq"] >= start_seq
                and r["kind"] == flight.CONTROL_TRANSFER)
            return {"arm": name, "fired": len(pending), "acked": acked,
                    "wall_s": round(wall, 1), "transfers": transfers,
                    "unresolved": unresolved(),
                    "acked_per_s": round(acked / wall, 1)}
        finally:
            cluster.close()

    uniform = leg1_arm("uniform-ctl-on", True, False)
    skew_off = leg1_arm("skew-ctl-off", False, True)
    skew_on = leg1_arm("skew-ctl-on", True, True)
    ratio = skew_on["acked_per_s"] / max(1e-9, uniform["acked_per_s"])
    emit({
        "metric": ("elastic controller: 100:1-skew acked throughput "
                   "vs uniform, controller on"),
        "value": round(ratio * 100.0, 1),
        "unit": "% of uniform acked throughput",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "pump_s": pump_s,
            "skew": HOTSPOT_SKEW,
            "arms": [uniform, skew_off, skew_on],
            "skew_off_over_uniform": round(
                skew_off["acked_per_s"]
                / max(1e-9, uniform["acked_per_s"]), 3),
            "policy": ("one pumped window per arm, one cluster per arm "
                       "(controller on/off is start-time ExpertConfig); "
                       "single-process GIL-shared harness, slow-apply "
                       "SM — the skew-off reference shows the "
                       "apply-bound ceiling of one concentrated shard"),
        },
    })

    # -- leg 2: masked quiesce at 90% cold -------------------------------

    class NullSM(IStateMachine):
        def __init__(self, *a):
            self.n = 0

        def update(self, entry):
            self.n += 1
            return Result(value=self.n)

        def lookup(self, q):
            return self.n

        def save_snapshot(self, w, files, done):
            w.write(b"\x00")

        def recover_from_snapshot(self, r, files, done):
            r.read(1)

    shards = tuple(range(1, n_shards + 1))
    hot_shards = shards[:max(1, n_shards // 10)]
    cold_shards = shards[len(hot_shards):]
    addrs = {1: "el-1", 2: "el-2", 3: "el-3"}

    def leg2_arm(quiesce_cold: bool) -> dict:
        ex = ExpertConfig(kernel_log_cap=128, kernel_capacity=n_shards,
                          kernel_apply_batch=32,
                          kernel_compaction_overhead=16,
                          fleet_stats_every=8)
        hosts: dict = {}
        root = tempfile.mkdtemp(prefix="dbtpu-elastic-")
        stop = threading.Event()
        writers: list = []
        try:
            for rid, addr in addrs.items():
                nh = NodeHost(NodeHostConfig(
                    raft_address=addr, rtt_millisecond=2, expert=ex,
                    node_host_dir=os.path.join(root, f"nh{rid}")))
                hosts[rid] = nh
                for sid in shards:
                    # heartbeat_rtt=1: the cold 90%'s heartbeat volume
                    # IS what the quiesce mask deletes — run it at the
                    # chaos harness's rate so the off arm carries it
                    nh.start_replica(addrs, False, NullSM, Config(
                        shard_id=sid, replica_id=rid, election_rtt=10,
                        heartbeat_rtt=1, device_resident=True,
                        quiesce=quiesce_cold and sid in cold_shards))
            deadline = _t.time() + 120
            while _t.time() < deadline:
                if all(any(hosts[r].get_leader_id(s)[1] for r in addrs)
                       for s in shards):
                    break
                _t.sleep(0.1)

            acked = [0] * len(hot_shards)

            def writer(i: int, sid: int) -> None:
                sess = Session.new_noop_session(sid)

                def leader_host():
                    lid, ok = hosts[1].get_leader_id(sid)
                    return hosts[lid if ok and lid in hosts else 1]

                futs: deque = deque()
                payload = b"x" * 16
                while not stop.is_set():
                    try:
                        nh = leader_host()
                        while len(futs) < window:
                            futs.append(nh.propose(sess, payload,
                                                   timeout_s=10.0))
                        futs.popleft().get(10.0)
                        acked[i] += 1
                    except Exception:
                        futs.clear()
                        _t.sleep(0.02)

            writers = [threading.Thread(target=writer, args=(i, sid),
                                        daemon=True)
                       for i, sid in enumerate(hot_shards)]
            for t in writers:
                t.start()

            def quiesced_total() -> int:
                return sum(
                    int(hosts[r].events.metrics.snapshot()
                        .get("fleet.quiesced_shards", 0)) for r in addrs)

            # idle cold lanes cross the e_timeout*10 idle threshold in
            # ~200 ms here; wait for EVERY cold lane on EVERY host so
            # the windows measure the fully-engaged mask (arm A settles
            # the same wall time so warmup drift lands on both arms)
            want = len(cold_shards) * len(addrs) if quiesce_cold else 0
            deadline = _t.time() + 30.0
            while quiesced_total() < want and _t.time() < deadline:
                _t.sleep(0.1)
            _t.sleep(1.0)

            def step_totals() -> tuple[int, int]:
                # the round timer's histogram: every engine of the process
                snap = telemetry.GLOBAL.snapshot()
                return (snap.get("engine_round_us.count{phase=total}", 0),
                        int(snap.get("engine_round_us.sum{phase=total}", 0)))

            def measure() -> dict:
                s0, u0 = step_totals()
                w0 = sum(acked)
                _t.sleep(seconds)
                s1, u1 = step_totals()
                w1 = sum(acked)
                return {
                    "steps": s1 - s0,
                    "step_ms": round((u1 - u0) / max(1, s1 - s0) / 1e3,
                                     3),
                    "duty_ms_per_s": round((u1 - u0) / 1e3 / seconds, 1),
                    "writes_per_s": round((w1 - w0) / seconds),
                }
            measure()    # warm one throwaway window
            runs = [measure() for _ in range(3)]
            return {"runs": runs, "quiesced_gauge": quiesced_total(),
                    "step_ms": sorted(r["step_ms"] for r in runs)[1],
                    "duty_ms_per_s": sorted(
                        r["duty_ms_per_s"] for r in runs)[1]}
        finally:
            stop.set()
            for t in writers:
                t.join(timeout=15)
            for nh in hosts.values():
                nh.close()
            shutil.rmtree(root, ignore_errors=True)

    off = leg2_arm(False)
    on = leg2_arm(True)
    a, b = off["step_ms"], on["step_ms"]
    reduction_pct = (a - b) / max(1e-9, a) * 100.0
    emit({
        "metric": (f"masked quiesce: engine step-time reduction, "
                   f"{n_shards} shards x 3 replicas, "
                   f"{len(cold_shards)} cold"),
        "value": round(reduction_pct, 1),
        "unit": "% median per-step ms vs quiesce-off",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "shards": n_shards,
            "hot_shards": len(hot_shards),
            "cold_shards": len(cold_shards),
            "seconds_per_window": seconds,
            "off_arm": off,
            "on_arm": on,
            "expected_quiesced_gauge": len(cold_shards) * len(addrs),
            "policy": ("median-of-3 windows per arm, arms on separate "
                       "sequential clusters (quiesce is start-time "
                       "Config); engine threads are tick-saturated in "
                       "both arms (duty pegs ~1 core/host), so the "
                       "host-seam saving lands in per-step ms — "
                       "device shapes are fixed by design"),
        },
    })


def run_safety_ab() -> None:
    """BENCH_SAFETY=1: interleaved A-B overhead of the runtime
    invariant probe (core/invariants.py) on top of the fleet_stats +
    fleet_health production path, at the engine's decimation cadence.

    Arm A is the pre-probe production path: the bench loop in
    ``every``-step launches plus one fleet_stats and one fleet_health
    call + fetch per launch.  Arm B adds exactly what
    KernelEngine._collect_invariants adds — one jitted
    ``check_invariants`` call carrying the InvariantDigest between
    launches, plus its O(NI) report fetch.  Arms interleave A,B,A,B,...
    (median-of-3 per arm) so box drift lands on both.  Knobs:
    BENCH_SAFETY_GROUPS (default 10000), BENCH_SAFETY_STEPS (120),
    BENCH_SAFETY_EVERY (10)."""
    import jax

    from dragonboat_tpu.bench_loop import (
        bench_params,
        elect_all,
        make_cluster,
        run_steps,
    )
    from dragonboat_tpu.core import fleet, health, invariants

    platform = jax.devices()[0].platform
    replicas = 3
    g = int(os.environ.get("BENCH_SAFETY_GROUPS", "10000"))
    steps = int(os.environ.get("BENCH_SAFETY_STEPS", "120"))
    every = max(1, int(os.environ.get("BENCH_SAFETY_EVERY", "10")))
    kp = bench_params(replicas)
    state = make_cluster(kp, g, replicas)
    state, box = elect_all(kp, replicas, state)
    num_lanes = int(state.term.shape[0])
    h_digest = health.empty_digest(num_lanes)
    i_digest = invariants.empty_digest(num_lanes)
    violations_seen = 0

    def window(with_probe: bool) -> float:
        nonlocal state, box, h_digest, i_digest, violations_seen
        t0 = time.time()
        done = 0
        while done < steps:
            state, box = run_steps(kp, replicas, every, True, True,
                                   state, box)
            done += every
            fleet.stats_to_dict(fleet.fleet_stats(state, box.from_))
            h_report, h_digest = health.fleet_health(state, box.from_,
                                                     h_digest)
            health.report_to_dict(h_report)
            if with_probe:
                i_report, i_digest = invariants.check_invariants(
                    state, i_digest)
                violations_seen += invariants.report_to_dict(
                    i_report)["total"]
        state.term.block_until_ready()
        return time.time() - t0

    # warm all executables (run_steps, fleet_stats, fleet_health,
    # check_invariants) outside the timed windows
    window(True)
    a_walls, b_walls = [], []
    for _ in range(3):
        a_walls.append(window(False))
        b_walls.append(window(True))
    a = sorted(a_walls)[1]
    b = sorted(b_walls)[1]
    overhead_pct = (b - a) / a * 100.0
    emit({
        "metric": (f"invariant-probe step-latency overhead, {g} groups "
                   f"x {replicas} replicas, decimation N={every}"),
        "value": round(overhead_pct, 2),
        "unit": "% vs stats+health step",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "groups": g,
            "replicas": replicas,
            "steps_per_arm_window": steps,
            "decimation_every": every,
            "plain_wall_s": [round(x, 3) for x in a_walls],
            "probe_wall_s": [round(x, 3) for x in b_walls],
            "plain_step_ms": round(a / steps * 1e3, 3),
            "probe_step_ms": round(b / steps * 1e3, 3),
            "num_invariants": invariants.NUM_INVARIANTS,
            # the probed windows double as a scaled safety check: a
            # healthy 10k-group bench cluster must stay violation-free
            "violations_seen": int(violations_seen),
            "policy": "median-of-3 interleaved windows per arm",
        },
    })


def run_capacity_ab() -> None:
    """BENCH_CAPACITY=1: interleaved A-B overhead of the capacity rail
    (capacity.py) on top of the fleet_stats + fleet_health production
    path, at the engine's decimation cadence.

    Arm A is the post-health production path: the bench loop in
    ``every``-step launches plus one fleet_stats and one fleet_health
    call + fetch per launch.  Arm B routes the same three dispatches
    through CompileTracker wrappers (the cache-size probe around every
    call) and adds exactly what KernelEngine._collect_capacity adds per
    launch — one measure_tree_bytes walk over the live trees plus one
    engine_snapshot assembly (contracts model + allocator stats +
    watermark check).  Arms interleave A,B,A,B,... (median-of-3 per
    arm) so box drift lands on both.  Knobs: BENCH_CAPACITY_GROUPS
    (default 10000), BENCH_CAPACITY_STEPS (120), BENCH_CAPACITY_EVERY
    (10)."""
    import jax

    from dragonboat_tpu import capacity
    from dragonboat_tpu.bench_loop import (
        bench_params,
        elect_all,
        make_cluster,
        run_steps,
    )
    from dragonboat_tpu.core import fleet, health

    platform = jax.devices()[0].platform
    replicas = 3
    g = int(os.environ.get("BENCH_CAPACITY_GROUPS", "10000"))
    steps = int(os.environ.get("BENCH_CAPACITY_STEPS", "120"))
    every = max(1, int(os.environ.get("BENCH_CAPACITY_EVERY", "10")))
    kp = bench_params(replicas)
    state = make_cluster(kp, g, replicas)
    state, box = elect_all(kp, replicas, state)
    num_lanes = int(state.term.shape[0])
    digest = health.empty_digest(num_lanes)
    classes = ("ShardState", "HealthDigest")   # KernelEngine's model set

    wrapped = {
        "bench_run_steps":
            capacity.TRACKER.wrap("bench_run_steps", run_steps),
        "bench_fleet_stats":
            capacity.TRACKER.wrap("bench_fleet_stats", fleet.fleet_stats),
        "bench_fleet_health":
            capacity.TRACKER.wrap("bench_fleet_health",
                                  health.fleet_health),
    }
    peak = 0
    seq = 0

    def window(with_capacity: bool) -> float:
        nonlocal state, box, digest, peak, seq
        t0 = time.time()
        done = 0
        while done < steps:
            done += every
            if not with_capacity:
                state, box = run_steps(kp, replicas, every, True, True,
                                       state, box)
                fleet.stats_to_dict(fleet.fleet_stats(state, box.from_))
                report, digest = health.fleet_health(state, box.from_,
                                                     digest)
                health.report_to_dict(report)
                continue
            state, box = wrapped["bench_run_steps"](
                kp, replicas, every, True, True, state, box)
            fleet.stats_to_dict(
                wrapped["bench_fleet_stats"](state, box.from_))
            report, digest = wrapped["bench_fleet_health"](
                state, box.from_, digest)
            health.report_to_dict(report)
            seq += 1
            live = capacity.measure_tree_bytes(state, digest)
            peak = max(peak, live)
            capacity.engine_snapshot(
                kp, num_lanes, live, peak,
                {n: w.stats() for n, w in wrapped.items()},
                ticks=seq, classes=classes)
        state.term.block_until_ready()
        return time.time() - t0

    # warm every executable (run_steps at `every`, fleet_stats,
    # fleet_health, and the capacity host path) outside the timed windows
    window(True)
    a_walls, b_walls = [], []
    for _ in range(3):
        a_walls.append(window(False))
        b_walls.append(window(True))
    a = sorted(a_walls)[1]
    b = sorted(b_walls)[1]
    overhead_pct = (b - a) / a * 100.0
    emit({
        "metric": (f"capacity-rail step-latency overhead, {g} groups x "
                   f"{replicas} replicas, decimation N={every}"),
        "value": round(overhead_pct, 2),
        "unit": "% vs stats+health step",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "groups": g,
            "replicas": replicas,
            "steps_per_arm_window": steps,
            "decimation_every": every,
            "plain_wall_s": [round(x, 3) for x in a_walls],
            "capacity_wall_s": [round(x, 3) for x in b_walls],
            "plain_step_ms": round(a / steps * 1e3, 3),
            "capacity_step_ms": round(b / steps * 1e3, 3),
            "bench_entries": {n: w.stats() for n, w in wrapped.items()},
            "policy": "median-of-3 interleaved windows per arm",
        },
    })


def run_trace_ab() -> None:
    """BENCH_TRACE=1: interleaved A-B overhead of proposal-lifecycle
    tracing (lifecycle.py) at the default 1-in-64 sampling.

    The tracer lives in the HOST plumbing (request books, staging,
    retire, logdb, apply pool, transport hub), so the pure jitted loops
    the telemetry A/B used have no tracer presence at all — this bench
    drives the full serving path instead (the run_serve_bench harness:
    3 in-process NodeHosts, chan transport, device-resident shards,
    steady pipelined writer per shard) with traffic running
    CONTINUOUSLY while the arms alternate: each window re-points the
    process-global tracer (sample_every 0 = off vs the default 64) and
    reads the engines' own step-latency counters over the window.  Arms
    interleave A,B,A,B,... (median-of-3 per arm) so box drift lands on
    both.  Knobs: BENCH_TRACE_SHARDS (default 16), BENCH_TRACE_SECONDS
    (per window, default 4), BENCH_TRACE_WINDOW (pipelined proposals
    per shard, 16), BENCH_TRACE_EVERY (sampling rate in arm B, 64)."""
    import shutil
    import tempfile
    import threading
    import time as _t
    from collections import deque

    import jax

    from dragonboat_tpu import lifecycle, telemetry
    from dragonboat_tpu.client import Session
    from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class NullSM(IStateMachine):
        def __init__(self, *a):
            self.n = 0

        def update(self, entry):
            self.n += 1
            return Result(value=self.n)

        def lookup(self, q):
            return self.n

        def save_snapshot(self, w, files, done):
            w.write(b"\x00")

        def recover_from_snapshot(self, r, files, done):
            r.read(1)

    platform = jax.devices()[0].platform
    n_shards = int(os.environ.get("BENCH_TRACE_SHARDS", "16"))
    seconds = float(os.environ.get("BENCH_TRACE_SECONDS", "4"))
    window = int(os.environ.get("BENCH_TRACE_WINDOW", "16"))
    every = int(os.environ.get("BENCH_TRACE_EVERY", "64"))
    shards = tuple(range(1, n_shards + 1))
    addrs = {1: "tr-1", 2: "tr-2", 3: "tr-3"}
    ex = ExpertConfig(kernel_log_cap=128, kernel_capacity=n_shards,
                      kernel_apply_batch=32,
                      kernel_compaction_overhead=16,
                      trace_sample_every=0)    # arm A state at start
    hosts = {}
    root = tempfile.mkdtemp(prefix="dbtpu-trace-")
    stop = threading.Event()
    writers = []
    try:
        for rid, addr in addrs.items():
            nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=2, expert=ex,
                node_host_dir=os.path.join(root, f"nh{rid}")))
            hosts[rid] = nh
            for sid in shards:
                nh.start_replica(addrs, False, NullSM, Config(
                    shard_id=sid, replica_id=rid, election_rtt=10,
                    heartbeat_rtt=2, device_resident=True))
        deadline = _t.time() + 120
        while _t.time() < deadline:
            if all(any(hosts[r].get_leader_id(s)[1] for r in addrs)
                   for s in shards):
                break
            _t.sleep(0.1)

        acked = [0] * n_shards

        def writer(i: int, sid: int) -> None:
            sess = Session.new_noop_session(sid)

            def leader_host():
                lid, ok = hosts[1].get_leader_id(sid)
                return hosts[lid if ok and lid in hosts else 1]

            futs: deque = deque()
            payload = b"x" * 16
            while not stop.is_set():
                try:
                    nh = leader_host()
                    while len(futs) < window:
                        futs.append(nh.propose(sess, payload,
                                               timeout_s=10.0))
                    futs.popleft().get(10.0)
                    acked[i] += 1
                except Exception:
                    futs.clear()
                    _t.sleep(0.02)

        writers = [threading.Thread(target=writer, args=(i, sid),
                                    daemon=True)
                   for i, sid in enumerate(shards)]
        for t in writers:
            t.start()
        _t.sleep(1.0)    # settle: windows full, elections over

        def step_totals() -> tuple[int, int]:
            # the round timer's histogram: every engine of the process
            snap = telemetry.GLOBAL.snapshot()
            return (snap.get("engine_round_us.count{phase=total}", 0),
                    int(snap.get("engine_round_us.sum{phase=total}", 0)))

        def measure(sample_every: int) -> dict:
            lifecycle.TRACER.configure(sample_every=sample_every)
            _t.sleep(0.2)    # flush windows staged under the old arm
            s0, u0 = step_totals()
            w0 = sum(acked)
            _t.sleep(seconds)
            s1, u1 = step_totals()
            w1 = sum(acked)
            return {
                "steps": s1 - s0,
                "step_ms": round((u1 - u0) / max(1, s1 - s0) / 1e3, 3),
                "writes_per_s": round((w1 - w0) / seconds),
            }

        a_runs, b_runs = [], []
        measure(0)           # warm one throwaway window
        for _ in range(3):
            a_runs.append(measure(0))
            b_runs.append(measure(every))
        stop.set()
        a = sorted(r["step_ms"] for r in a_runs)[1]
        b = sorted(r["step_ms"] for r in b_runs)[1]
        overhead_pct = (b - a) / a * 100.0
        traces = len(lifecycle.TRACER.completed())
        emit({
            "metric": (f"lifecycle-trace step-latency overhead, "
                       f"{n_shards} shards x 3 replicas, serving path, "
                       f"sampling 1/{every}"),
            "value": round(overhead_pct, 2),
            "unit": "% vs tracing-off arm",
            "vs_baseline": 0.0,
            "detail": {
                "platform": platform,
                "shards": n_shards,
                "window": window,
                "seconds_per_window": seconds,
                "sample_every": every,
                "off_arm": a_runs,
                "on_arm": b_runs,
                "off_step_ms": a,
                "on_step_ms": b,
                "completed_traces_in_ring": traces,
                "policy": "median-of-3 interleaved windows per arm, "
                          "continuous traffic",
            },
        })
    finally:
        stop.set()
        for t in writers:
            t.join(timeout=15)
        for nh in hosts.values():
            nh.close()
        shutil.rmtree(root, ignore_errors=True)


def run_fabric_ab() -> None:
    """BENCH_FABRIC=1: interleaved A-B overhead of the full fabric
    observability stack (fabric.py) — per-link transport telemetry +
    trace propagation + hop census — on top of lifecycle tracing.

    Same harness as run_trace_ab (3 in-process NodeHosts, chan
    transport, device-resident shards, continuous pipelined writers)
    but the arms toggle BOTH dials together: arm A = tracer off +
    fabric meter off, arm B = tracer at the default 1-in-64 sampling +
    fabric meter on, so the B arm pays the per-batch link tallies AND
    the sampled header/census path — the whole round-16 addition.
    Knobs: BENCH_FABRIC_SHARDS (default 16), BENCH_FABRIC_SECONDS (per
    window, default 4), BENCH_FABRIC_WINDOW (pipelined proposals per
    shard, 16), BENCH_FABRIC_EVERY (sampling rate in arm B, 64)."""
    import shutil
    import tempfile
    import threading
    import time as _t
    from collections import deque

    import jax

    from dragonboat_tpu import fabric, lifecycle, telemetry
    from dragonboat_tpu.client import Session
    from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class NullSM(IStateMachine):
        def __init__(self, *a):
            self.n = 0

        def update(self, entry):
            self.n += 1
            return Result(value=self.n)

        def lookup(self, q):
            return self.n

        def save_snapshot(self, w, files, done):
            w.write(b"\x00")

        def recover_from_snapshot(self, r, files, done):
            r.read(1)

    platform = jax.devices()[0].platform
    n_shards = int(os.environ.get("BENCH_FABRIC_SHARDS", "16"))
    seconds = float(os.environ.get("BENCH_FABRIC_SECONDS", "4"))
    window = int(os.environ.get("BENCH_FABRIC_WINDOW", "16"))
    every = int(os.environ.get("BENCH_FABRIC_EVERY", "64"))
    shards = tuple(range(1, n_shards + 1))
    addrs = {1: "fb-1", 2: "fb-2", 3: "fb-3"}
    ex = ExpertConfig(kernel_log_cap=128, kernel_capacity=n_shards,
                      kernel_apply_batch=32,
                      kernel_compaction_overhead=16,
                      trace_sample_every=0,      # arm A state at start
                      fabric_telemetry=False)
    hosts = {}
    root = tempfile.mkdtemp(prefix="dbtpu-fabric-")
    stop = threading.Event()
    writers = []
    try:
        for rid, addr in addrs.items():
            nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=2, expert=ex,
                node_host_dir=os.path.join(root, f"nh{rid}")))
            hosts[rid] = nh
            for sid in shards:
                nh.start_replica(addrs, False, NullSM, Config(
                    shard_id=sid, replica_id=rid, election_rtt=10,
                    heartbeat_rtt=2, device_resident=True))
        deadline = _t.time() + 120
        while _t.time() < deadline:
            if all(any(hosts[r].get_leader_id(s)[1] for r in addrs)
                   for s in shards):
                break
            _t.sleep(0.1)

        acked = [0] * n_shards

        def writer(i: int, sid: int) -> None:
            sess = Session.new_noop_session(sid)

            def leader_host():
                lid, ok = hosts[1].get_leader_id(sid)
                return hosts[lid if ok and lid in hosts else 1]

            futs: deque = deque()
            payload = b"x" * 16
            while not stop.is_set():
                try:
                    nh = leader_host()
                    while len(futs) < window:
                        futs.append(nh.propose(sess, payload,
                                               timeout_s=10.0))
                    futs.popleft().get(10.0)
                    acked[i] += 1
                except Exception:
                    futs.clear()
                    _t.sleep(0.02)

        writers = [threading.Thread(target=writer, args=(i, sid),
                                    daemon=True)
                   for i, sid in enumerate(shards)]
        for t in writers:
            t.start()
        _t.sleep(1.0)    # settle: windows full, elections over

        def step_totals() -> tuple[int, int]:
            # the round timer's histogram: every engine of the process
            snap = telemetry.GLOBAL.snapshot()
            return (snap.get("engine_round_us.count{phase=total}", 0),
                    int(snap.get("engine_round_us.sum{phase=total}", 0)))

        def measure(sample_every: int, fabric_on: bool) -> dict:
            lifecycle.TRACER.configure(sample_every=sample_every)
            fabric.METER.configure(enabled=fabric_on)
            _t.sleep(0.2)    # flush windows staged under the old arm
            s0, u0 = step_totals()
            w0 = sum(acked)
            _t.sleep(seconds)
            s1, u1 = step_totals()
            w1 = sum(acked)
            return {
                "steps": s1 - s0,
                "step_ms": round((u1 - u0) / max(1, s1 - s0) / 1e3, 3),
                "writes_per_s": round((w1 - w0) / seconds),
            }

        a_runs, b_runs = [], []
        measure(0, False)    # warm one throwaway window
        for _ in range(3):
            a_runs.append(measure(0, False))
            b_runs.append(measure(every, True))
        stop.set()
        a = sorted(r["step_ms"] for r in a_runs)[1]
        b = sorted(r["step_ms"] for r in b_runs)[1]
        overhead_pct = (b - a) / a * 100.0
        snap = fabric.METER.snapshot()
        emit({
            "metric": (f"fabric-telemetry step-latency overhead, "
                       f"{n_shards} shards x 3 replicas, serving path, "
                       f"tracer+meter vs neither, sampling 1/{every}"),
            "value": round(overhead_pct, 2),
            "unit": "% vs fabric-off arm",
            "vs_baseline": 0.0,
            "detail": {
                "platform": platform,
                "shards": n_shards,
                "window": window,
                "seconds_per_window": seconds,
                "sample_every": every,
                "off_arm": a_runs,
                "on_arm": b_runs,
                "off_step_ms": a,
                "on_step_ms": b,
                "links_seen": len(snap["links"]),
                "census_finished": snap["census"]["finished"],
                "p50_commit_host_hops":
                    snap["census"]["p50_commit_host_hops"],
                "policy": "median-of-3 interleaved windows per arm, "
                          "continuous traffic, both dials per arm",
            },
        })
    finally:
        stop.set()
        for t in writers:
            t.join(timeout=15)
        for nh in hosts.values():
            nh.close()
        shutil.rmtree(root, ignore_errors=True)


def run_pipeline_ab() -> None:
    """BENCH_PIPELINE=1: A-B of the serial depth-0 loop vs the fused
    depth-1 pipelined loop (PR 6) at MATCHED micro-step counts — the
    pipelined arm runs half as many fori iterations, each two fused
    micro-steps, so both arms advance the protocol identically (they
    are bitwise-equal loops, tests/test_pipeline_differential.py).

    Phase 1 interleaves throughput windows A,B,A,B,... (median-of-3 per
    arm, same policy as the headline bench) and reports step_ms +
    writes/s per arm.  Phase 2 runs the instrumented latency loop per
    arm and reports commit percentiles in each arm's OWN clock unit:
    device steps for serial, pipeline steps for pipelined — raft's
    propose->commit chain spans 2 micro-steps, so the pipelined arm's
    p50 lands at <= 1 pipeline step where the serial arm needs 2.
    Knobs: BENCH_PIPE_GROUPS (default 1024 — the BENCH_r06 comparison
    geometry), BENCH_PIPE_STEPS (micro-steps per window, default 120),
    BENCH_PIPE_LAT_STEPS (default max(40, steps // 2))."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dragonboat_tpu.bench_loop import (
        bench_params,
        elect_all,
        lat_init,
        make_cluster,
        run_steps,
        run_steps_lat,
        run_steps_lat_pipelined,
        run_steps_pipelined,
    )
    from dragonboat_tpu.core import params as KP

    platform = jax.devices()[0].platform
    replicas = 3
    g = int(os.environ.get("BENCH_PIPE_GROUPS", "1024"))
    micro = int(os.environ.get("BENCH_PIPE_STEPS", "120"))
    micro -= micro % 2
    lat_steps = int(os.environ.get("BENCH_PIPE_LAT_STEPS",
                                   str(max(40, micro // 2))))
    lat_steps -= lat_steps % 2
    kp = bench_params(replicas)
    B = kp.proposal_cap
    state0, box0 = elect_all(kp, replicas, make_cluster(kp, g, replicas))
    lead = np.asarray(state0.role) == KP.LEADER

    arms = {"serial": {"state": state0, "box": box0},
            "pipelined": {"state": state0, "box": box0}}

    def committed(st):
        return np.asarray(st.committed)[lead].astype(np.int64).sum()

    def window(arm):
        a = arms[arm]
        if arm == "serial":
            def run():
                a["state"], a["box"] = run_steps(
                    kp, replicas, micro, True, True, a["state"], a["box"])
        else:
            def run():
                a["state"], a["box"] = run_steps_pipelined(
                    kp, replicas, micro // 2, True, True,
                    a["state"], a["box"])
        c0 = committed(a["state"])
        t0 = time.time()
        run()
        a["state"].term.block_until_ready()
        dt = time.time() - t0
        w = int(committed(a["state"]) - c0)
        return {"wall_s": round(dt, 3),
                "micro_step_ms": round(dt / micro * 1e3, 3),
                "writes": w,
                "writes_per_s": round(w / dt)}

    # warm both executables outside the timed windows
    for arm in arms:
        window(arm)
    wins = {"serial": [], "pipelined": []}
    for _ in range(3):
        for arm in ("serial", "pipelined"):
            wins[arm].append(window(arm))
    med = {arm: sorted(ws, key=lambda r: r["micro_step_ms"])[1]
           for arm, ws in wins.items()}

    def lat_arm(arm):
        a = arms[arm]
        pipe = arm == "pipelined"
        loop = run_steps_lat_pipelined if pipe else run_steps_lat
        iters = lat_steps // 2 if pipe else lat_steps
        stamp, hist, reads = lat_init(kp, a["state"].term.shape[0])
        # warm the exact executable; its stamps stay in the baseline
        st, bx, sp, hi, rd = loop(
            kp, replicas, iters, B, False, True, True,
            jnp.asarray(0, jnp.int32), a["state"], a["box"],
            stamp, hist, reads)
        hi0 = np.asarray(hi).astype(np.int64)
        t0 = time.time()
        st, bx, sp, hi, rd = loop(
            kp, replicas, iters, B, False, True, True,
            jnp.asarray(iters, jnp.int32), st, bx, sp, hi, rd)
        st.term.block_until_ready()
        dt = time.time() - t0
        histw = np.asarray(hi).astype(np.int64) - hi0
        # latency unit = this arm's dispatch clock; cost scaled to the
        # UNinstrumented step_ms, as the headline latency phase does
        unit_ms = med[arm]["micro_step_ms"] * (2 if pipe else 1)
        out = {"unit": "pipeline steps" if pipe else "device steps",
               "unit_step_ms": round(unit_ms, 3),
               "instrumented_wall_s": round(dt, 3)}
        for name, q in (("p50", 0.50), ("p99", 0.99), ("p99.9", 0.999)):
            p = _pctile(histw, q)
            out[name + "_steps"] = p
            out[name + "_ms"] = (round(p * unit_ms, 3) if p is not None
                                 else None)
        return out

    lat = {arm: lat_arm(arm) for arm in ("serial", "pipelined")}
    s_ms, p_ms = med["serial"]["micro_step_ms"], med["pipelined"]["micro_step_ms"]
    emit({
        "metric": (f"pipelined vs serial step loop, {g} groups x "
                   f"{replicas} replicas, 16B"),
        "value": med["pipelined"]["writes_per_s"],
        "unit": "writes/s (pipelined arm)",
        "vs_baseline": round(med["pipelined"]["writes_per_s"]
                             / BASELINE_WPS, 4),
        "detail": {
            "platform": platform,
            "groups": g,
            "micro_steps_per_window": micro,
            "policy": "median-of-3 interleaved windows per arm",
            "serial": {**med["serial"], "windows": wins["serial"],
                       "commit_latency": lat["serial"]},
            "pipelined": {**med["pipelined"], "windows": wins["pipelined"],
                          "commit_latency": lat["pipelined"]},
            "micro_step_ms_ratio": round(p_ms / s_ms, 4) if s_ms else None,
        },
    })


def run_mesh_pipeline_ab() -> None:
    """BENCH_MESH_PIPELINE=1: A-B of the MESH dispatch path's two jit
    entries (engine/dispatch.py MeshDispatch) under the same host
    protocol the engine runs — serial depth-0 (non-donated
    jit_serve_step, blocking per-step staging) vs pipelined depth-1
    (jit_serve_step_donated: buffers donated to XLA, host staging
    built from one-step-stale retired copies) — at 1024 groups x 3
    replicas on a ('g','r') = (1, 3) host mesh.

    Interleaved windows A,B,A,B,... (median-of-3 per arm, the headline
    bench's policy); each arm reports wall, per-micro-step time and
    committed writes/s on leader rows.  Knobs: BENCH_MESH_GROUPS
    (default 1024), BENCH_MESH_STEPS (micro-steps per window, default
    120)."""
    import numpy as np

    import jax

    from dragonboat_tpu.bench_loop import bench_params
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.core.kstate import StepInput
    from dragonboat_tpu.parallel.ici import (
        jit_serve_step,
        jit_serve_step_donated,
        make_ici_cluster,
    )
    from jax.sharding import Mesh

    replicas = 3
    devs = jax.devices()
    if len(devs) < replicas:
        raise RuntimeError(
            f"mesh A/B needs {replicas} host devices, have {len(devs)} "
            "(main() forces xla_force_host_platform_device_count "
            "before jax loads — do not preimport jax)")
    groups = int(os.environ.get("BENCH_MESH_GROUPS", "1024"))
    micro = int(os.environ.get("BENCH_MESH_STEPS", "120"))
    platform = devs[0].platform
    kp = bench_params(replicas)
    B = kp.proposal_cap
    mesh = Mesh(np.array(devs[:replicas]).reshape(1, replicas),
                ("g", "r"))
    cluster, state0, box0 = make_ici_cluster(kp, mesh, groups)
    cut = cluster.shard(
        np.zeros((cluster.total_rows, kp.num_peers), bool))

    def host_input(role_h, proc_h, propose=True):
        # the engine's _InputBuilder shape: staged from HOST copies, so
        # nothing aliases the donated device buffers
        G = role_h.shape[0]
        lead = role_h == KP.LEADER
        z = lambda: np.zeros((G,), np.int32)  # noqa: E731
        return StepInput(
            prop_valid=np.broadcast_to(
                lead[:, None] & propose, (G, B)).copy(),
            prop_cc=np.zeros((G, B), bool),
            ri_valid=np.zeros((G,), bool),
            ri_low=z(), ri_high=z(), transfer_to=z(),
            tick=np.ones((G,), bool),
            quiesced=np.zeros((G,), bool),
            applied=proc_h)

    # election pump: tick until every group has one leader
    state, box = state0, box0
    for _ in range(40):
        role_h = np.asarray(state.role)
        if int((role_h == KP.LEADER).sum()) >= groups:
            break
        inp = cluster.shard(host_input(
            role_h, np.asarray(state.processed), propose=False))
        state, box, _ = jit_serve_step(
            kp, cluster, state, box, inp, cut)
    lead_rows = np.asarray(state.role) == KP.LEADER

    def committed(st):
        return int(np.asarray(st.committed)[lead_rows]
                   .astype(np.int64).sum())

    arms = {"serial": {"state": state, "box": box},
            "pipelined": {"state": state, "box": box}}

    def window(arm):
        a = arms[arm]
        c0 = committed(a["state"])
        t0 = time.time()
        if arm == "serial":
            # depth-0 protocol: stage from the CURRENT state (blocking
            # host fetch), dispatch the non-donated oracle
            for _ in range(micro):
                inp = cluster.shard(host_input(
                    np.asarray(a["state"].role),
                    np.asarray(a["state"].processed)))
                a["state"], a["box"], _ = jit_serve_step(
                    kp, cluster, a["state"], a["box"], inp, cut)
        else:
            # depth-1 protocol: stage from one-step-stale retired
            # copies (host build overlaps the in-flight device step),
            # pull the NEXT staging copies right before dispatch hands
            # the buffers to XLA.
            # np.array (a real copy), never np.asarray: on CPU that is
            # a zero-copy view of a buffer this arm donates away
            role_h = np.array(a["state"].role)
            proc_h = np.array(a["state"].processed)
            for _ in range(micro):
                inp = cluster.shard(host_input(role_h, proc_h))
                role_h = np.array(a["state"].role)
                proc_h = np.array(a["state"].processed)
                a["state"], a["box"], _ = \
                    jit_serve_step_donated(
                        kp, cluster, a["state"], a["box"], inp, cut)
        a["state"].term.block_until_ready()
        dt = time.time() - t0
        w = committed(a["state"]) - c0
        return {"wall_s": round(dt, 3),
                "micro_step_ms": round(dt / micro * 1e3, 3),
                "writes": w,
                "writes_per_s": round(w / dt)}

    for arm in arms:  # warm both executables outside the timed windows
        window(arm)
    wins = {"serial": [], "pipelined": []}
    for _ in range(3):
        for arm in ("serial", "pipelined"):
            wins[arm].append(window(arm))
    med = {arm: sorted(ws, key=lambda r: r["micro_step_ms"])[1]
           for arm, ws in wins.items()}
    speedup = (med["serial"]["micro_step_ms"]
               / max(med["pipelined"]["micro_step_ms"], 1e-9))
    emit({
        "metric": ("mesh dispatch serial vs pipelined (donated), "
                   f"{groups} groups x {replicas} replicas"),
        "value": round(speedup, 3),
        "unit": "x serial/pipelined micro-step time",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "mesh": f"('g','r') = (1, {replicas})",
            "groups": groups,
            "micro_steps_per_window": micro,
            "serial": med["serial"],
            "pipelined": med["pipelined"],
            "windows": wins,
            "policy": "median-of-3 interleaved windows per arm",
        },
    })


def run_fabric_resident_ab() -> None:
    """BENCH_FABRIC_RESIDENT=1: the round-17 tentpole's closing number
    — co-located consensus traffic over the interconnect vs through the
    host hub, on the SERVING loop (parallel/ici.py jit_serve_step).

    Arm A (resident) serves with an all-open per-link cut mask:
    messages ride the in-step collective and the host stages nothing
    but StepInput.  Arm B (hub) serves with EVERY link cut — the step
    emits but exchanges nothing on the mesh; its out-lanes are pulled
    to the host, staged through core/router.route (the hub fallback's
    slot addressing) and re-uploaded as the next inbox, which is
    exactly what every co-located message paid before round 17.  Arms
    interleave A,B,A,B,... (median-of-3 per arm); the resident entry
    runs under a CompileTracker wrapper and must show compiles=1 /
    retraces=0 across pump + warm + all windows.  Knobs:
    BENCH_FABRIC_RESIDENT_GROUPS (default 1024),
    BENCH_FABRIC_RESIDENT_STEPS (micro-steps per window, default
    120)."""
    import numpy as np

    import jax

    from dragonboat_tpu import capacity
    from dragonboat_tpu.bench_loop import bench_params
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.core.router import route
    from dragonboat_tpu.parallel.ici import (
        jit_serve_step,
        make_ici_cluster,
        self_driving_input,
    )
    from jax.sharding import Mesh

    replicas = 3
    devs = jax.devices()
    if len(devs) < replicas:
        raise RuntimeError(
            f"fabric A/B needs {replicas} host devices, have {len(devs)} "
            "(main() forces xla_force_host_platform_device_count "
            "before jax loads — do not preimport jax)")
    groups = int(os.environ.get("BENCH_FABRIC_RESIDENT_GROUPS", "1024"))
    micro = int(os.environ.get("BENCH_FABRIC_RESIDENT_STEPS", "120"))
    platform = devs[0].platform
    kp = bench_params(replicas)
    mesh = Mesh(np.array(devs[:replicas]).reshape(1, replicas),
                ("g", "r"))
    cluster, state, box = make_ici_cluster(kp, mesh, groups)
    # g_size=1 layout: router row n*R+ir lives at mesh row ir*groups+n
    perm = np.empty(groups * replicas, np.int64)
    for n in range(groups):
        for ir in range(replicas):
            perm[n * replicas + ir] = ir * groups + n
    iperm = np.argsort(perm)
    total = cluster.total_rows
    cut_open = cluster.shard(
        np.zeros((total, kp.num_peers), bool))
    cut_all = cluster.shard(
        np.ones((total, kp.num_peers), bool))

    # prime the startup-only signature: the very first call sees the
    # fresh device_put arrays from make_ici_cluster, whose committed
    # layouts differ from every later jit-output step — a one-time
    # second lowering that exists at any engine's startup, not a
    # retrace the serving loop can hit
    inp = self_driving_input(kp, state, propose=False)
    state, box, _ = jit_serve_step(kp, cluster, state, box, inp,
                                   cut_open)

    # the resident entry under compile telemetry: the acceptance gate
    # is ONE compile (the steady-state signature) and ZERO retraces
    # across pump + warm + every window — cut is a traced argument, so
    # flipping the mask must not re-lower the executable
    tracker = capacity.CompileTracker()
    serve_resident = tracker.wrap("fabric_resident_serve",
                                  jit_serve_step)

    # election pump (resident path) until every group has one leader
    for _ in range(40):
        if int((np.asarray(state.role) == KP.LEADER).sum()) >= groups:
            break
        inp = self_driving_input(kp, state, propose=False)
        state, box, _ = serve_resident(
            kp, cluster, state, box, inp, cut_open)
    lead_rows = np.asarray(state.role) == KP.LEADER

    route_jit = jax.jit(route, static_argnums=(0, 1))
    pull = lambda t: jax.tree.map(  # noqa: E731
        lambda x: np.array(x), t)
    repermute = lambda t, p: jax.tree.map(  # noqa: E731
        lambda x: x[p], t)

    def committed(st):
        return int(np.asarray(st.committed)[lead_rows]
                   .astype(np.int64).sum())

    arms = {"resident": {"state": state, "box": box},
            "hub": {"state": state, "box": box}}

    def window(arm):
        a = arms[arm]
        c0 = committed(a["state"])
        t0 = time.time()
        for _ in range(micro):
            inp = self_driving_input(kp, a["state"], propose=True)
            if arm == "resident":
                a["state"], a["box"], _ = serve_resident(
                    kp, cluster, a["state"], a["box"], inp, cut_open)
            else:
                # hub delivery: the mesh exchanges nothing (every link
                # cut); out-lanes round-trip the host through route()
                a["state"], _, outgoing = jit_serve_step(
                    kp, cluster, a["state"], a["box"], inp, cut_all)
                hub_box = route_jit(
                    kp, replicas, repermute(pull(outgoing), perm))
                a["box"] = cluster.shard(repermute(pull(hub_box), iperm))
        a["state"].term.block_until_ready()
        dt = time.time() - t0
        w = committed(a["state"]) - c0
        return {"wall_s": round(dt, 3),
                "micro_step_ms": round(dt / micro * 1e3, 3),
                "writes": w,
                "writes_per_s": round(w / dt)}

    for arm in arms:  # warm both executables outside the timed windows
        window(arm)
    wins = {"resident": [], "hub": []}
    for _ in range(3):
        for arm in ("resident", "hub"):
            wins[arm].append(window(arm))
    med = {arm: sorted(ws, key=lambda r: r["micro_step_ms"])[1]
           for arm, ws in wins.items()}
    speedup = (med["hub"]["micro_step_ms"]
               / max(med["resident"]["micro_step_ms"], 1e-9))
    ct = serve_resident.stats()
    if ct["compiles"] != 1 or ct["retraces"] != 0:
        raise RuntimeError(
            f"resident serve entry re-lowered: {ct} (cut-mask flips or "
            "input staging changed the traced signature)")
    emit({
        "metric": ("device-resident fabric vs host-hub delivery, "
                   f"{groups} groups x {replicas} replicas, "
                   "serving loop"),
        "value": round(speedup, 3),
        "unit": "x hub/resident micro-step time",
        "vs_baseline": 0.0,
        "detail": {
            "platform": platform,
            "mesh": f"('g','r') = (1, {replicas})",
            "groups": groups,
            "micro_steps_per_window": micro,
            "resident": med["resident"],
            "hub": med["hub"],
            "windows": wins,
            "resident_compile": {"calls": ct["calls"],
                                 "compiles": ct["compiles"],
                                 "retraces": ct["retraces"]},
            "policy": "median-of-3 interleaved windows per arm",
        },
    })


def _three_host_devices() -> None:
    """The two mesh modes want one device per replica slot.  The flag
    below only shapes the CPU backend (and must be set before anything
    imports jax); on any other backend too few devices raises from
    engine/mesh_engine.py."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=3"
        ).strip()


def main() -> None:
    """Run the one mode the environment selects; a failure propagates
    (traceback, non-zero exit) instead of printing a value-0 record."""
    modes = (
        ("BENCH_FABRIC_RESIDENT", run_fabric_resident_ab),
        ("BENCH_MESH_PIPELINE", run_mesh_pipeline_ab),
        ("BENCH_ELASTIC", run_elastic_ab),
        ("BENCH_TRANSFER", run_transfer_ab),
        ("BENCH_SAFETY", run_safety_ab),
        ("BENCH_CAPACITY", run_capacity_ab),
        ("BENCH_FABRIC", run_fabric_ab),
        ("BENCH_TRACE", run_trace_ab),
        ("BENCH_PIPELINE", run_pipeline_ab),
        ("BENCH_TELEMETRY", run_telemetry_ab),
        ("BENCH_HEALTH", run_health_ab),
        ("BENCH_SERVE", run_serve_bench),
    )
    for knob, run in modes:
        if os.environ.get(knob) == "1":
            if knob in ("BENCH_FABRIC_RESIDENT", "BENCH_MESH_PIPELINE"):
                _three_host_devices()
            run()
            return
    run_bench()


if __name__ == "__main__":
    main()
