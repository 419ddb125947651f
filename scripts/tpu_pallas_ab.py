#!/usr/bin/env python
"""On-device A/B of the rsm-apply kernels: the pallas kernel is held
bit-exact to the XLA path in interpret mode; its reason to exist is a
compiled device number, which has not been taken yet (PERF.md).

Measures, at the bench shape (sm_params, direct-mapped table):

  1. the bare apply kernels on a synthetic [G, AB] committed window —
     sequential probing scan vs one-pass range apply vs the pallas
     block kernel (VMEM-resident table across the window);
  2. the full device-SM step loop (run_steps_sm) with the XLA range
     apply vs the pallas apply.

Round 17 adds ``kind=fabric_ab`` rungs for the device-resident fabric:
the serving loop with hub delivery vs the in-step collective exchange
(parallel/ici.py per-link cut mask open vs all-cut + host route), and
the two hot gather shapes on that path — inbox lane staging and the
quorum match select — as pallas VMEM block kernels vs their XLA
lowerings (parallel/fabric_pallas.py).

Prints one JSON line per rung family (kind=pallas_ab / pipeline_ab /
fabric_ab) to stdout.  On the CPU backend (PALLAS_AB_FORCE_CPU=1 also asks
for 8 virtual devices) pallas runs in interpret mode — the relative
number means nothing there, the plumbing check does.

Usage: python scripts/tpu_pallas_ab.py [groups]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the fabric serve rung needs one host device per replica slot; must be
# set before jax loads (harmless on real TPU: flag only affects CPU)
if os.environ.get("PALLAS_AB_FORCE_CPU") == "1":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp

from dragonboat_tpu import hostenv

# the one persistent-cache helper (hostenv), vetoable via
# DRAGONBOAT_TPU_COMPILE_CACHE=0
_CACHE_ARTIFACTS = hostenv.cache_entry_count()
_CACHE_DIR = hostenv.enable_compile_cache()
print("PALLAS_AB compile_cache: "
      + ("vetoed (DRAGONBOAT_TPU_COMPILE_CACHE=0)" if _CACHE_DIR is None
         else f"{'warm' if _CACHE_ARTIFACTS else 'cold'} "
              f"({_CACHE_ARTIFACTS} artifact(s)) dir={_CACHE_DIR}"),
      flush=True)


def bare_apply_ab(G: int, AB: int, iters: int = 50) -> dict:
    """Apply kernels alone on synthetic windows (no raft step around
    them): per-call ms for scan/range/pallas at [G, AB]."""
    import numpy as np

    from dragonboat_tpu.rsm.device_kv import DeviceKV
    from dragonboat_tpu.rsm.device_kv_pallas import apply_kernel_pallas

    kv = DeviceKV(table_cap=1024, hash_keys=False)
    T = kv.table_cap
    rng = np.random.default_rng(3)
    first = jnp.asarray(rng.integers(0, T, G), jnp.int32)
    vals = jnp.asarray(rng.integers(1, 1 << 20, (G, AB)), jnp.int32)
    valid = jnp.asarray(rng.random((G, AB)) < 0.9)
    idx = first[:, None] + jnp.arange(AB, dtype=jnp.int32)[None, :]
    keys = idx & (T - 1)
    cmds = jnp.stack([keys, vals], axis=-1)

    out = {}

    def timed(tag, fn):
        st = kv.init_state(G)
        st, _ = fn(st)                      # compile
        jax.block_until_ready(st["vals"])
        t0 = time.time()
        for _ in range(iters):
            st, _ = fn(st)
        jax.block_until_ready(st["vals"])
        out[tag + "_ms"] = round((time.time() - t0) / iters * 1e3, 3)

    timed("apply_scan", lambda st: kv.apply_kernel(st, cmds, valid))
    timed("apply_range",
          lambda st: kv.apply_kernel_range(st, first & (T - 1), vals, valid))
    try:
        timed("apply_pallas",
              lambda st: apply_kernel_pallas(kv, st, cmds, valid))
    except Exception as e:
        out["apply_pallas_error"] = str(e)[-200:]
    return out


def step_loop_ab(G: int, steps: int) -> dict:
    """run_steps_sm with the range apply vs the pallas apply — the
    number that decides which one full_step_sm ships."""
    from dragonboat_tpu.bench_loop import (
        elect_all,
        make_cluster,
        make_device_sm,
        run_steps_sm,
        sm_params,
    )

    kp = sm_params(3)
    out = {}
    for tag, use_pallas in (("sm_range", False), ("sm_pallas", True)):
        try:
            state, box = elect_all(kp, 3, make_cluster(kp, G, 3))
            kv, kv_state = make_device_sm(G, 3, use_pallas=use_pallas)
            state, box, kv_state, _ = run_steps_sm(
                kp, 3, kv, 4, True, True, state, box, kv_state)  # compile
            jax.block_until_ready(state.term)
            t0 = time.time()
            state, box, kv_state, _ = run_steps_sm(
                kp, 3, kv, steps, True, True, state, box, kv_state)
            jax.block_until_ready(state.term)
            out[tag + "_step_ms"] = round(
                (time.time() - t0) / steps * 1e3, 3)
        except Exception as e:
            out[tag + "_error"] = str(e)[-200:]
    return out


def pipeline_loop_ab(G: int, pipe_iters: int) -> dict:
    """Serial run_steps vs the fused depth-1 run_steps_pipelined at
    matched micro-step counts (serial iters = 2 * pipe_iters) — the
    device-side cost of the pipelined loop body (PR 6 tentpole)."""
    from dragonboat_tpu.bench_loop import (
        bench_params,
        elect_all,
        make_cluster,
        run_steps,
        run_steps_pipelined,
    )

    kp = bench_params(3)
    out = {}
    for tag, loop, iters in (("serial", run_steps, 2 * pipe_iters),
                             ("pipelined", run_steps_pipelined, pipe_iters)):
        try:
            state, box = elect_all(kp, 3, make_cluster(kp, G, 3))
            # warm the EXACT executable (iters is a static arg)
            state, box = loop(kp, 3, iters, True, True, state, box)
            jax.block_until_ready(state.term)
            t0 = time.time()
            state, box = loop(kp, 3, iters, True, True, state, box)
            jax.block_until_ready(state.term)
            micro = iters * (2 if tag == "pipelined" else 1)
            out[tag + "_step_ms"] = round(
                (time.time() - t0) / micro * 1e3, 3)
        except Exception as e:
            out[tag + "_error"] = str(e)[-200:]
    return out


def gather_donated_ab(G: int, iters: int = 30) -> dict:
    """Single-dispatch step vs step_donated at the bench shape: the hot
    gather paths (log window fetch, inbox route) re-lowered with buffer
    donation, which lets XLA write outputs over the dead input SoA
    arrays instead of allocating per step.  Both arms pay the same
    host-side empty-inbox/input staging, as the engine does."""
    from dragonboat_tpu.bench_loop import bench_params, elect_all, make_cluster
    from dragonboat_tpu.core.kernel import step, step_donated
    from dragonboat_tpu.core.kstate import empty_inbox, empty_input

    kp = bench_params(3)
    out = {}
    for tag, fn in (("step", step), ("step_donated", step_donated)):
        try:
            state, _ = elect_all(kp, 3, make_cluster(kp, G, 3))
            n = state.term.shape[0]
            state, _ = fn(kp, state, empty_inbox(kp, n),
                          empty_input(kp, n))           # compile
            jax.block_until_ready(state.term)
            t0 = time.time()
            for _ in range(iters):
                state, _ = fn(kp, state, empty_inbox(kp, n),
                              empty_input(kp, n))
            jax.block_until_ready(state.term)
            out[tag + "_ms"] = round((time.time() - t0) / iters * 1e3, 3)
        except Exception as e:
            out[tag + "_error"] = str(e)[-200:]
    return out


def fabric_serve_ab(groups: int, micro: int = 40,
                    replicas: int = 2) -> dict:
    """Hub delivery vs device-resident exchange on the SERVING loop
    (round 17 tentpole): both arms run jit_serve_step; the resident arm
    serves with an all-open per-link cut mask (messages ride the
    in-step collective), the hub arm with EVERY link cut — its
    out-lanes are pulled to the host, staged back through
    core/router.route (the hub fallback's addressing) and re-uploaded
    as the next inbox.  Per-micro-step ms for each arm; the delta is
    the host hub's tax on co-located links."""
    import numpy as np

    from jax.sharding import Mesh

    from dragonboat_tpu.bench_loop import bench_params
    from dragonboat_tpu.core import params as KP
    from dragonboat_tpu.core.router import route
    from dragonboat_tpu.parallel.ici import (
        jit_serve_step,
        make_ici_cluster,
        self_driving_input,
    )

    devs = jax.devices()
    if len(devs) < replicas:
        return {"serve_error":
                f"needs {replicas} devices, have {len(devs)}"}
    kp = bench_params(replicas)
    mesh = Mesh(np.array(devs[:replicas]).reshape(1, replicas),
                ("g", "r"))
    cluster, state, box = make_ici_cluster(kp, mesh, groups)
    n_local = groups  # g_size=1: mesh row ir*n_local + n <-> router n*R+ir
    perm = np.empty(groups * replicas, np.int64)
    for n in range(groups):
        for ir in range(replicas):
            perm[n * replicas + ir] = ir * n_local + n
    iperm = np.argsort(perm)
    total = cluster.total_rows
    cut_open = cluster.shard(np.zeros((total, kp.num_peers), bool))
    cut_all = cluster.shard(np.ones((total, kp.num_peers), bool))

    # election pump (resident path) until every group has a leader
    for _ in range(40):
        if int((np.asarray(state.role) == KP.LEADER).sum()) >= groups:
            break
        inp = self_driving_input(kp, state, propose=False)
        state, box, _ = jit_serve_step(
            kp, cluster, state, box, inp, cut_open)

    route_jit = jax.jit(route, static_argnums=(0, 1))
    pull = lambda t: jax.tree.map(lambda x: np.array(x), t)  # noqa: E731
    repermute = lambda t, p: jax.tree.map(  # noqa: E731
        lambda x: x[p], t)

    arms = {"resident": (state, box), "hub": (state, box)}
    out = {}
    for tag in arms:
        st, bx = arms[tag]
        for warm in (True, False):
            t0 = time.time()
            for _ in range(micro):
                inp = self_driving_input(kp, st, propose=True)
                if tag == "resident":
                    st, bx, _ = jit_serve_step(
                        kp, cluster, st, bx, inp, cut_open)
                else:
                    st, _, outgoing = jit_serve_step(
                        kp, cluster, st, bx, inp, cut_all)
                    hub_box = route_jit(
                        kp, replicas, repermute(pull(outgoing), perm))
                    bx = cluster.shard(repermute(pull(hub_box), iperm))
            jax.block_until_ready(st.term)
            if warm:  # first window compiles; only the second is timed
                continue
            out[tag + "_step_ms"] = round(
                (time.time() - t0) / micro * 1e3, 3)
    if "resident_step_ms" in out and "hub_step_ms" in out:
        out["hub_over_resident_x"] = round(
            out["hub_step_ms"] / max(out["resident_step_ms"], 1e-9), 3)
    return out


def fabric_gather_ab(G: int, iters: int = 50) -> dict:
    """The serving path's two hot gather shapes as pallas VMEM block
    kernels vs their XLA lowerings (parallel/fabric_pallas.py): inbox
    lane staging (batched gather) and the quorum match order statistic
    (sort + gather).  Asserts bitwise agreement on the way."""
    import numpy as np

    from dragonboat_tpu.parallel.fabric_pallas import (
        gather_lanes_pallas,
        gather_lanes_xla,
        quorum_match_pallas,
        quorum_match_xla,
    )

    K, R = 32, 8
    rng = np.random.default_rng(11)
    vals = jnp.asarray(rng.integers(0, 1 << 20, (G, K)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, K, (G, K)), jnp.int32)
    match = jnp.asarray(rng.integers(0, 1 << 16, (G, R)), jnp.int32)
    voting = jnp.asarray(rng.random((G, R)) < 0.9)
    q = jnp.asarray(rng.integers(1, R // 2 + 2, G), jnp.int32)
    interpret = jax.default_backend() == "cpu"
    out = {"gather_interpret": interpret}

    def timed(tag, fn, *a):
        r = fn(*a)                                  # compile
        jax.block_until_ready(r)
        t0 = time.time()
        for _ in range(iters):
            r = fn(*a)
        jax.block_until_ready(r)
        out[tag + "_ms"] = round((time.time() - t0) / iters * 1e3, 3)
        return r

    ref = timed("inbox_gather_xla", jax.jit(gather_lanes_xla), vals, idx)
    try:
        got = timed("inbox_gather_pallas",
                    gather_lanes_pallas, vals, idx, interpret)
        out["inbox_gather_bitwise"] = bool(jnp.array_equal(ref, got))
    except Exception as e:
        out["inbox_gather_pallas_error"] = str(e)[-200:]
    ref = timed("quorum_match_xla",
                jax.jit(quorum_match_xla), match, voting, q)
    try:
        got = timed("quorum_match_pallas",
                    quorum_match_pallas, match, voting, q, interpret)
        out["quorum_match_bitwise"] = bool(jnp.array_equal(ref, got))
    except Exception as e:
        out["quorum_match_pallas_error"] = str(e)[-200:]
    return out


def main() -> None:
    g = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() \
        else 1024
    plat = jax.devices()[0].platform
    if plat == "cpu" and os.environ.get("PALLAS_AB_FORCE_CPU") != "1":
        print(json.dumps({"skipped": "cpu backend (interpret-mode pallas "
                                     "measures nothing); set "
                                     "PALLAS_AB_FORCE_CPU=1 to self-test"}))
        return
    rec = {"ts": time.time(), "kind": "pallas_ab", "platform": plat,
           "groups": g}
    from dragonboat_tpu.bench_loop import sm_params

    AB = sm_params(3).apply_batch
    print(f"backend: {plat}  groups: {g}  AB: {AB}", flush=True)
    rec.update(bare_apply_ab(g * 3, AB))
    print("bare: " + json.dumps(rec), flush=True)
    rec.update(step_loop_ab(g, steps=max(10, min(50, 100_000 // g))))
    # pipelined-loop + donated-dispatch rungs (PR 6) as their own
    # kind-tagged line so downstream greps select by rung family
    pipe = {"ts": time.time(), "kind": "pipeline_ab", "platform": plat,
            "groups": g}
    pipe.update(pipeline_loop_ab(g, pipe_iters=max(5, min(25, 50_000 // g))))
    pipe.update(gather_donated_ab(g))
    # device-resident fabric rungs (round 17) as their own kind line
    fab = {"ts": time.time(), "kind": "fabric_ab", "platform": plat,
           "groups": g}
    fab.update(fabric_serve_ab(min(g, 1024),
                               micro=max(5, min(40, 20_000 // g))))
    fab.update(fabric_gather_ab(g))
    print(json.dumps(rec), flush=True)
    print(json.dumps(pipe), flush=True)
    print(json.dumps(fab), flush=True)


if __name__ == "__main__":
    main()
