"""Self-driving device loop for benchmarking and the graft entry.

``full_step`` is the production-shaped training-step analog: one fused
cluster step (raft kernel + device message routing) plus the feedback the
host engine would provide — proposals enqueued on leaders, the RSM applied
cursor trailing the processed cursor, and the logical clock ticking.  It
runs entirely on device so ``lax.fori_loop`` can iterate it with zero host
dispatch, which is how the bench measures sustained writes/sec
(BASELINE config #2: shards × 3 replicas, 16B writes, vmapped step loop;
payloads live in the host mirror / device RSM value lanes, not in the raft
ring, mirroring the reference's in-memory KV benchmark shape).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dragonboat_tpu.core import params as KP
from dragonboat_tpu.core.kernel import step
from dragonboat_tpu.core.kstate import (
    Inbox,
    ShardState,
    StepInput,
    empty_inbox,
    empty_input,
    init_state,
)
from dragonboat_tpu.core.router import route

I32 = jnp.int32


def bench_params(replicas: int = 3,
                 platform: str | None = None) -> KP.KernelParams:
    """Measured sweet spot (PERF.md): with the dispatch-by-type inbox
    (family-specialized handler bodies) the fixed scan cost is small
    enough that proposal/replication width 32 is the knee — 1.08M
    writes/s on one CPU core at 1024 groups with this exact config;
    width 48 regresses (bigger ring + conflict scans outweigh the batch
    gain).

    ``platform`` (default: the live backend) picks the ring-read
    lowering: one-hot selects on device (batched gathers serialize over
    [G] on TPU), dynamic indexing on CPU (the gather is a plain load
    there and one-hot costs ~3.5x)."""
    if platform is None:
        import jax

        platform = jax.default_backend()
    return KP.KernelParams(
        onehot_reads=(platform != "cpu"),
        num_peers=replicas,
        # 128 comfortably holds the uncompacted window (overhead 16 +
        # apply lag + the in-flight batch ≈ 96) and halves ring traffic
        # vs 256
        log_cap=128,
        inbox_cap=5 * (replicas - 1),
        msg_entries=32,
        proposal_cap=32,
        readindex_cap=4,
        apply_batch=64,
        # keep the compaction window + in-flight batch well under log_cap:
        # a large overhead pushes the ring-room gate into the proposal edge
        # and throttles accepted writes/step (measured: 64 -> 23.7/step,
        # 32 -> 23.7, 16 -> 28.0 at CAP=128; CAP=256 reaches 32/step but
        # the doubled ring traffic nets out slower)
        compaction_overhead=16,
    )


def make_cluster(kp: KP.KernelParams, num_groups: int, replicas: int = 3,
                 election: int = 10) -> ShardState:
    import numpy as np

    G = num_groups * replicas
    rids = np.tile(np.arange(1, replicas + 1, dtype=np.int32), num_groups)
    pids = np.arange(1, replicas + 1, dtype=np.int32)
    return init_state(kp, G, rids, pids, election_timeout=election)


def _self_input(kp: KP.KernelParams, state: ShardState, tick, propose,
                write_width: int | None, do_reads: bool, now) -> StepInput:
    """The self-driving feedback input: auto-propose on leaders (first
    ``write_width`` lanes, or all), optional one batched ReadIndex per
    leader, instant-apply RSM cursor, logical clock tick.  ONE builder so
    the instrumented and headline loops cannot drift apart."""
    G, B = state.term.shape[0], kp.proposal_cap
    is_leader = state.role == KP.LEADER
    pv = jnp.broadcast_to(is_leader[:, None], (G, B)) & jnp.asarray(
        propose, bool)
    if write_width is not None and write_width < B:
        pv = pv & (jnp.arange(B, dtype=jnp.int32) < write_width)[None, :]
    # inline payloads: lane j proposes value (last + 1 + j) — the entry's
    # own index, so any replica can verify lv[slot(i)] == i for committed i
    pval = (state.last[:, None] + 1 + jnp.arange(B, dtype=jnp.int32)[None, :])
    ri = (is_leader & jnp.asarray(do_reads, bool)
          & jnp.asarray(propose, bool))
    ctx = jnp.broadcast_to(jnp.asarray(now, jnp.int32) & 0x7FFFFFFF, (G,))
    return StepInput(
        prop_valid=pv,
        prop_cc=jnp.zeros((G, B), bool),
        ri_valid=ri,
        ri_low=ctx,
        ri_high=ctx,
        transfer_to=jnp.zeros((G,), jnp.int32),
        tick=jnp.broadcast_to(jnp.asarray(tick, bool), (G,)),
        quiesced=jnp.zeros((G,), bool),
        applied=state.processed,  # instant-apply RSM feedback
        prop_val=pval,
    )


def full_step(kp: KP.KernelParams, replicas: int, state: ShardState,
              box: Inbox, tick, propose):
    """One self-driving step: auto-propose on leaders, sync applied, tick.

    ``tick``/``propose`` are traced booleans so one compiled executable
    covers the elect, settle and load phases (compiles are minutes-scale
    on TPU; variants would triple that)."""
    inp = _self_input(kp, state, tick, propose, None, False, 0)
    state, out = step(kp, state, box, inp)
    nxt = route(kp, replicas, out)
    return state, nxt, out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def run_steps(kp: KP.KernelParams, replicas: int, iters: int,
              tick, propose, state: ShardState, box: Inbox):
    """iters self-driving steps under one jit — the bench inner loop."""
    tick = jnp.asarray(tick, bool)
    propose = jnp.asarray(propose, bool)

    def body(_, carry):
        st, bx = carry
        st, bx, _ = full_step(kp, replicas, st, bx, tick, propose)
        return st, bx

    return jax.lax.fori_loop(0, iters, body, (state, box))


# ---------------------------------------------------------------------------
# pipelined (double-pumped) loops — PipelineConfig depth 1's device shape.
#
# One PIPELINE step fuses two protocol micro-steps (step ∘ route, twice)
# under a single fori_loop body, so the host boundary — and the
# instrumentation clock `now` — advances once per fused pair.  Raft's
# propose → replicate → ack → commit chain spans 2 micro-steps; fused,
# it retires inside ONE pipeline step, which is exactly the "commit p50
# ≤ 1 tick" the roadmap targets.  Everything in the carry is i32/bool
# (threefry included), so fusing the pair is bitwise-neutral:
# run_steps_pipelined(n) must equal run_steps(2n) leaf-for-leaf — the
# depth-0 serial loop stays the differential oracle
# (tests/test_pipeline_differential.py).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def run_steps_pipelined(kp: KP.KernelParams, replicas: int, iters: int,
                        tick, propose, state: ShardState, box: Inbox):
    """iters pipeline steps, each two fused self-driving micro-steps —
    bitwise ≡ ``run_steps(kp, replicas, 2 * iters, ...)``."""
    tick = jnp.asarray(tick, bool)
    propose = jnp.asarray(propose, bool)

    def body(_, carry):
        st, bx = carry
        st, bx, _ = full_step(kp, replicas, st, bx, tick, propose)
        st, bx, _ = full_step(kp, replicas, st, bx, tick, propose)
        return st, bx

    return jax.lax.fori_loop(0, iters, body, (state, box))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def run_steps_storm_pipelined(kp: KP.KernelParams, replicas: int, iters: int,
                              drop_p, seed, state: ShardState, box: Inbox):
    """Pipelined election storm: the fold_in counter advances per
    MICRO-step (2i, 2i+1) so the Bernoulli drop masks replay the serial
    loop's RNG stream exactly — bitwise ≡ ``run_steps_storm(2 * iters)``."""
    key0 = jax.random.PRNGKey(seed)
    drop_p = jnp.asarray(drop_p, jnp.float32)

    def body(i, carry):
        st, bx = carry
        st, bx, _ = full_step(kp, replicas, st, bx, True, False)
        bx = _drop_box(bx, jax.random.fold_in(key0, 2 * i), drop_p)
        st, bx, _ = full_step(kp, replicas, st, bx, True, False)
        bx = _drop_box(bx, jax.random.fold_in(key0, 2 * i + 1), drop_p)
        return st, bx

    return jax.lax.fori_loop(0, iters, body, (state, box))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def run_steps_mixed_pipelined(kp: KP.KernelParams, replicas: int, iters: int,
                              write_width: int, now0, state: ShardState,
                              box: Inbox, reads):
    """Pipelined 9:1 mix: the ReadIndex ctx clock advances per micro-step
    (now0 + 2i, now0 + 2i + 1) — bitwise ≡ ``run_steps_mixed(2 * iters)``."""

    def body(i, carry):
        st, bx, rd = carry
        for j in (0, 1):
            inp = _self_input(kp, st, True, True, write_width, True,
                              now0 + 2 * i + j)
            st, out = step(kp, st, bx, inp)
            bx = route(kp, replicas, out)
            rd = rd + out.rtr_valid.sum(dtype=jnp.int32)
        return st, bx, rd

    return jax.lax.fori_loop(0, iters, body, (state, box, reads))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def run_steps_mixed(kp: KP.KernelParams, replicas: int, iters: int,
                    write_width: int, now0, state: ShardState, box: Inbox,
                    reads):
    """The mixed read/write loop WITHOUT latency instrumentation: writes
    narrowed to ``write_width`` lanes, one batched ReadIndex ctx per
    leader per step, and the only extra carry is the completed-ctx
    counter (an [RI]-bool sum — nothing like the stamp ring's one-hot
    writes).  Exists because measuring the 9:1 mix on the instrumented
    loop conflated ReadIndex cost with latency-capture cost (~2x).
    Deliberately a separate loop rather than an ``instrument`` flag on
    ``run_steps_lat``: the [G, log_cap] stamp ring would still ride the
    fori_loop carry, and whether XLA fully elides an untouched carry is
    exactly the kind of backend detail a benchmark must not bet on."""

    def body(i, carry):
        st, bx, rd = carry
        inp = _self_input(kp, st, True, True, write_width, True, now0 + i)
        st, out = step(kp, st, bx, inp)
        bx = route(kp, replicas, out)
        rd = rd + out.rtr_valid.sum(dtype=jnp.int32)
        return st, bx, rd

    return jax.lax.fori_loop(0, iters, body, (state, box, reads))


# ---------------------------------------------------------------------------
# device-SM pipeline: the full propose -> replicate -> commit -> APPLY loop
# with the rsm-apply kernel (rsm/device_kv.py) fused into the step
# ---------------------------------------------------------------------------


def sm_params(replicas: int = 3) -> KP.KernelParams:
    """bench_params with the inline-payload lanes enabled (the lv ring +
    ent_val routing the device-SM data path rides)."""
    import dataclasses

    return dataclasses.replace(bench_params(replicas), inline_payloads=True)


def make_device_sm(num_groups: int, replicas: int = 3,
                   table_cap: int = 1024, use_pallas: bool = False):
    """(DeviceKV, kv_state) sized for the bench cluster.  Direct-mapped:
    the range apply writes key = index mod table_cap, so every slot is
    that key's private home and no write can ever be rejected."""
    from dragonboat_tpu.rsm.device_kv import DeviceKV

    G = num_groups * replicas
    kv = DeviceKV(table_cap=table_cap, hash_keys=False,
                  use_pallas=use_pallas)
    return kv, kv.init_state(G)


def full_step_sm(kp: KP.KernelParams, replicas: int, kv, state: ShardState,
                 box: Inbox, kv_state, tick, propose):
    """``full_step`` plus the device RSM: payloads ride the lv ring (the
    inline payload slot — proposals stamp it, replicate messages carry
    it, so FOLLOWERS hold real values too), and the apply window the
    kernel releases is applied to the DeviceKV by the fused rsm-apply
    kernel on every replica.  This is the north star's full data path —
    the reference benches apply to an in-memory KV on the host
    (kvtest.go); here the apply itself is device work."""
    assert kp.inline_payloads, "device-SM path needs sm_params()"
    CAP, AB = kp.log_cap, kp.apply_batch
    state, box2, out = full_step(kp, replicas, state, box, tick, propose)
    # apply the released window through the rsm-apply kernel, reading
    # payloads from the replicated lv ring (valid on leaders AND followers)
    idx = out.apply_first[:, None] + jnp.arange(AB, dtype=jnp.int32)[None, :]
    valid = idx <= out.apply_last[:, None]                   # [G, AB]
    vals = jnp.take_along_axis(state.lv, idx & (CAP - 1), axis=1)
    if kv.use_pallas:
        # fused pallas apply: the table block stays in VMEM across the
        # window (bit-identical to both XLA forms —
        # tests/test_device_kv_pallas.py)
        from dragonboat_tpu.rsm.device_kv_pallas import apply_kernel_pallas

        key_space = (kv.table_cap // 2 if kv.hash_keys else kv.table_cap)
        keys = idx & (key_space - 1)
        cmds = jnp.stack([keys, vals], axis=-1)              # [G, AB, 2]
        kv_state, (_results, ok) = apply_kernel_pallas(
            kv, kv_state, cmds, valid)
    elif not kv.hash_keys:
        # raft applies a CONTIGUOUS window: one-pass range apply, no
        # serial B-iteration scan (keys = index mod table_cap)
        first_key = out.apply_first & (kv.table_cap - 1)
        kv_state, (_results, ok) = kv.apply_kernel_range(
            kv_state, first_key, vals, valid)
    else:
        # hashed tables: probing scan; half the table as key space keeps
        # load <= 0.5 so probe windows don't fill and reject
        keys = idx & (kv.table_cap // 2 - 1)
        cmds = jnp.stack([keys, vals], axis=-1)              # [G, AB, 2]
        kv_state, (_results, ok) = kv.apply_kernel(kv_state, cmds, valid)
    # a rejected committed write must be surfaced, not swallowed —
    # the bench reports the count
    n_rejected = jnp.sum(~ok & valid)
    return state, box2, kv_state, n_rejected, out


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def run_steps_mixed_sm(kp: KP.KernelParams, replicas: int, kv, iters: int,
                       write_width: int, now0, state: ShardState, box: Inbox,
                       kv_state, reads, acc, rejects):
    """The 9:1 mix with reads SERVED, not just permitted: the device-SM
    write pipeline (lv ring -> range apply) plus one batched ReadIndex
    ctx per leader per step, and for every confirmed ctx a window of
    ``9 * write_width`` lookups below the ctx index is executed against
    the device-resident table.  ``reads`` counts served CTXs — multiply
    by RB host-side: an on-device running sum of lookups would overflow
    int32 within one window at 100k groups.  ``acc`` folds the read
    VALUES into the carry so the lookups are live computation XLA cannot
    elide; ``rejects`` accumulates across calls like the other carries.  The read pass is slot-scan shaped ([G, T] compare/select —
    each table slot tests whether it falls in the served window) rather
    than a batched gather, for the same reason as kernel._get1.  Works
    on BOTH table kinds: direct-mapped slots test their own position;
    hashed slots test their STORED key (open addressing keeps keys
    unique per table, so a served key hits at most one slot).  The
    bench default stays direct-mapped because raft applies a contiguous
    index window — the range apply exploits exactly that; the hashed
    probing apply would measure the hash scheme, not the mix
    (equivalence across kinds: tests/test_bench_modes.py)."""
    assert kp.inline_payloads, "device-SM path needs sm_params()"
    T = kv.table_cap
    KS = T // 2 if kv.hash_keys else T      # key space (device_kv.py)
    CAP, AB = kp.log_cap, kp.apply_batch
    RB = 9 * write_width

    def body(i, carry):
        st, bx, ks, rd, ac, rej = carry
        inp = _self_input(kp, st, True, True, write_width, True, now0 + i)
        st, out = step(kp, st, bx, inp)
        bx = route(kp, replicas, out)
        # write side: released window -> device table (range apply, as
        # full_step_sm; the take_along_axis window read is shared with
        # that path and rides its device A/B)
        idx = out.apply_first[:, None] + jnp.arange(AB, dtype=I32)[None, :]
        valid = idx <= out.apply_last[:, None]
        vals = jnp.take_along_axis(st.lv, idx & (CAP - 1), axis=1)
        if kv.hash_keys:
            keys = idx & (KS - 1)
            cmds = jnp.stack([keys, vals], axis=-1)
            ks, (_res, ok) = kv.apply_kernel(ks, cmds, valid)
        else:
            first_key = out.apply_first & (T - 1)
            ks, (_res, ok) = kv.apply_kernel_range(ks, first_key, vals,
                                                   valid)
        rej = rej + jnp.sum(~ok & valid)
        # read side: serve the newest confirmed ctx per lane — RB keys
        # directly below the ctx index, read slot-scan style.  ReadIndex
        # semantics: a ctx is servable only once the SM has applied past
        # its index (node.py gates real reads the same way); an
        # unservable ctx is dropped from the count, never served stale
        rix = jnp.max(jnp.where(out.rtr_valid, out.rtr_index, 0), axis=1)
        served = jnp.any(out.rtr_valid, axis=1) & (rix <= st.processed)
        if kv.hash_keys:
            # stored key (keys-1; 0 = empty sentinel) tested against the
            # served key window, modulo the key space
            d = (rix[:, None] - 1 - (ks["keys"] - 1)) & (KS - 1)
            hit = (d < RB) & (ks["keys"] > 0) & served[:, None]
        else:
            d = ((rix[:, None] - 1
                  - jnp.arange(T, dtype=I32)[None, :]) & (T - 1))
            hit = (d < RB) & served[:, None]
        ac = ac + jnp.sum(jnp.where(hit, ks["vals"], 0))
        rd = rd + jnp.sum(served.astype(I32))
        return st, bx, ks, rd, ac, rej

    return jax.lax.fori_loop(
        0, iters, body, (state, box, kv_state, reads, acc, rejects))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def run_steps_sm(kp: KP.KernelParams, replicas: int, kv, iters: int,
                 tick, propose, state, box, kv_state):
    """iters device-SM pipeline steps under one jit (module-level: the
    executable caches across calls — kp/kv are hashable statics)."""
    tick = jnp.asarray(tick, bool)
    propose = jnp.asarray(propose, bool)

    def body(_, carry):
        st, bx, ks, rej = carry
        st, bx, ks, r, _ = full_step_sm(kp, replicas, kv, st, bx, ks,
                                        tick, propose)
        return st, bx, ks, rej + r

    return jax.lax.fori_loop(
        0, iters, body,
        (state, box, kv_state, jnp.asarray(0, jnp.int32)))


# ---------------------------------------------------------------------------
# commit-latency capture + 9:1 ReadIndex mix (BASELINE configs #2/#3 detail:
# the reference's latency tables README.md:53-64 and the 11M ops/s mixed
# number README.md:47)
# ---------------------------------------------------------------------------

LAT_BUCKETS = 64  # steps-to-release, 1-step buckets, last bucket saturates


def lat_init(kp: KP.KernelParams, G: int):
    """(stamp ring, histogram, completed-read-ctx counter)."""
    return (jnp.zeros((G, kp.log_cap), jnp.int32),
            jnp.zeros((LAT_BUCKETS,), jnp.int32),
            jnp.asarray(0, jnp.int32))


def _stamp_accepts(kp: KP.KernelParams, stamp, out, now):
    """Record the step at which each accepted proposal entered the log.
    One-hot select over the ring — NO dynamic scatters (the v5e
    miscompile class PERF.md documents)."""
    CAP = kp.log_cap
    idx = out.prop_index & (CAP - 1)                      # [G, B]
    iota = jnp.arange(CAP, dtype=jnp.int32)
    hit = ((iota[None, None, :] == idx[:, :, None])
           & out.prop_accepted[:, :, None]).any(axis=1)   # [G, CAP]
    return jnp.where(hit, now, stamp)


def _bucket_releases(kp: KP.KernelParams, stamp, hist, out, now, is_leader):
    """Histogram (now - stamp) for every entry released to the RSM on
    LEADER rows this step — the client-visible commit+apply latency in
    steps (only leader rows carry proposal stamps; follower releases of
    the same entries would read unstamped slots)."""
    CAP, AB = kp.log_cap, kp.apply_batch
    idx = out.apply_first[:, None] + jnp.arange(AB, dtype=jnp.int32)[None, :]
    valid = ((idx <= out.apply_last[:, None])
             & is_leader[:, None])                        # [G, AB]
    st = jnp.take_along_axis(stamp, idx & (CAP - 1), axis=1)
    lat = jnp.clip(now - st, 0, LAT_BUCKETS - 1)
    oh = ((lat[:, :, None] == jnp.arange(LAT_BUCKETS, dtype=jnp.int32))
          & valid[:, :, None])
    return hist + oh.sum(axis=(0, 1), dtype=jnp.int32)


def full_step_lat(kp: KP.KernelParams, replicas: int, write_width: int,
                  do_reads: bool, state: ShardState, box: Inbox,
                  tick, propose, now, stamp, hist, reads):
    """``full_step`` plus latency stamping and (optionally) a batched
    ReadIndex per leader per step — the quorum round that serves a batch
    of linearizable reads (raft.go ReadIndex; one ctx covers every read
    queued behind it, which is how the reference reaches its 9:1 mixed
    number)."""
    is_leader = state.role == KP.LEADER
    inp = _self_input(kp, state, tick, propose, write_width, do_reads, now)
    state, out = step(kp, state, box, inp)
    nxt = route(kp, replicas, out)
    stamp = _stamp_accepts(kp, stamp, out, now)
    hist = _bucket_releases(kp, stamp, hist, out, now, is_leader)
    reads = reads + out.rtr_valid.sum(dtype=jnp.int32)
    return state, nxt, stamp, hist, reads


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def run_steps_lat(kp: KP.KernelParams, replicas: int, iters: int,
                  write_width: int, do_reads: bool, tick, propose,
                  now0, state, box, stamp, hist, reads):
    """iters instrumented steps under one jit; carries the latency ring,
    histogram and read counter."""
    tick = jnp.asarray(tick, bool)
    propose = jnp.asarray(propose, bool)

    def body(i, carry):
        st, bx, sp, hi, rd = carry
        st, bx, sp, hi, rd = full_step_lat(
            kp, replicas, write_width, do_reads, st, bx,
            tick, propose, now0 + i, sp, hi, rd)
        return st, bx, sp, hi, rd

    return jax.lax.fori_loop(0, iters, body,
                             (state, box, stamp, hist, reads))


# ---------------------------------------------------------------------------
# election storm (BASELINE config #4): randomized message drops + pre-vote
# across many shards, then measure recovery to single-leader everywhere
# ---------------------------------------------------------------------------


def _drop_box(box: Inbox, key, p):
    """Randomly drop routed messages: dropped slots are ALL-ZERO (the
    kernel's inbox contract — see tests/test_mesh_differential.py)."""
    keep = ~jax.random.bernoulli(key, p, box.mtype.shape)   # [G, K]

    def z(x):
        if x is None:
            return None
        k = keep if x.ndim == keep.ndim else keep[..., None]
        return jnp.where(k, x, jnp.zeros_like(x))

    return type(box)(*[z(f) for f in box])


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def run_steps_storm(kp: KP.KernelParams, replicas: int, iters: int,
                    drop_p, seed, state: ShardState, box: Inbox):
    """iters ticking steps with Bernoulli(drop_p) message loss — the
    randomized-drop election storm (pre-vote keeps terms from exploding,
    raft.go:2059 pre-vote rationale)."""
    key0 = jax.random.PRNGKey(seed)
    drop_p = jnp.asarray(drop_p, jnp.float32)

    def body(i, carry):
        st, bx = carry
        st, bx, _ = full_step(kp, replicas, st, bx, True, False)
        bx = _drop_box(bx, jax.random.fold_in(key0, i), drop_p)
        return st, bx

    return jax.lax.fori_loop(0, iters, body, (state, box))


def elect_all(kp: KP.KernelParams, replicas: int, state: ShardState,
              max_rounds: int = 40):
    """Tick (no proposals) until every group has a leader."""
    import numpy as np

    box = empty_inbox(kp, state.term.shape[0])
    for _ in range(max_rounds):
        state, box = run_steps(kp, replicas, 10, True, False, state, box)
        role = np.asarray(state.role).reshape(-1, replicas)
        if (role == KP.LEADER).any(axis=1).all():
            # settle in-flight traffic
            state, box = run_steps(kp, replicas, 6, False, False, state, box)
            return state, box
    raise RuntimeError("election did not converge")
