#!/usr/bin/env python
"""Chip smoke: serve Raft shards from the TPU through NodeHost, once.

The quickest proof that the system still starts on the chip.  One process,
the entry points a user calls: three in-process ``NodeHost``s over the chan
transport, each with an on-disk (fsynced) LogDB and the default
``ExpertConfig`` kernel geometry (1024 lanes x 1024-entry ring per host),
48 shards x 3 replicas with ``Config(device_resident=True)``, 16-byte
commands into an in-memory KV state machine — the upstream benchmark
deployment (BASELINE.md: 48 Raft shards across 3 NodeHosts).  Seeded writes
go to every shard's leader host; every acknowledged write is read back
linearizably from the leader's host AND a follower's host, and must be
present in all three replicas' state machines.  The plain reference is a
dict fed the same writes.

    python chip_smoke.py                  one chip (what the driver runs)
    python chip_smoke.py --chips 4        only the mesh phase: replicas of a
                                          group on three different chips,
                                          Raft traffic as collectives, vs the
                                          same writes with every link cut to
                                          the host hub
    python chip_smoke.py --rehearse ...   the only way this runs off the
                                          chip (CPU, small); never prints the
                                          contract's last line

Every stdout line is one JSON object.  Timings are set-up facts
(``"setup_fact": true``), not benchmark results.  With no accelerator the
script exits non-zero before it builds anything.  The last line on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import tempfile
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

import jax  # (imports touch no backend; main() checks first)
import jaxlib
import numpy as np

from dragonboat_tpu import capacity, fabric, hostenv, lifecycle, native
from dragonboat_tpu.config import (
    Config, ExpertConfig, MeshSpec, NodeHostConfig,
)
from dragonboat_tpu.core.kstate import round_columns
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.parallel import round as mesh_round
from dragonboat_tpu.request import RequestDroppedError
from dragonboat_tpu.statemachine import IStateMachine, Result

REPLICAS = 3
WRITES_PER_SHARD = 4
CLIENT_THREADS = 16    # concurrent client sessions, one shard each
MESH_COLLECTIVES = ("all-gather", "all-to-all", "collective-permute",
                    "all-reduce")


class SmokeFailure(Exception):
    """A phase of the smoke did not hold; the script exits non-zero."""


def check(ok, message) -> None:
    if not ok:
        raise SmokeFailure(str(message))


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count (default 48; 4 with --rehearse); the "
                         "only scale knob — geometry, replicas, fsync and "
                         "read-back are never cut")
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU backend at a small size to find "
                         "wrong paths; prints no contract line")
    return ap.parse_args(argv)


def require_device(args):
    """-> jax.devices(), or exit non-zero: off the chip nothing is built.
    ``--rehearse`` is the only way onto the CPU backend, and is never
    chosen by the script itself."""
    if args.rehearse and args.chips == 4:
        # the rehearsal's virtual devices; only the CPU backend reads this
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            sys.exit(f"--rehearse is for the CPU backend; jax reports "
                     f"{platform!r}")
    elif platform != "tpu":
        sys.exit(f"chip_smoke: no accelerator — jax reports platform "
                 f"{platform!r}; refusing to carry on (--rehearse rehearses "
                 f"on the CPU)")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax reports "
                 f"{len(devices)} device(s)")
    return devices


class KV(IStateMachine):
    """In-memory ``key=value`` store."""

    def __init__(self, shard_id, replica_id):
        self.kv: dict[str, str] = {}

    def update(self, entry):
        k, v = entry.cmd.decode().split("=", 1)
        self.kv[k] = v
        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        w.write(json.dumps(self.kv).encode())

    def recover_from_snapshot(self, r, files, done):
        self.kv = json.loads(r.read().decode())


def seeded_writes(seed: int, shards, per_shard: int):
    """-> [(shard, key, value)], every command ``key=value`` 16 bytes."""
    rng = random.Random(seed)
    out = []
    for sid in shards:
        for i in range(per_shard):
            key, val = f"k{sid:03d}{i:03d}", f"{rng.getrandbits(32):08x}"
            check(len(f"{key}={val}".encode()) == 16, "command width")
            out.append((sid, key, val))
    return out


def retrying(fn, deadline_s: float = 60.0):
    """Call ``fn`` until it stops raising the transient not-ready error
    users must retry (SKILL.md flow 4); re-raises once the deadline passes.
    Any other error, a timeout included, fails the phase.
    -> (result, retries)."""
    end, retries = time.time() + deadline_s, 0
    while True:
        try:
            return fn(), retries
        except RequestDroppedError:
            if time.time() > end:
                raise
            retries += 1
            time.sleep(0.05)


def stage_medians() -> dict:
    """Median dwell (us) per lifecycle stage over the sampled proposals
    still in the tracer's ring — where an acknowledged write's time went."""
    dwell: dict[str, list[int]] = {}
    for tr in lifecycle.TRACER.completed():
        if tr["kind"] != lifecycle.KIND_PROPOSAL:
            continue
        prev = tr["stamps"][0][1]
        for stage, ts in tr["stamps"][1:]:
            dwell.setdefault(stage, []).append(ts - prev)
            prev = ts
        dwell.setdefault("total", []).append(tr["total_us"])
    return {s: int(np.median(v)) for s, v in dwell.items()}


def compile_report() -> dict:
    return {entry: {"calls": row["calls"], "compiles": row["compiles"],
                    "retraces": row["retraces"],
                    "compile_s": round(row["compile_us_total"] / 1e6, 3)}
            for entry, row in sorted(capacity.TRACKER.snapshot().items())}


def mesh_step_collectives(eng) -> dict:
    """Compile the mesh serve entry the engine dispatches through, for the
    shapes and shardings it holds (the resident state, the carried inbox,
    a round's upload, the cut mask), and count the collectives in the
    optimized HLO (shapes only: the engine thread owns the arrays)."""
    cl, disp = eng.cluster, eng._dispatch
    sharding = cl.sharding()

    def shape(x, dims=None):
        return jax.ShapeDtypeStruct(dims or x.shape, x.dtype,
                                    sharding=sharding)

    with eng.mu:
        state, box = jax.tree.map(shape, (eng._resident, disp._box))
    up = shape(box, (cl.total_rows, round_columns(cl.kp).up_width))
    entry = (mesh_round.jit_serve_step_donated if eng.pipeline_depth > 0
             else mesh_round.jit_serve_step)
    hlo = entry.lower(
        cl.kp, cl, state, box, up, shape(disp.cut)).compile().as_text()
    return {c: hlo.count(f" {c}(") + hlo.count(f" {c}-start(")
            for c in MESH_COLLECTIVES}


def check_on_device(label, hosts, shards, mesh: bool, devices,
                    cut_links: bool):
    """The engine state really is on the device(s).  -> (device names,
    collectives of the mesh serve step or None).  With ``cut_links`` every
    mesh link is then handed to the host hub."""
    for rid, nh in hosts.items():
        check(all(nh.nodes[sid].peer is None for sid in shards),
              f"{label}: host {rid}: a shard fell back to the host engine")
    collectives = None
    if mesh:
        eng = hosts[1].mesh_engine
        check(eng is not None
              and all(h.mesh_engine is eng for h in hosts.values()),
              f"{label}: the hosts do not share one mesh engine")
        on = eng.state.term.sharding.device_set
        check(len(on) == REPLICAS,
              f"{label}: state not on {REPLICAS} distinct devices: {on}")
        collectives = mesh_step_collectives(eng)
        check(sum(collectives.values()) > 0,
              f"{label}: the compiled serve step holds no collective")
        if cut_links:
            for sid in shards:
                for a in range(1, REPLICAS + 1):
                    for b in range(a + 1, REPLICAS + 1):
                        eng.set_link_hub_served(
                            eng.by_shard[(sid, a)], b, True)
    else:
        engines = [nh.kernel_engine for nh in hosts.values()]
        check(all(e is not None for e in engines),
              f"{label}: a host has no kernel engine")
        on = {d for e in engines for d in e.state.term.devices()}
        check(on == {devices[0]}, f"{label}: state not on {devices[0]}: {on}")
    check(all(d.platform == devices[0].platform for d in on), on)
    return sorted(str(d) for d in on), collectives


def await_leaders(label, hosts, shards, deadline_s: float = 600.0) -> dict:
    """-> {shard: leader replica id}, once all three hosts agree on one
    leader for every shard."""
    leaders: dict[int, int] = {}
    deadline = time.time() + deadline_s
    while len(leaders) < len(shards):
        for sid in shards:
            if sid in leaders:
                continue
            votes = [h.get_leader_id(sid) for h in hosts.values()]
            if all(ok for _, ok in votes) and len(
                    {lid for lid, _ in votes}) == 1:
                leaders[sid] = votes[0][0]
        check(time.time() < deadline,
              f"{label}: {len(shards) - len(leaders)} of {len(shards)} "
              f"shards leaderless after {deadline_s:.0f} s")
        time.sleep(0.02)
    return leaders


def link_classes(label, cut_links: bool) -> dict:
    """This cluster's six host-to-host links, all of the expected class."""
    mine = {k: v for k, v in fabric.METER.snapshot()["link_classes"].items()
            if k.startswith(label)}
    want = "hub" if cut_links else "resident"
    check(len(mine) == REPLICAS * (REPLICAS - 1)
          and set(mine.values()) == {want},
          f"{label}: link classes are not all {want!r}: {mine}")
    return mine


def drive_clients(label, hosts, leaders, writes) -> dict:
    """Seeded writes on each shard's leader host, then a linearizable
    read-back of every write from the leader's host and a follower's.
    Shards are driven concurrently (as their users would), each in order.
    -> answers and the phase's set-up facts; raises on a write that is
    never acknowledged or a read that differs."""
    by_shard: dict[int, list] = {}
    for sid, key, val in writes:
        by_shard.setdefault(sid, []).append((key, val))

    def write_shard(sid: int) -> tuple[int, int]:
        acked = retries = 0
        sess = hosts[leaders[sid]].get_noop_session(sid)
        for key, val in by_shard[sid]:
            def propose(cmd=f"{key}={val}".encode()):
                lid, ok = hosts[leaders[sid]].get_leader_id(sid)
                if ok and lid in hosts:
                    leaders[sid] = lid
                return hosts[leaders[sid]].sync_propose(
                    sess, cmd, timeout_s=30)
            retries += retrying(propose)[1]
            acked += 1      # sync_propose returned: quorum-committed, applied
        return acked, retries

    def read_shard(sid: int) -> tuple[dict, int]:
        retries, got = 0, {}
        lead = leaders[sid]
        for key, val in by_shard[sid]:
            for rid in (lead, lead % REPLICAS + 1):
                ans, r = retrying(lambda: hosts[rid].sync_read(
                    sid, key, timeout_s=30))
                retries += r
                check(ans == val,
                      f"{label}: shard {sid} {key}: host {rid} read "
                      f"{ans!r}, acknowledged {val!r}")
            got[(sid, key)] = ans
        return got, retries

    answers: dict = {}
    with ThreadPoolExecutor(max_workers=CLIENT_THREADS) as pool:
        t0 = time.time()
        acked, retries = map(sum, zip(*pool.map(write_shard, by_shard)))
        write_s = time.time() - t0
        check(acked == len(writes),
              f"{label}: {acked} of {len(writes)} writes acknowledged")
        stages = stage_medians()    # before the reads crowd the ring
        t0 = time.time()
        for got, r in pool.map(read_shard, by_shard):
            answers.update(got)
            retries += r
        read_s = time.time() - t0
    return answers, dict(
        writes_attempted=len(writes),
        writes_acknowledged=acked,
        reads_checked=2 * len(writes), transient_retries=retries,
        write_s=round(write_s, 3), read_s=round(read_s, 3),
        write_stage_us_median=stages)


def await_replica_copies(label, hosts, answers, deadline_s: float = 60.0):
    """Every replica's state machine holds every acknowledged write."""
    deadline = time.time() + deadline_s
    missing = [(rid, sid, key) for sid, key in answers for rid in hosts]
    while missing:
        missing = [(rid, sid, key) for rid, sid, key in missing
                   if hosts[rid].stale_read(sid, key) != answers[(sid, key)]]
        check(not missing or time.time() < deadline,
              f"{label}: {len(missing)} replica copies missing after "
              f"{deadline_s:.0f} s, e.g. {missing[:3]}")
        time.sleep(0.02)
    return len(answers) * len(hosts)


def serve(label: str, root: str, shards, writes, expert: ExpertConfig,
          mesh: bool, devices, cut_links: bool = False) -> dict:
    """Build three NodeHosts, elect, write, read back, check replicas.
    -> {(shard, key): value} as the cluster answered.  Raises on any failed
    phase; the hosts are always closed."""
    addrs = {rid: f"{label}-{rid}" for rid in range(1, REPLICAS + 1)}
    hosts: dict[int, NodeHost] = {}
    lifecycle.TRACER.reset()
    try:
        t0 = time.time()
        for rid, addr in addrs.items():
            nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=5, expert=expert,
                node_host_dir=os.path.join(root, f"{label}-nh{rid}")))
            hosts[rid] = nh
            check(nh.logdb.name().startswith("sharded-tan"),
                  f"{label}: not the on-disk LogDB: {nh.logdb.name()}")
            for sid in shards:
                nh.start_replica(addrs, False, KV, Config(
                    shard_id=sid, replica_id=rid, election_rtt=10,
                    heartbeat_rtt=2, device_resident=not mesh,
                    mesh_resident=mesh))
        started_s = time.time() - t0
        state_devices, collectives = check_on_device(
            label, hosts, shards, mesh, devices, cut_links)

        t0 = time.time()
        leaders = await_leaders(label, hosts, shards)
        say(phase=f"{label}:elected", setup_fact=True, shards=len(shards),
            replicas=REPLICAS, start_replicas_s=round(started_s, 3),
            elect_s=round(time.time() - t0, 3), state_devices=state_devices,
            compiles=compile_report())

        links = link_classes(label, cut_links) if mesh else None
        answers, facts = drive_clients(label, hosts, leaders, writes)
        copies = await_replica_copies(label, hosts, answers)
        say(phase=f"{label}:served", setup_fact=True, **facts,
            replica_copies_checked=copies, fsync=True,
            logdb=hosts[1].logdb.name(), link_classes=links,
            collectives=collectives, compiles=compile_report())
        return answers
    finally:
        for nh in hosts.values():
            nh.close()


def main() -> None:
    args = parse_args(sys.argv[1:])
    devices = require_device(args)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    n_shards = args.shards or (4 if args.rehearse else 48)
    shards = tuple(range(1, n_shards + 1))
    say(phase="start", python=sys.version.split()[0], jax=jax.__version__,
        jaxlib=jaxlib.__version__, numpy=np.__version__, device=device,
        chips=args.chips, rehearse=args.rehearse, shards=n_shards,
        replicas=REPLICAS, writes_per_shard=WRITES_PER_SHARD, seed=args.seed,
        shard_cut=(None if n_shards == 48 else
                   f"{n_shards} shards instead of the deployment's 48"))
    entries_before = hostenv.cache_entry_count()
    cache_dir = hostenv.enable_compile_cache()
    events: Counter = Counter()     # jax's own cache hit/miss events
    jax.monitoring.register_event_listener(
        lambda event, **_: events.update([event]))
    say(phase="environment", compile_cache_dir=cache_dir,
        cache_entries_before=entries_before,
        native_replay_library=native.available())

    writes = seeded_writes(args.seed, shards, WRITES_PER_SHARD)
    reference = {(sid, key): val for sid, key, val in writes}
    knobs = {"trace_sample_every": 1}

    root = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        if args.chips == 1:
            ex = ExpertConfig(**knobs)
            say(phase="geometry", setup_fact=True, hosts=REPLICAS,
                kernel_capacity=ex.kernel_capacity,
                kernel_log_cap=ex.kernel_log_cap,
                pipeline_depth=ex.kernel_pipeline_depth)
            answers = serve("smoke", root, shards, writes, ex, False, devices)
            check(answers == reference, "answers differ from the reference")
        else:
            # config.MeshSpec places one device per replica slot, so a
            # 3-replica group takes three of the four chips and the fourth
            # idles: no other shape exists today
            n_local = max(48, n_shards)
            say(phase="geometry", setup_fact=True,
                mesh={"g_size": 1, "replicas": REPLICAS, "n_local": n_local},
                note="MeshSpec(g_size=1, replicas=3) holds state on three "
                     "of the four chips; config.MeshSpec allows no shape "
                     "that uses the fourth with 3-replica groups")
            got = {}
            for arm, cut in (("resident", False), ("hub", True)):
                spec = MeshSpec(name=f"smoke-{arm}-{time.monotonic_ns()}",
                                g_size=1, replicas=REPLICAS, n_local=n_local)
                got[arm] = serve(f"mesh-{arm}", root, shards, writes,
                                 ExpertConfig(mesh=spec, **knobs), True,
                                 devices, cut_links=cut)
            check(got["resident"] == got["hub"] == reference,
                  "the arms' answers differ from each other or the reference")
            say(phase="mesh:compared", arms_equal=True,
                answers=len(reference))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    report = compile_report()
    retraced = {e: r["retraces"] for e, r in report.items() if r["retraces"]}
    check(not retraced, f"retraces after warm-up: {retraced}")
    say(phase="done", compile_cache_dir=cache_dir,
        cache_entries_before=entries_before,
        cache_entries_after=hostenv.cache_entry_count(),
        cache_hits=events["/jax/compilation_cache/cache_hits"],
        cache_misses=events["/jax/compilation_cache/cache_misses"],
        compiles=report)
    if args.rehearse:
        say(rehearsal=True, note="CPU rehearsal: not a chip run", **device)
        return
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
