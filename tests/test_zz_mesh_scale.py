"""Mesh at non-toy geometry (VERDICT r3 item 8): 256 groups spread over
all 8 virtual devices, witness/host/mesh shards coexisting, and
eviction + snapshot + membership change running CONCURRENTLY on mesh
residents.  Lives in its own zz module: the [1024]-row mesh step keeps
the single CI core busy, so it must sort after the real-time suites.
"""

import threading
import time

from dragonboat_tpu.config import (
    Config,
    ExpertConfig,
    MeshSpec,
    NodeHostConfig,
)
from dragonboat_tpu.nodehost import NodeHost

from test_kernel_engine import propose_retry
from test_nodehost import KVStateMachine, wait_leader

N_MESH = 256
REPLICAS = 4          # g_size 2 x replicas 4 = all 8 virtual devices


def test_mesh_256_groups_8_devices_mixed_residency_concurrent_ops(one_core):
    prefix = f"m256-{time.monotonic_ns()}"
    spec = MeshSpec(name=prefix, g_size=2, replicas=REPLICAS, n_local=128)
    addrs = {i: f"{prefix}-{i}" for i in range(1, REPLICAS + 1)}
    mesh_shards = tuple(range(1, N_MESH + 1))
    kernel_shards = (301, 302, 303)       # single-device kernel engine
    witness_shard = 310                   # witness member -> host engine
    hosts = {}
    try:
        for rid, addr in addrs.items():
            nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=10,
                expert=ExpertConfig(mesh=spec, kernel_log_cap=64,
                                    kernel_apply_batch=8,
                                    kernel_compaction_overhead=8,
                                    kernel_capacity=16)))
            hosts[rid] = nh
        for rid, nh in hosts.items():
            for sid in mesh_shards:
                # shard 70 will be CC-evicted to the HOST engines mid-test
                # and keeps its config there: it gets a timeout that works
                # in both regimes (see host_rtt note below) — on the mesh,
                # ticks coalesce to ~1/step so this only delays its own
                # first election by ~100 steps inside the 600 s window
                e_rtt, hb_rtt = (100, 10) if sid == 70 else (10, 2)
                nh.start_replica(addrs, False, KVStateMachine, Config(
                    shard_id=sid, replica_id=rid, election_rtt=e_rtt,
                    heartbeat_rtt=hb_rtt, mesh_resident=True))
        # Election timeouts for NON-mesh shards are sized to this box's
        # step granularity: with 256 mesh groups one worker iteration
        # takes ~1 s, but wall-clock ticks accrue every 10 ms — a 10-rtt
        # timeout delivers ~100 expired ticks per step, so every
        # replica campaigns EVERY step and elections never converge
        # (mesh lanes are immune: all replicas of a group advance in the
        # same device step, so their relative timers stay coherent).
        # 150 rtt ≈ 1.5 s spans a couple of iterations and the random
        # spread resolves the race.
        host_rtt = dict(election_rtt=150, heartbeat_rtt=15)
        # mixed residency: device-resident kernel shards on hosts 1-3
        k_addrs = {i: addrs[i] for i in (1, 2, 3)}
        for rid in (1, 2, 3):
            for sid in kernel_shards:
                hosts[rid].start_replica(k_addrs, False, KVStateMachine,
                                         Config(shard_id=sid,
                                                replica_id=rid,
                                                election_rtt=20,
                                                heartbeat_rtt=2,
                                                device_resident=True))
        # witness-bearing group: voters on hosts 1-2, witness on host 3
        w_addrs = {i: addrs[i] for i in (1, 2, 3)}
        for rid in (1, 2):
            hosts[rid].start_replica(w_addrs, False, KVStateMachine, Config(
                shard_id=witness_shard, replica_id=rid, **host_rtt))
        hosts[3].start_replica(w_addrs, False, KVStateMachine, Config(
            shard_id=witness_shard, replica_id=3, is_witness=True,
            **host_rtt))

        # -- every mesh group elects through the all_gather step --------
        deadline = time.time() + 600
        elected = 0
        while time.time() < deadline:
            elected = sum(
                1 for sid in mesh_shards
                if any(hosts[r].get_leader_id(sid)[1] for r in addrs))
            if elected == N_MESH:
                break
            time.sleep(0.5)
        assert elected == N_MESH, f"only {elected}/{N_MESH} mesh elected"
        for rid, nh in hosts.items():
            resident = sum(1 for sid in mesh_shards
                           if (sid, rid) in nh.mesh_engine.by_shard)
            assert resident == N_MESH
        # mesh step time at this geometry, for PERF.md (captured while
        # the mesh is at full residency)
        m = hosts[1].metrics()
        print(f"\nMESH_STEP_US ewma={m.get('engine.kernel_step.ewma_us', 0)}"
              f" at rows={spec.g_size * REPLICAS * spec.n_local}",
              flush=True)

        # -- concurrent: proposals + snapshot + CC-driven eviction ------
        errors = []

        def writer():
            try:
                for sid in (1, 17, 99, 200, 256):
                    lid = wait_leader(hosts, shard_id=sid, timeout=60)
                    nh = hosts[lid]
                    propose_retry(nh, nh.get_noop_session(sid),
                                  f"w{sid}=v".encode(), timeout_s=15,
                                  deadline_s=90)
            except Exception as e:            # noqa: BLE001
                errors.append(("writer", e))

        def snapshotter():
            try:
                sid = 40
                lid = wait_leader(hosts, shard_id=sid, timeout=60)
                nh = hosts[lid]
                propose_retry(nh, nh.get_noop_session(sid), b"s=1",
                              timeout_s=15, deadline_s=90)
                end = time.time() + 120
                while True:
                    try:
                        nh.sync_request_snapshot(sid, timeout_s=30)
                        break
                    except Exception:         # noqa: BLE001
                        if time.time() > end:
                            raise
                        time.sleep(0.5)
            except Exception as e:            # noqa: BLE001
                errors.append(("snapshotter", e))

        def config_changer():
            """Adding replica id 9 exceeds the mesh addressing (1..4):
            the whole group must EVICT to the host engines and keep
            serving — eviction and membership change in one motion."""
            try:
                sid = 70
                lid = wait_leader(hosts, shard_id=sid, timeout=60)
                nh = hosts[lid]
                propose_retry(nh, nh.get_noop_session(sid), b"pre=cc",
                              timeout_s=15, deadline_s=90)
                end = time.time() + 120
                while True:
                    try:
                        nh.sync_request_add_nonvoting(
                            sid, 9, f"{prefix}-x", 0, timeout_s=30)
                        break
                    except Exception:         # noqa: BLE001
                        if time.time() > end:
                            raise
                        time.sleep(0.5)
            except Exception as e:            # noqa: BLE001
                errors.append(("config_changer", e))

        threads = [threading.Thread(target=f)
                   for f in (writer, snapshotter, config_changer)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=420)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads), "concurrent op hung"

        # the CC'd group left the mesh everywhere and still serves
        end = time.time() + 120
        while time.time() < end:
            off_mesh = all((70, rid) not in nh.mesh_engine.by_shard
                           for rid, nh in hosts.items())
            if off_mesh:
                break
            time.sleep(0.5)
        assert off_mesh, "shard 70 still mesh-resident after CC"
        # ONE worker thread services the [1024]-row mesh step AND every
        # host-path node on this box, so the evicted group's re-election
        # progresses one message round per ~1s engine iteration — give
        # it the time that implies
        try:
            lid = wait_leader(hosts, shard_id=70, timeout=360)
        except AssertionError:
            for rid, nh in hosts.items():
                n = nh.nodes.get(70)
                print(f"DIAG host {rid}: node={type(n).__name__ if n else None}"
                      f" leader={n.leader_id() if n else '-'}"
                      f" term={n.node_term() if n else '-'}"
                      f" inq={len(n.incoming_msgs) if n else '-'}",
                      flush=True)
            raise
        end = time.time() + 180
        while True:
            try:
                assert hosts[lid].sync_read(70, "pre", timeout_s=60) == "cc"
                break
            except AssertionError:
                raise
            except Exception:
                if time.time() > end:
                    raise
                time.sleep(1.0)

        # witness + kernel shards served throughout (wait on the hosts
        # that CARRY the shard — host 4 never reports a leader for it,
        # so a 4-host majority would demand all three carriers incl.
        # the metadata-lagged witness)
        lid = wait_leader({r: hosts[r] for r in (1, 2, 3)},
                          shard_id=witness_shard, timeout=240)
        propose_retry(hosts[lid], hosts[lid].get_noop_session(witness_shard),
                      b"wit=ok", timeout_s=15, deadline_s=90)
        lid = wait_leader({r: hosts[r] for r in (1, 2, 3)},
                          shard_id=301, timeout=240)
        propose_retry(hosts[lid], hosts[lid].get_noop_session(301),
                      b"k=ok", timeout_s=15, deadline_s=90)

    finally:
        for nh in hosts.values():
            nh.close()
