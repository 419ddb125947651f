"""Build one configuration's deployment through the entry points a user
calls: in-process ``NodeHost``s over the chan transport, ``start_replica``
for every replica, an on-disk LogDB, the engine state on the device(s).

Copied from ``chip_smoke.py`` (PR 23) and cut to what a benchmark run needs;
the original stays the smoke.  Everything here is set-up: it is timed as
part of ``setup_s`` and never inside the measured window.
"""

from __future__ import annotations

import functools
import json
import os
import time
import zlib
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from dragonboat_tpu import fabric
from dragonboat_tpu.config import (
    Config, ExpertConfig, MeshSpec, NodeHostConfig,
)
from dragonboat_tpu.core import kernel
from dragonboat_tpu.core.kstate import empty_inbox, empty_input
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.parallel import ici
from dragonboat_tpu.request import RequestError
from dragonboat_tpu.statemachine import IStateMachine, Result

from benchmark import peaks

HERE = os.path.dirname(os.path.abspath(__file__))

#: faults a control run may switch on (never on in a benchmark run); each
#: breaks one guarantee the configurations state, where the answer is made
FAULTS = ("lost-write", "diverging-replica", "stale-read")
_FAULT_ONE_IN = 4


class BenchFailure(Exception):
    """A phase of the run did not hold; the command exits non-zero and
    prints no result line."""


def check(ok, message) -> None:
    if not ok:
        raise BenchFailure(str(message))


def load_json(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json`` — the one place a name in
    ``BENCHMARK.json`` becomes a file."""
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


class FaultSwitch:
    """Which guarantee, if any, the state machines break right now."""

    def __init__(self) -> None:
        self.fault: str | None = None


def _marked(text: str) -> bool:
    return zlib.crc32(text.encode()) % _FAULT_ONE_IN == 0


class KV(IStateMachine):
    """In-memory ``key=value`` store (the smoke's, plus ``get_hash`` for
    ``NodeHost.get_sm_hash`` and the control's fault switch)."""

    def __init__(self, shard_id, replica_id, switch: FaultSwitch) -> None:
        self.replica_id = replica_id
        self.switch = switch
        self.kv: dict[str, str] = {}
        self.before: dict[str, str | None] = {}   # stale-read control only

    def update(self, entry):
        k, v = entry.cmd.decode().split("=", 1)
        fault = self.switch.fault
        if fault is not None and _marked(k + v):
            if fault == "lost-write" or (fault == "diverging-replica"
                                         and self.replica_id == 3):
                return Result(value=len(self.kv))   # acknowledged, dropped
        if fault == "stale-read":
            self.before[k] = self.kv.get(k)
        self.kv[k] = v
        return Result(value=len(self.kv))

    def lookup(self, query):
        if (self.switch.fault == "stale-read" and query in self.before
                and _marked(query)):
            return self.before[query]               # the value it replaced
        return self.kv.get(query)

    def get_hash(self) -> int:
        return zlib.crc32("\n".join(
            f"{k}={v}" for k, v in sorted(self.kv.items())).encode())

    def save_snapshot(self, w, files, done):
        w.write(json.dumps(self.kv).encode())

    def recover_from_snapshot(self, r, files, done):
        self.kv = json.loads(r.read().decode())


class Deployment:
    """The hosts of one configuration, started and elected."""

    def __init__(self, cfg: dict, devices, root: str, shards: int,
                 trace_sample_every: int | None, say) -> None:
        self.cfg = cfg
        self.devices = devices
        self.replicas = int(cfg["replicas"])
        self.shards = tuple(range(1, shards + 1))
        self.mesh = cfg["engine"] == "mesh"
        self.switch = FaultSwitch()
        self.hosts: dict[int, NodeHost] = {}
        self.leaders: dict[int, int] = {}
        self.label = f"bench-{cfg['name']}"
        self.facts: dict = {}
        try:
            self._start(root, trace_sample_every)
            self.state_devices = self._check_on_device()
            t0 = time.monotonic()
            self.leaders = self._await_leaders()
            self.facts["elect_s"] = time.monotonic() - t0
            t0 = time.monotonic()
            self._place_leaders()
            self.facts["place_leaders_s"] = time.monotonic() - t0
            self.facts["shards_led_by_host"] = {
                rid: sum(lead == rid for lead in self.leaders.values())
                for rid in self.hosts}
            if cfg.get("warm_row_fetch"):
                t0 = time.monotonic()
                self.facts["row_fetch_shapes"] = self._warm_row_fetch()
                self.facts["warm_row_fetch_s"] = time.monotonic() - t0
            if self.mesh:
                self.facts["link_classes"] = self._link_classes()
        except BaseException:
            self.close()
            raise
        say(phase="deployed", config=cfg["name"], shards=len(self.shards),
            replicas=self.replicas, state_devices=self.state_devices,
            logdb=self.hosts[1].logdb.name(), **self.facts)

    # -- set-up ------------------------------------------------------------

    def _expert(self, trace_sample_every) -> ExpertConfig:
        knobs = dict(self.cfg.get("expert", {}))
        if trace_sample_every is not None:
            knobs["trace_sample_every"] = trace_sample_every
        if self.mesh:
            m = self.cfg["mesh"]
            # the name is this process's only mesh engine, so it may be fixed
            knobs["mesh"] = MeshSpec(
                name=self.label, g_size=m["g_size"], replicas=m["replicas"],
                n_local=max(m["n_local"], len(self.shards)))
        return ExpertConfig(**knobs)

    def _start(self, root: str, trace_sample_every) -> None:
        raft = self.cfg["raft"]
        expert = self._expert(trace_sample_every)
        addrs = {rid: f"{self.label}-{rid}"
                 for rid in range(1, self.replicas + 1)}
        t0 = time.monotonic()
        for rid, addr in addrs.items():
            nh = NodeHost(NodeHostConfig(
                raft_address=addr, rtt_millisecond=raft["rtt_millisecond"],
                expert=expert,
                node_host_dir=os.path.join(root, f"nh{rid}")))
            self.hosts[rid] = nh
            check(nh.logdb.name().startswith("sharded-tan"),
                  f"not the on-disk LogDB: {nh.logdb.name()}")
        self.facts["hosts_s"] = time.monotonic() - t0

        def start_host(rid: int) -> None:
            nh = self.hosts[rid]
            for sid in self.shards:
                nh.start_replica(
                    addrs, False,
                    lambda s, r: KV(s, r, self.switch),
                    Config(shard_id=sid, replica_id=rid,
                           election_rtt=raft["election_rtt"],
                           heartbeat_rtt=raft["heartbeat_rtt"],
                           device_resident=not self.mesh,
                           mesh_resident=self.mesh))

        # one thread per host where the configuration says so: on the chip
        # machine 144 replicas start in 33 s from three threads, 57 s from one
        workers = (len(self.hosts)
                   if self.cfg.get("start_hosts_in_parallel") else 1)
        t0 = time.monotonic()
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(start_host, self.hosts))   # raises a host's error
        self.facts["start_replicas_s"] = time.monotonic() - t0

    def _check_on_device(self) -> list[str]:
        """The engine state is on the device(s) and no shard fell back to
        the host engine."""
        for rid, nh in self.hosts.items():
            check(all(nh.nodes[sid].peer is None for sid in self.shards),
                  f"host {rid}: a shard fell back to the host engine")
        if self.mesh:
            eng = self.hosts[1].mesh_engine
            check(eng is not None and all(h.mesh_engine is eng
                                          for h in self.hosts.values()),
                  "the hosts do not share one mesh engine")
            on = eng.state.term.sharding.device_set
            check(len(on) == self.replicas,
                  f"state not on {self.replicas} distinct devices: {on}")
            self.engines = [eng]
        else:
            self.engines = [nh.kernel_engine for nh in self.hosts.values()]
            check(all(e is not None for e in self.engines),
                  "a host has no kernel engine")
            on = {d for e in self.engines for d in e.state.term.devices()}
            check(on == {self.devices[0]},
                  f"state not on {self.devices[0]}: {on}")
        check(all(d.platform == self.devices[0].platform for d in on), on)
        self.state_device_objects = sorted(on, key=lambda d: d.id)
        return [str(d) for d in self.state_device_objects]

    def _await_leaders(self, deadline_s: float = 600.0) -> dict:
        """-> {shard: leader replica id}, once all hosts agree on one
        leader for every shard."""
        leaders: dict[int, int] = {}
        deadline = time.monotonic() + deadline_s
        while len(leaders) < len(self.shards):
            for sid in self.shards:
                if sid in leaders:
                    continue
                votes = [h.get_leader_id(sid) for h in self.hosts.values()]
                if all(ok for _, ok in votes) and len(
                        {lid for lid, _ in votes}) == 1:
                    leaders[sid] = votes[0][0]
            check(time.monotonic() < deadline,
                  f"{len(self.shards) - len(leaders)} of {len(self.shards)} "
                  f"shards leaderless after {deadline_s:.0f} s")
            time.sleep(0.02)
        return leaders

    def _place_leaders(self, deadline_s: float = 30.0) -> None:
        """Shard ``s`` is led from host ``(s - 1) % replicas + 1``, so every
        run gives every engine the same share of the leaders' work (where
        elections happen to land them differs from run to run).  A transfer
        is fire-and-forget and raft abandons one that misses an election
        timeout, so it is asked again until the deadline; what is left then
        is reported, not hidden."""
        want = {sid: (sid - 1) % self.replicas + 1 for sid in self.shards}
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            wrong = [sid for sid in self.shards
                     if self.leader_host(sid) != want[sid]]
            if not wrong:
                break
            for sid in wrong:
                try:
                    self.hosts[self.leaders[sid]].request_leader_transfer(
                        sid, want[sid])
                except RequestError:
                    pass            # the last one asked is still outstanding
            time.sleep(0.25)
        self.leaders = self._await_leaders()

    def _warm_row_fetch(self) -> int:
        """Compile, now, every shape of the one per-step device read whose
        shape varies: the engine fetches the term-ring rows of the lanes a
        step saved with ``state.lt[idx]`` (``kernel_engine.py``
        ``_process_outputs``), and jax compiles that gather and its index
        arithmetic anew for every count of rows — ~0.5 s of stalls per new
        count, inside an engine round, wherever in the run the count first
        shows.  Left to the traffic, a window met new counts in one run of
        six (PERF.md, PR 24).  The programs are jax's own, keyed by shape
        and placement, so running the same expression here on the engine's
        own array fills the cache the engine reads.  -> counts compiled."""
        eng = self.engines[0]        # the kernel engines share shapes
        with eng.mu:
            lt, rows = eng.state.lt, len(eng.nodes)   # depth 0 donates nothing
        for n in range(1, rows + 1):
            idx = jnp.asarray(np.arange(n, dtype=np.int32))
            np.asarray(lt[idx])
        return rows

    def _link_classes(self) -> dict:
        mine = {k: v
                for k, v in fabric.METER.snapshot()["link_classes"].items()
                if k.startswith(self.label)}
        check(len(mine) == self.replicas * (self.replicas - 1)
              and set(mine.values()) == {"resident"},
              f"mesh links are not all resident: {mine}")
        return mine

    # -- what the load and the checks need ---------------------------------

    def leader_host(self, sid: int) -> int:
        """The shard's leader as its hosts see it now (leaders move)."""
        lid, ok = self.hosts[self.leaders[sid]].get_leader_id(sid)
        if ok and lid in self.hosts:
            self.leaders[sid] = lid
        return self.leaders[sid]

    def replica_value(self, rid: int, sid: int, key: str):
        return self.hosts[rid].stale_read(sid, key)

    def replica_items(self, rid: int, sid: int) -> dict:
        """A copy of one replica's whole table (reference for the initial
        values of a history); read outside the window only."""
        return dict(self.hosts[rid].nodes[sid].sm.sm.kv)

    def sm_hashes(self, sid: int) -> list[int]:
        return [nh.get_sm_hash(sid) for nh in self.hosts.values()]

    def step_bytes_per_device(self) -> int:
        """Bytes on one chip of the step's arguments and outputs, from the
        shapes the engine serves with (``peaks.step_bytes_per_device``)."""
        eng = self.engines[0]

        def shapes(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
                tree)

        with eng.mu:
            state = shapes(eng.state)
        if self.mesh:
            cl, disp = eng.cluster, eng._dispatch
            args = (state, shapes(disp.box),
                    shapes(cl.shard(empty_input(cl.kp, cl.total_rows))),
                    shapes(cl.shard(disp.cut)))
            outs = jax.eval_shape(
                functools.partial(ici.jit_serve_step, cl.kp, cl), *args)
            # eval_shape drops shardings; outputs are sharded as the state
            n = len(eng.state.term.sharding.device_set)
            return (peaks.step_bytes_per_device(args, ())
                    + peaks.step_bytes_per_device((), outs) // n)
        rows = eng.state.term.shape[0]
        args = (state, shapes(empty_inbox(eng.kp, rows)),
                shapes(empty_input(eng.kp, rows)))
        outs = jax.eval_shape(functools.partial(kernel.step, eng.kp), *args)
        return peaks.step_bytes_per_device(args, outs)

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    def close(self) -> None:
        for nh in self.hosts.values():
            nh.close()
        self.hosts = {}
