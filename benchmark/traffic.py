"""The one load generator.  A traffic mix is a data file of parameters
(``benchmark/traffic/<name>.json``); this module interprets it.

Closed loop, as a NodeHost's callers are: service threads that each hold a
session, keep a fixed number of operations in flight and consume replies in
issue order, as a pipelined session sees them.  The writer loop is
``bench.py:run_serve_bench``'s, repaired: a monotonic clock, a state machine
that can be checked, every outcome recorded, transient drops retried as
users are told to (SKILL.md flow 4) and counted.

Latency is ``time.monotonic_ns`` from the call of ``propose`` /
``read_index`` to the return of the client's ``get`` (for a read: to the
return of the lookup that follows it).
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from typing import Iterator, NamedTuple

from dragonboat_tpu.request import (
    RequestDroppedError, RequestError, RequestTimeoutError,
)

WRITE, READ = "write", "read"
OK, TIMEOUT, FAILED = "ok", "timeout", "failed"
_RETRY_PAUSE_S = 0.02


class Record(NamedTuple):
    """One finished operation, as its client saw it."""
    kind: str
    shard: int
    key: str
    value: object        # written value, or the value a read returned
    call_ns: int
    ret_ns: int
    status: str          # OK | TIMEOUT (outcome unknown) | FAILED (not applied)
    host: int            # replica id of the host that served it
    retries: int         # transient RequestDroppedError retries before it


def validate(traffic: dict) -> None:
    """Refuse a mix the generator cannot drive (outside input)."""
    if traffic.get("loop") != "closed":
        raise ValueError("only closed-loop mixes are implemented")
    for key in ("threads_per_shard", "in_flight_per_thread", "payload_bytes",
                "mix_block", "request_timeout_s"):
        if not traffic.get(key, 0) > 0:
            raise ValueError(f"traffic {key} must be positive")
    if traffic["payload_bytes"] < 16:
        raise ValueError("a command is key(7) '=' value(8+): 16 bytes least")
    share = traffic.get("read_share", 0.0)
    if not 0.0 <= share < 1.0:
        raise ValueError(f"read_share must be in [0, 1), got {share}")
    if share > 0 and not traffic.get("key_space_per_shard", 0) > 0:
        raise ValueError("reads need a key space to read from")
    for key in ("write_target", "read_target"):
        if traffic.get(key, "leader") not in ("leader", "spread"):
            raise ValueError(f"{key} must be 'leader' or 'spread'")


def value_of(rng: random.Random, payload_bytes: int) -> str:
    return f"{rng.getrandbits(32):08x}" + "x" * (payload_bytes - 16)


def command(key: str, value: str) -> bytes:
    return f"{key}={value}".encode()


def shared_key(shard: int, k: int) -> str:
    return f"{shard:02x}{k:05x}"


def op_stream(traffic: dict, seed: int, shard: int, gidx: int,
              first_key: int) -> Iterator[tuple[str, str, str | None]]:
    """The endless seeded sequence of one client thread: (kind, key, value).
    Every block of ``mix_block`` operations holds the same number of reads
    whatever the seed, in a seeded order, so a seed changes the order of the
    work and never its amount."""
    rng = random.Random(f"{seed}:{gidx}")
    block = int(traffic["mix_block"])
    reads = round(traffic.get("read_share", 0.0) * block)
    space = int(traffic.get("key_space_per_shard", 0))
    payload = int(traffic["payload_bytes"])
    n = first_key
    while True:
        kinds = [READ] * reads + [WRITE] * (block - reads)
        rng.shuffle(kinds)
        for kind in kinds:
            if space:
                key = shared_key(shard, rng.randrange(space))
            else:
                key = f"{gidx:02x}{n:05x}"     # a new key for every write
                n += 1
            yield kind, key, (value_of(rng, payload)
                              if kind == WRITE else None)


def preload_stream(traffic: dict, seed: int, shard: int):
    """Every key of the shard's key space once, seeded values."""
    rng = random.Random(f"{seed}:preload:{shard}")
    for k in range(int(traffic["key_space_per_shard"])):
        yield WRITE, shared_key(shard, k), value_of(
            rng, int(traffic["payload_bytes"]))


class Client(threading.Thread):
    """One service thread: one shard, one session, ``in_flight`` operations
    outstanding, replies consumed in issue order."""

    def __init__(self, dep, shard: int, gidx: int, ops, in_flight: int,
                 timeout_s: float, write_target: str, read_target: str,
                 stop: threading.Event) -> None:
        super().__init__(name=f"bench-client-{gidx}", daemon=True)
        self.dep, self.shard, self.gidx = dep, shard, gidx
        self.ops = iter(ops)
        self.in_flight = in_flight
        self.timeout_s = timeout_s
        self.write_target, self.read_target = write_target, read_target
        self.stop_issuing = stop
        self.records: list[Record] = []
        self.transient_retries = 0
        self.error: BaseException | None = None
        self._spread = gidx
        self._session = None
        self._exhausted = False

    # -- issuing -----------------------------------------------------------

    def _host_for(self, target: str) -> int:
        if target == "leader":
            return self.dep.leaders[self.shard]
        self._spread += 1
        return self._spread % len(self.dep.hosts) + 1

    def _send(self, kind: str, key: str, value, rid: int):
        """-> the future, or None where the host refused it as busy / not
        ready (the transient error users must retry)."""
        nh = self.dep.hosts[rid]
        try:
            if kind == WRITE:
                return nh.propose(self._session, command(key, value),
                                  timeout_s=self.timeout_s)
            return nh.read_index(self.shard, timeout_s=self.timeout_s)
        except RequestDroppedError:
            return None

    def _issue(self, q: deque) -> None:
        try:
            kind, key, value = next(self.ops)
        except StopIteration:
            self._exhausted = True
            return
        rid = self._host_for(self.write_target if kind == WRITE
                             else self.read_target)
        call_ns = time.monotonic_ns()
        q.append([kind, key, value, call_ns,
                  self._send(kind, key, value, rid), rid, 0])

    # -- consuming ---------------------------------------------------------

    def _finish(self, op, status: str, value=None) -> None:
        kind, key, written, call_ns, _fut, rid, retries = op
        self.records.append(Record(
            kind, self.shard, key, written if kind == WRITE else value,
            call_ns, time.monotonic_ns(), status, rid, retries))

    def _await_oldest(self, q: deque) -> None:
        op = q.popleft()
        kind, key, value, call_ns, fut, rid, _retries = op
        try:
            if fut is None:
                raise RequestDroppedError("refused at the call")
            fut.get(self.timeout_s)
        except RequestDroppedError:
            # transient: ask the leader again, the operation keeps its clock
            waited_s = (time.monotonic_ns() - call_ns) / 1e9
            if waited_s > self.timeout_s:
                self._finish(op, FAILED)
                return
            self.transient_retries += 1
            op[6] += 1
            time.sleep(_RETRY_PAUSE_S)
            if kind == WRITE or self.read_target == "leader":
                op[5] = rid = self.dep.leader_host(self.shard)
            op[4] = self._send(kind, key, value, rid)
            q.appendleft(op)
            return
        except RequestTimeoutError:
            self._finish(op, TIMEOUT)
            return
        except RequestError:
            self._finish(op, FAILED)
            return
        if kind == READ:
            self._finish(op, OK, self.dep.hosts[rid].read_local_node(
                self.shard, key))
        else:
            self._finish(op, OK)

    def run(self) -> None:
        try:
            self._session = self.dep.hosts[
                self.dep.leaders[self.shard]].get_noop_session(self.shard)
            q: deque = deque()
            while not (self.stop_issuing.is_set() or self._exhausted):
                while (len(q) < self.in_flight and not self._exhausted
                       and not self.stop_issuing.is_set()):
                    self._issue(q)
                if q:
                    self._await_oldest(q)
            while q:                       # drain: every outcome is recorded
                self._await_oldest(q)
        except BaseException as e:         # read by Load.join
            self.error = e


class Load:
    """All the client threads of one mix on one deployment."""

    def __init__(self, dep, traffic: dict, streams) -> None:
        """``streams``: [(shard, gidx, ops iterator)], one per thread."""
        self.stop_issuing = threading.Event()
        self.clients = [
            Client(dep, shard, gidx, ops,
                   int(traffic["in_flight_per_thread"]),
                   float(traffic["request_timeout_s"]),
                   traffic.get("write_target", "leader"),
                   traffic.get("read_target", "leader"), self.stop_issuing)
            for shard, gidx, ops in streams]

    def start(self) -> None:
        for c in self.clients:
            c.start()

    def finished_per_client(self) -> list[int]:
        return [len(c.records) for c in self.clients]

    def join(self, deadline_s: float) -> list[Record]:
        """Stop issuing, wait until everything in flight has an outcome.
        -> every record, in no particular order."""
        self.stop_issuing.set()
        end = time.monotonic() + deadline_s
        for c in self.clients:
            c.join(max(0.0, end - time.monotonic()))
            if c.is_alive():
                raise RuntimeError(f"{c.name} did not drain in "
                                   f"{deadline_s:.0f} s")
            if c.error is not None:
                raise c.error
        return [r for c in self.clients for r in c.records]

    def transient_retries(self) -> int:
        return sum(c.transient_retries for c in self.clients)


def client_streams(traffic: dict, seed: int, shards, first_key: int):
    """[(shard, gidx, stream)] for the measured load."""
    out, gidx = [], 0
    for shard in shards:
        for _ in range(int(traffic["threads_per_shard"])):
            out.append((shard, gidx,
                        op_stream(traffic, seed, shard, gidx, first_key)))
            gidx += 1
    if gidx > 256:
        raise ValueError(f"{gidx} client threads: keys hold two hex digits")
    return out


def preload(dep, traffic: dict, seed: int) -> dict:
    """Write every key of every shard's key space once (set-up).
    -> {(shard, key): value}, all acknowledged."""
    if not int(traffic.get("key_space_per_shard", 0)):
        return {}
    deep = dict(traffic, in_flight_per_thread=int(
        traffic.get("preload_in_flight", 32)))
    load = Load(dep, deep, [(shard, i, preload_stream(traffic, seed, shard))
                            for i, shard in enumerate(dep.shards)])
    load.start()
    while any(c.is_alive() for c in load.clients):
        time.sleep(0.05)
    records = load.join(60.0)
    bad = [r for r in records if r.status != OK]
    if bad:
        raise RuntimeError(f"preload: {len(bad)} writes not acknowledged, "
                           f"e.g. {bad[0]}")
    return {(r.shard, r.key): r.value for r in records}
