"""Capacity & compilation observability (capacity.py): the contracts
model vs measured device bytes at three geometries, the compile
tracker's retrace semantics, the steady-state one-compile-per-entry
regression on live engines at both pipeline depths, the
/debug/capacity + /healthz endpoints, the doctor CLIs, and the strict
schema validator."""

import importlib.util
import json
import os
import sys
import time
import urllib.error
import urllib.request

import pytest

import jax

from dragonboat_tpu import capacity, flight, telemetry
from dragonboat_tpu.core import health, kstate
from dragonboat_tpu.core.params import KernelParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_global_tracker():
    """Live engines in this module wrap into the process-wide
    capacity.TRACKER; drop their states/spans afterwards so later
    modules' /trace exports see only their own compile spans."""
    yield
    capacity.TRACKER.clear()


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------
# contracts model vs measured bytes (the differential the ISSUE pins)

GEOMETRIES = [
    ("default", KernelParams(), 4),
    ("inline-payloads", KernelParams(inline_payloads=True), 6),
    ("custom", KernelParams(num_peers=5, log_cap=256, readindex_cap=8), 3),
]


@pytest.mark.parametrize("name,kp,groups", GEOMETRIES,
                         ids=[g[0] for g in GEOMETRIES])
def test_model_matches_measured_bytes(name, kp, groups):
    """Analytic bytes-per-group from the CONTRACTS grammar must track
    what the constructors actually allocate, within 1%, per class."""
    state = kstate.init_state(kp, groups, replica_id=1,
                              peer_ids=list(range(1, kp.num_peers + 1)))
    box = kstate.empty_inbox(kp, groups)
    inp = kstate.empty_input(kp, groups)
    digest = health.empty_digest(groups)
    trees = {"ShardState": state, "Inbox": box, "StepInput": inp,
             "HealthDigest": digest}
    per = capacity.model_bytes_per_group(kp)
    for cls, tree in trees.items():
        predicted = per[cls] * groups
        measured = capacity.measure_tree_bytes(tree)
        assert measured > 0, f"{cls}: empty measurement"
        delta = abs(predicted - measured) / measured
        assert delta <= 0.01, (
            f"{name}/{cls}: predicted {predicted} vs measured {measured} "
            f"({delta:.2%} off)")
    # ... and what an engine keeps between rounds: the state in its
    # resident form and the mesh backend's carried inbox, every packed
    # field an int32 column, the rings as they are; exact
    packed = capacity.model_bytes_per_group(
        kp, packed=capacity.PACKED_RESIDENT)
    resident = kstate.pack_program(kp)(state)
    assert packed["ShardState"] * groups \
        == capacity.measure_tree_bytes(resident) > per["ShardState"] * groups
    assert packed["Inbox"] == 4 * kstate.inbox_columns(kp)[1]
    assert packed["HealthDigest"] == per["HealthDigest"]


def test_predict_and_max_g_consistency():
    kp = KernelParams()
    # the resident set as an engine keeps it (the packed form)
    per = capacity.model_bytes_per_group(
        kp, capacity.RESIDENT_CLASSES, capacity.PACKED_RESIDENT)
    total = per["total"]
    assert total == sum(per[c] for c in capacity.RESIDENT_CLASSES)
    assert capacity.predict_bytes(kp, 7, capacity.RESIDENT_CLASSES,
                                  capacity.PACKED_RESIDENT) == 7 * total
    # max_g * per_group fits the budget; one more group does not
    budget = 1000 * total + total // 2
    g = capacity.max_g_for_budget(kp, budget)
    assert g == 1000
    assert g * total <= budget < (g + 1) * total
    assert capacity.max_g_for_budget(kp, 0) == 0


def test_measure_tree_bytes_tolerates_none_and_scalars():
    assert capacity.measure_tree_bytes(None) == 0
    assert capacity.measure_tree_bytes({"a": None, "b": 3}) == 0
    arr = jax.numpy.zeros((4, 2), jax.numpy.int32)
    assert capacity.measure_tree_bytes((arr, None), arr) == 2 * arr.nbytes


# ---------------------------------------------------------------------
# compile tracker unit semantics (injected clock / registry / recorder)


class _FakeJit:
    """Callable with a jit-style executable cache: compiles whenever
    told to, so tests script exact compile/clean sequences."""

    def __init__(self):
        self.cache = 0
        self.compile_next = True

    def _cache_size(self):
        return self.cache

    def __call__(self):
        if self.compile_next:
            self.cache += 1
            self.compile_next = False
        return self.cache


class _Recorder:
    def __init__(self):
        self.records = []

    def record(self, kind, **fields):
        self.records.append(dict(fields, kind=kind))
        return len(self.records) - 1


def _mk_tracker():
    clock = {"t": 0}

    def tick():
        clock["t"] += 10
        return clock["t"]

    rec = _Recorder()
    reg = telemetry.Registry()
    return capacity.CompileTracker(clock=tick, registry=reg,
                                   recorder=rec), rec, reg


def test_tracker_counts_compiles_and_edge_triggers_storm():
    tracker, rec, reg = _mk_tracker()
    fn = _FakeJit()
    entry = tracker.wrap("step", fn)
    entry()                      # first compile: expected, not a retrace
    entry()                      # clean call -> steady state
    st = entry.stats()
    assert st["calls"] == 2 and st["compiles"] == 1
    assert st["retraces"] == 0 and st["compile_us_total"] == 10
    assert rec.records == []
    fn.compile_next = True
    entry()                      # compile after steady state: retrace
    st = entry.stats()
    assert st["compiles"] == 2 and st["retraces"] == 1
    assert [r["kind"] for r in rec.records] == [capacity.RETRACE_STORM]
    assert rec.records[0]["entry"] == "step"
    assert rec.records[0]["tick"] == 3    # call count, not wall clock
    entry()                      # clean
    fn.compile_next = True
    entry()                      # second retrace: storm already latched
    assert entry.stats()["retraces"] == 2
    assert len(rec.records) == 1, "storm flight event must edge-trigger"
    # the compile histogram carries every compile under the entry label
    expo = reg.exposition()
    assert 'compile_us_count{entry="step"} 3' in expo


def test_tracker_per_wrap_counters_are_independent():
    tracker, rec, _ = _mk_tracker()
    fn = _FakeJit()
    a = tracker.wrap("step", fn)
    a()
    a()
    # a NEW engine wrapping the same function: its first compile (cache
    # grows under ITS call) must not count as a retrace of `a`
    b = tracker.wrap("step", fn)
    fn.compile_next = True
    b()
    assert b.stats()["compiles"] == 1 and b.stats()["retraces"] == 0
    assert a.stats()["compiles"] == 1 and a.stats()["retraces"] == 0
    assert rec.records == []
    # snapshot aggregates the two wraps under one entry label
    snap = tracker.snapshot()
    assert snap["step"]["calls"] == 3 and snap["step"]["compiles"] == 2


def test_tracker_counts_functions_without_cache_probe():
    tracker, rec, _ = _mk_tracker()
    entry = tracker.wrap("plain", lambda: 7)
    assert entry() == 7
    st = entry.stats()
    assert st["calls"] == 1 and st["compiles"] == 0
    assert tracker.chrome_events() == []


def test_tracker_chrome_events_are_valid_spans():
    from dragonboat_tpu.lifecycle import validate_chrome_trace

    tracker, _, _ = _mk_tracker()
    fn = _FakeJit()
    entry = tracker.wrap("step", fn)
    entry()
    entry()
    fn.compile_next = True
    entry()
    events = tracker.chrome_events()
    assert len(events) == 2
    assert validate_chrome_trace({"traceEvents": events}) == 2
    assert events[0]["pid"] == "compile" and events[0]["tid"] == "step"
    assert events[0]["args"]["retrace"] is False
    assert events[1]["args"]["retrace"] is True


# ---------------------------------------------------------------------
# snapshot assembly, merge, exposition, strict validation


def _entries(**over):
    base = {"calls": 10, "compiles": 1, "retraces": 0,
            "compile_us_total": 500, "last_compile_us": 500}
    base.update(over)
    return base


def test_engine_snapshot_trips_watermark_on_budget():
    kp = KernelParams()
    snap = capacity.engine_snapshot(
        kp, 4, live_bytes=950, peak_bytes=960, entries={},
        budget_bytes=1000, watermark_pct=10.0, ticks=3)
    capacity.validate_capacity(snap)
    assert snap["memory_pressure"] is True and snap["headroom_pct"] < 10
    assert snap["model_predicted_bytes"] == \
        snap["model_bytes_per_group"] * 4
    assert snap["model_max_g_at_budget"] == \
        1000 // snap["model_bytes_per_group"]
    roomy = capacity.engine_snapshot(
        kp, 4, live_bytes=10, peak_bytes=10, entries={},
        budget_bytes=1 << 30, ticks=4)
    assert roomy["memory_pressure"] is False
    storm = capacity.engine_snapshot(
        kp, 4, live_bytes=10, peak_bytes=10,
        entries={"step": _entries(retraces=2)}, ticks=5)
    assert storm["retrace_storm"] is True


def test_merge_into_sums_footprints_and_tags_entries():
    base = capacity.empty_dict()
    kp = KernelParams()
    a = capacity.engine_snapshot(kp, 4, 100, 120,
                                 {"step": _entries()}, ticks=2)
    b = capacity.engine_snapshot(kp, 2, 50, 60,
                                 {"step": _entries(retraces=1)}, ticks=5)
    capacity.merge_into(base, a, engine="kernel")
    capacity.merge_into(base, b, engine="mesh")
    capacity.validate_capacity(base)
    assert base["ticks"] == 5 and base["capacity"] == 6
    assert base["bytes_in_use"] == 150 and base["bytes_peak"] == 180
    assert base["retrace_storm"] is True
    assert set(base["entries"]) == {"kernel:step", "mesh:step"}
    assert base["model_predicted_bytes"] == \
        a["model_predicted_bytes"] + b["model_predicted_bytes"]


def test_register_exposition_idempotent_and_renders_gauges():
    reg = telemetry.Registry()
    snap = capacity.engine_snapshot(
        KernelParams(), 4, 2048, 4096,
        {"step": _entries(), "fleet_stats": _entries(retraces=1)},
        ticks=1)
    capacity.register_exposition(reg, lambda: snap)
    # idempotent: a second claim with a different source is a no-op
    capacity.register_exposition(reg, lambda: None)
    expo = reg.exposition()
    assert "capacity_bytes_in_use 2048" in expo
    assert "capacity_bytes_peak 4096" in expo
    assert 'capacity_compile_total{entry="step"} 1' in expo
    assert 'capacity_retrace_total{entry="fleet_stats"} 1' in expo
    # replace=True re-points (the NodeHost merged view claims the names
    # over any engine's device-only registration)
    capacity.register_exposition(reg, lambda: None, replace=True)
    assert "capacity_bytes_in_use 0" in reg.exposition()


def test_validate_capacity_is_strict():
    good = capacity.empty_dict()
    capacity.validate_capacity(good)
    missing = capacity.empty_dict()
    del missing["bytes_peak"]
    with pytest.raises(ValueError, match="bytes_peak"):
        capacity.validate_capacity(missing)
    boolish = capacity.empty_dict()
    boolish["ticks"] = True        # bool is an int subclass: reject
    with pytest.raises(ValueError, match="ticks"):
        capacity.validate_capacity(boolish)
    extra = capacity.empty_dict()
    extra["surprise"] = 1
    with pytest.raises(ValueError, match="surprise"):
        capacity.validate_capacity(extra)
    flagless = capacity.empty_dict()
    flagless["memory_pressure"] = 0
    with pytest.raises(ValueError, match="memory_pressure"):
        capacity.validate_capacity(flagless)
    badent = capacity.empty_dict()
    badent["entries"]["step"] = dict(_entries(), junk=1)
    with pytest.raises(ValueError, match="junk"):
        capacity.validate_capacity(badent)
    shorted = capacity.empty_dict()
    shorted["entries"]["step"] = {"calls": 1}
    with pytest.raises(ValueError, match="compiles"):
        capacity.validate_capacity(shorted)


# ---------------------------------------------------------------------
# live engines: steady state compiles each entry EXACTLY once per
# geometry, at both pipeline depths


def _clear_jit_caches():
    from dragonboat_tpu.core import fleet, kernel, kstate

    for fn in (kernel.step, kernel.step_donated, fleet.fleet_stats,
               health.fleet_health):
        clear = getattr(fn, "_clear_cache", None)
        if clear is not None:
            clear()
    # an engine's collection and a lane's health row are programs over the
    # resident form, one per geometry for the process: forget them too
    from dragonboat_tpu.core import digest

    digest.digest_program.cache_clear()
    kstate.resident_program.cache_clear()


def _wait(cond, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.1)
    return cond()


def _single_host(prefix, depth, groups):
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from dragonboat_tpu.config import Config, ExpertConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost

    from test_nodehost import KVStateMachine

    nh = NodeHost(NodeHostConfig(
        raft_address=f"{prefix}-1", rtt_millisecond=5, enable_metrics=True,
        expert=ExpertConfig(kernel_log_cap=64, kernel_capacity=groups,
                            fleet_stats_every=2,
                            kernel_pipeline_depth=depth)))
    nh.start_replica({1: f"{prefix}-1"}, False, KVStateMachine, Config(
        shard_id=1, replica_id=1, election_rtt=10, heartbeat_rtt=2,
        device_resident=True))
    return nh


@pytest.mark.parametrize("depth", [0, 1])
def test_steady_state_compiles_each_entry_once_per_geometry(depth):
    """50-step steady-state run: every active jit entry compiles exactly
    once, zero retraces, no retrace_storm flight event — then a SECOND
    engine at a different geometry compiles its own entries exactly once
    without tripping the first engine's counters."""
    _clear_jit_caches()
    seq0 = flight.RECORDER.next_seq
    active = "step_donated" if depth > 0 else "step"
    idle = "step" if depth > 0 else "step_donated"
    nh = _single_host(f"cap{depth}", depth, groups=4)
    try:
        assert _wait(lambda: nh.get_leader_id(1)[1], 45), "no leader"
        eng = nh.kernel_engine
        assert _wait(lambda: eng._capacity_seq >= 25, 60), \
            "fewer than 50 steady-state steps"
        with eng.mu:
            snap = eng.last_capacity
        capacity.validate_capacity(snap)
        ent = snap["entries"]
        assert ent[active]["compiles"] == 1, ent[active]
        assert ent[active]["retraces"] == 0
        assert ent[active]["calls"] >= 50
        assert ent[idle]["calls"] == 0
        # the collection: ONE program (core/digest.py), not three
        assert ent["fleet_digest"]["compiles"] == 1, ent["fleet_digest"]
        assert ent["fleet_digest"]["retraces"] == 0
        assert ent["fleet_digest"]["calls"] == eng._capacity_seq
        assert set(ent) == {"step", "step_donated", "fleet_digest"}
        assert snap["retrace_storm"] is False
        assert snap["ticks"] == eng._capacity_seq
        assert snap["bytes_in_use"] > 0
        assert snap["model_predicted_bytes"] == snap["bytes_in_use"], \
            "contracts model must match the resident trees exactly"
    finally:
        # stop engine 1 before engine 2 compiles the shared jit entries:
        # cache growth is attributed to whichever call window it lands
        # in, so an overlapping compile would smear into eng1's counters
        nh.close()

    # second geometry: a fresh engine at a different capacity pays its
    # own single compile per entry — no retrace anywhere
    nh2 = _single_host(f"cap{depth}b", depth, groups=8)
    try:
        assert _wait(lambda: nh2.get_leader_id(1)[1], 45)
        eng2 = nh2.kernel_engine
        assert _wait(lambda: eng2._capacity_seq >= 5, 60)
        with eng2.mu:
            snap2 = eng2.last_capacity
        assert snap2["entries"][active]["compiles"] == 1
        assert snap2["entries"][active]["retraces"] == 0
        assert snap2["retrace_storm"] is False
    finally:
        nh2.close()
    # the first engine's counters are untouched by engine 2's compiles
    # (per-wrap independence)
    with eng.mu:
        snap = eng.last_capacity
    assert snap["entries"][active]["compiles"] == 1
    assert snap["entries"][active]["retraces"] == 0
    storms = [r for r in flight.RECORDER.tail()
              if r["kind"] == flight.RETRACE_STORM
              and r["seq"] >= seq0]
    assert storms == [], storms


@pytest.mark.parametrize("case", ["env_set", "env_unset", "veto"])
def test_compile_cache_placement(monkeypatch, tmp_path, case):
    """The one compile-cache helper (hostenv; conftest, the benchmark,
    the scripts and ExpertConfig.compile_cache all route through it):
    JAX_COMPILATION_CACHE_DIR set -> that directory, and code sets
    none; unset -> the fixed <checkout>/.jax_cache;
    DRAGONBOAT_TPU_COMPILE_CACHE=0 -> None and nothing is set."""
    import jax

    from dragonboat_tpu import hostenv

    placed = str(tmp_path / "placed")
    monkeypatch.delenv("DRAGONBOAT_TPU_COMPILE_CACHE", raising=False)
    if case == "env_unset":
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    if case == "veto":
        monkeypatch.setenv("DRAGONBOAT_TPU_COMPILE_CACHE", "0")
    before = getattr(jax.config, hostenv.CACHE_DIR_OPTION)
    try:
        got = hostenv.enable_compile_cache()
        after = getattr(jax.config, hostenv.CACHE_DIR_OPTION)
    finally:
        jax.config.update(hostenv.CACHE_DIR_OPTION, before)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if case == "env_set":
        # the env var was set after jax started, so had code set a
        # directory the config would have moved
        assert got == placed and after == before
    elif case == "env_unset":
        assert got == after == os.path.join(checkout, ".jax_cache")
    else:
        assert got is None and after == before
        assert hostenv.cache_entry_count() == 0


_RELOAD_CHILD = """
import hashlib, sys
import jax
import numpy as np
jax.config.update("jax_platforms", "cpu")
from dragonboat_tpu import hostenv
hits = []
assert hostenv.enable_compile_cache(min_compile_secs=0.0) == sys.argv[1]
jax.monitoring.register_event_listener(
    lambda ev, **kw: hits.append(ev)
    if ev == "/jax/compilation_cache/cache_hits" else None)
import dataclasses, functools
from dragonboat_tpu.bench_loop import _self_input, bench_params, make_cluster
from dragonboat_tpu.core.kernel import step_donated
from dragonboat_tpu.core.kstate import empty_inbox
from dragonboat_tpu.core.router import route
kp = dataclasses.replace(
    bench_params(3, platform="cpu"), log_cap=64, msg_entries=4,
    proposal_cap=4, apply_batch=16)
state, box = make_cluster(kp, 4, 3), empty_inbox(kp, 12)
feed = jax.jit(functools.partial(_self_input, kp), static_argnums=(3, 4))
rt = jax.jit(functools.partial(route, kp, 3))
for _ in range(40):
    inp = feed(state, True, True, None, False, 0)
    state, out = step_donated(kp, state, box, inp)
    box = rt(out)
h = hashlib.sha256()
for leaf in jax.tree_util.tree_leaves(state):
    h.update(np.asarray(leaf).tobytes())
print("RELOAD", h.hexdigest(), int(np.asarray(state.committed).max()),
      len(hits))
"""


def test_donated_cache_reload(tmp_path):
    """A DONATED executable round-trips through the persistent cache
    soundly on the installed jax: process 1 compiles step_donated fresh
    into an empty cache, process 2 runs it from the cache (cache hits
    > 0) — both end 40 self-driving steps in bitwise the same state, and
    every result buffer reads back.  The cache is placed through
    JAX_COMPILATION_CACHE_DIR, so the children also show that the helper
    then sets no directory of its own."""
    import subprocess

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = str(tmp_path / "cache")
    env = dict(os.environ, PYTHONPATH=checkout, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    env.pop("XLA_FLAGS", None)
    env.pop("DRAGONBOAT_TPU_COMPILE_CACHE", None)

    def run():
        r = subprocess.run(
            [sys.executable, "-c", _RELOAD_CHILD, cache],
            env=env, capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RELOAD")][-1].split()
        return line[1], int(line[2]), int(line[3])

    fresh = run()
    assert any("step_donated" in n for n in os.listdir(cache))
    reloaded = run()
    assert fresh[1] > 0, "no commits: the comparison would be vacuous"
    assert fresh[2] == 0 and reloaded[2] > 0, (fresh, reloaded)
    assert fresh[:2] == reloaded[:2], (fresh, reloaded)


# ---------------------------------------------------------------------
# endpoints + doctor CLIs (synthetic sources, no cluster)


def _mk_server(cap_snapshot):
    from dragonboat_tpu.server.metrics_http import MetricsServer

    state = {"cap": cap_snapshot}
    info = {"node_host_id": "nhid-test", "raft_address": "t-1",
            "health": health.empty_dict(),
            "shards": [{"shard_id": 1, "replica_id": 2, "leader_id": 3,
                        "term": 4, "is_leader": False, "last_applied": 5,
                        "membership": {"addresses": {1: "t-1"},
                                       "non_votings": {}, "witnesses": {},
                                       "config_change_id": 1},
                        "resident": "host"}]}
    srv = MetricsServer([], address="127.0.0.1:0",
                        health_source=health.empty_dict,
                        capacity_source=lambda: state["cap"],
                        info_source=lambda: dict(
                            info, capacity=state["cap"]))
    return srv, state


def test_debug_capacity_roundtrip_and_healthz_degradation():
    srv, state = _mk_server(capacity.empty_dict())
    try:
        got = json.loads(urllib.request.urlopen(
            f"http://{srv.address}/debug/capacity", timeout=5).read())
        capacity.validate_capacity(got)
        assert got == json.loads(json.dumps(state["cap"]))
        ok = urllib.request.urlopen(f"http://{srv.address}/healthz",
                                    timeout=5)
        assert ok.status == 200 and ok.read() == b"ok\n"
        # memory pressure AND retrace storm each degrade /healthz
        for flag in ("memory_pressure", "retrace_storm"):
            bad = capacity.empty_dict()
            bad[flag] = True
            state["cap"] = bad
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"http://{srv.address}/healthz",
                                       timeout=5)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["capacity"]["tripped"] == [flag]
        state["cap"] = capacity.empty_dict()
        assert urllib.request.urlopen(f"http://{srv.address}/healthz",
                                      timeout=5).status == 200
    finally:
        srv.close()


def test_metrics_dump_capacity_and_fleet_doctor(capsys):
    import sys

    md = _load_script("metrics_dump")
    fd = _load_script("fleet_doctor")
    srv, state = _mk_server(capacity.empty_dict())
    argv = sys.argv
    try:
        # clean snapshot: validates, exits 0
        sys.argv = ["metrics_dump.py", srv.address, "--capacity"]
        assert md.main() == 0
        out = capsys.readouterr()
        assert "ok: 0 compile entrie(s)" in out.err
        assert json.loads(out.out)["bytes_in_use"] == 0
        # doctor renders the capacity block and exits 0
        sys.argv = ["fleet_doctor.py", srv.address]
        assert fd.main() == 0
        out = capsys.readouterr().out
        assert "capacity: OK" in out
        # degraded on retrace storm: both CLIs exit 1
        bad = capacity.empty_dict()
        bad["retrace_storm"] = True
        bad["entries"]["kernel:step"] = _entries(retraces=3)
        state["cap"] = bad
        sys.argv = ["metrics_dump.py", srv.address, "--capacity"]
        assert md.main() == 1
        out = capsys.readouterr()
        assert "degraded: retrace_storm" in out.err
        sys.argv = ["fleet_doctor.py", srv.address]
        assert fd.main() == 1
        out = capsys.readouterr().out
        assert "DEGRADED (retrace_storm)" in out
        assert "kernel:step" in out
        # memory pressure degrades the same way
        bad2 = capacity.empty_dict()
        bad2["memory_pressure"] = True
        state["cap"] = bad2
        sys.argv = ["fleet_doctor.py", srv.address]
        assert fd.main() == 1
        capsys.readouterr()
        # schema drift is exit 1 (dump) / 2 (doctor), not a crash
        state["cap"] = dict(capacity.empty_dict(), surprise=1)
        sys.argv = ["metrics_dump.py", srv.address, "--capacity"]
        assert md.main() == 1
        assert "schema validation failed" in capsys.readouterr().err
        sys.argv = ["fleet_doctor.py", srv.address]
        assert fd.main() == 2
        capsys.readouterr()
    finally:
        sys.argv = argv
        srv.close()


def test_trace_endpoint_merges_compile_spans():
    from dragonboat_tpu.lifecycle import validate_chrome_trace
    from dragonboat_tpu.server.metrics_http import MetricsServer

    tracker, _, _ = _mk_tracker()
    fn = _FakeJit()
    tracker.wrap("step", fn)()
    srv = MetricsServer([], address="127.0.0.1:0",
                        compile_tracker=tracker)
    try:
        trace = json.loads(urllib.request.urlopen(
            f"http://{srv.address}/trace", timeout=5).read())
        assert validate_chrome_trace(trace) >= 1
        compiles = [e for e in trace["traceEvents"]
                    if e.get("cat") == "compile"]
        assert len(compiles) == 1
        assert compiles[0]["name"] == "compile:step"
    finally:
        srv.close()


# ---------------------------------------------------------------------
# crossings timed per tag; every XLA compile counted with its phase

def test_sanctioned_crossing_times_its_extent_per_tag():
    """``device_crossing_us{tag=...}``: each sanctioned scope observes
    its own extent on the injected clock, one histogram child per tag."""
    ticks = iter([100, 130, 200, 290, 300, 305])
    reg = telemetry.Registry()
    meter = capacity.TransferMeter(clock=ticks.__next__, registry=reg)
    for tag in ("round_down", "round_down", "round_up"):
        with meter.sanctioned(tag):
            pass
    snap = reg.snapshot()
    assert snap["device_crossing_us.count{tag=round_down}"] == 2
    assert snap["device_crossing_us.sum{tag=round_down}"] == 120.0
    assert snap["device_crossing_us.count{tag=round_up}"] == 1
    assert snap["device_crossing_us.sum{tag=round_up}"] == 5.0
    assert meter.counts() == {"round_down": 2, "round_up": 1}


def test_crossing_is_timed_under_a_guard_too():
    import jax.numpy as jnp
    import numpy as np

    reg = telemetry.Registry()
    meter = capacity.TransferMeter(registry=reg)
    with meter.guard():
        with meter.sanctioned("round_down"):
            np.asarray(jnp.zeros((3,)))
    assert reg.snapshot()["device_crossing_us.count{tag=round_down}"] == 1


def test_compile_listener_counts_an_untracked_eager_gather_by_phase():
    """An eager jax expression no ``TRACKER`` entry wraps (the engine's
    per-count ``state.lt[idx]`` gather was one until the save window
    rode the round's download; the benchmark's row-fetch warm-up still
    is): the process-wide listener counts its compile and labels it with
    the round phase of the thread that compiled (``none`` outside a
    round)."""
    import jax.numpy as jnp
    import numpy as np

    from dragonboat_tpu import tracing

    def compiles(phase):
        return telemetry.GLOBAL.snapshot().get(
            f"xla_compiles{{phase={phase}}}", 0)

    table = jnp.arange(1237 * 3).reshape(1237, 3)
    before = {p: compiles(p) for p in ("fetch", "none")}
    tracker_before = capacity.TRACKER.snapshot()
    tracing._thread.phase = "fetch"
    try:
        rows = np.asarray(table[jnp.asarray(np.arange(13, dtype=np.int32))])
    finally:
        tracing._thread.phase = "none"
    assert rows.shape == (13, 3)
    assert compiles("fetch") > before["fetch"]
    assert capacity.TRACKER.snapshot() == tracker_before    # untracked
    np.asarray(table[jnp.asarray(np.arange(17, dtype=np.int32))])
    assert compiles("none") > before["none"]
    us = telemetry.GLOBAL.snapshot()["xla_compile_us.sum{phase=fetch}"]
    assert us > 0


def test_compile_listener_ignores_other_events():
    reg = telemetry.Registry()
    listener = capacity.CompileListener(registry=reg,
                                        phase_of=lambda: "upload")
    listener("/jax/core/compile/jaxpr_trace_duration", 0.5)
    assert "xla_compiles{phase=upload}" not in reg.snapshot()
    listener(capacity.CompileListener.EVENT, 0.25, fun_name="step")
    snap = reg.snapshot()
    assert snap["xla_compiles{phase=upload}"] == 1
    assert snap["xla_compile_us.sum{phase=upload}"] == 250000.0


# ---------------------------------------------------------------------
# the bytes a crossing moved; the capacity a deployment states

def test_sanctioned_crossing_counts_the_bytes_its_site_names():
    """``device_crossing_bytes{tag=...}``: the arrays the site hands the
    scope's ``moved``, host or device side, from ``nbytes`` alone; a scope
    that names none adds no sample."""
    import jax.numpy as jnp
    import numpy as np

    reg = telemetry.Registry()
    meter = capacity.TransferMeter(registry=reg)
    up = np.zeros((1024, 231), np.int32)
    with meter.sanctioned("round_up") as crossing:
        crossing.moved(up)
    with meter.sanctioned("round_down") as crossing:
        crossing.moved(jnp.zeros((1024, 345), jnp.int32))
    with meter.sanctioned("digest_down") as crossing:
        crossing.moved(np.zeros((16,), np.int32))
        crossing.moved(np.zeros((8,), np.int32), np.zeros((2, 4), bool))
    with meter.sanctioned("health_row"):
        pass
    snap = reg.snapshot()
    assert snap["device_crossing_bytes{tag=round_up}"] == 1024 * 231 * 4
    assert snap["device_crossing_bytes{tag=round_down}"] == 1024 * 345 * 4
    assert snap["device_crossing_bytes{tag=digest_down}"] == 64 + 32 + 8
    assert "device_crossing_bytes{tag=health_row}" not in snap
    assert snap["device_crossing_us.count{tag=health_row}"] == 1


def _host_config(**expert):
    from dragonboat_tpu.config import ExpertConfig, NodeHostConfig

    return NodeHostConfig(raft_address="capacity-stated", rtt_millisecond=5,
                          expert=ExpertConfig(**expert))


@pytest.mark.parametrize("lanes", [0, -1, 2.5, True, None, "4096"])
def test_a_kernel_capacity_that_is_no_positive_whole_number_is_refused(lanes):
    from dragonboat_tpu.config import ConfigError
    from dragonboat_tpu.nodehost import NodeHost

    with pytest.raises(ConfigError, match="kernel_capacity"):
        NodeHost(_host_config(kernel_capacity=lanes), auto_run=False)


def test_a_kernel_capacity_over_the_stated_device_budget_is_refused():
    """By the field's name, with what fits: 4,096 lanes keep 26.4 MB
    resident (6,452 B a lane at the default geometry)."""
    from dragonboat_tpu.config import ConfigError
    from dragonboat_tpu.nodehost import NodeHost

    per_lane = capacity.resident_bytes_per_group(KernelParams(
        num_peers=5, log_cap=1024, inbox_cap=8, msg_entries=8,
        proposal_cap=8, readindex_cap=4))
    assert per_lane == 6452
    with pytest.raises(ConfigError) as e:
        NodeHost(_host_config(kernel_capacity=4096,
                              capacity_device_budget_bytes=4095 * per_lane),
                 auto_run=False)
    assert "kernel_capacity 4096" in str(e.value)
    assert "capacity_device_budget_bytes" in str(e.value)
    assert "4095 lanes fit" in str(e.value)


@pytest.mark.parametrize("budget", [0, 4096 * 6452, int(16e9)])
def test_a_kernel_capacity_of_4096_is_accepted(budget):
    """With no budget stated (0: the backend's is not known before the
    backend is), with exactly its footprint, and with a chip's 16 GB."""
    from dragonboat_tpu.nodehost import NodeHost

    nh = NodeHost(_host_config(kernel_capacity=4096,
                               capacity_device_budget_bytes=budget),
                  auto_run=False)
    try:
        assert nh.config.expert.kernel_capacity == 4096
    finally:
        nh.close()


def test_a_full_engine_names_its_capacity_and_the_field():
    from dragonboat_tpu.engine.kernel_engine import KernelEngine

    eng = KernelEngine(KernelParams(log_cap=64), 2, send_message=None)
    eng._free.clear()
    with pytest.raises(RuntimeError,
                       match=r"at capacity \(2 lanes, "
                             r"ExpertConfig\.kernel_capacity\)"):
        eng.add_shard(object(), None)
    # an engine states its height under its own label, and takes it back
    gauge = f"engine_lanes{{what=capacity,engine={eng.label}}}"
    assert telemetry.GLOBAL.snapshot()[gauge] == 2
    eng.close()
    assert telemetry.GLOBAL.snapshot()[gauge] == 0
