"""Test config: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; shardings are validated on a
host-platform device mesh (the driver separately dry-runs multichip via
``__graft_entry__.dryrun_multichip``).
"""

import collections
import os
import sys

import pytest

# unit tests always run on the CPU backend with 8 virtual devices; this is
# the only place in the tree that forces a platform
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# persistent compile cache: the batched step kernel takes ~10-30s to compile;
# cache it across pytest runs and across xdist workers
from dragonboat_tpu.hostenv import enable_compile_cache  # noqa: E402

enable_compile_cache()


def _set_affinity_all_threads(cpus) -> None:
    """sched_setaffinity acts on ONE thread; apply it to every thread this
    process has (a thread that exits between the listing and the call is
    skipped)."""
    for tid in os.listdir("/proc/self/task"):
        try:
            os.sched_setaffinity(int(tid), cpus)
        except OSError:
            pass


@pytest.fixture
def one_core():
    """Keep the test's threads on one core (under xdist, worker gwN takes
    core N), for tests whose deadlines were sized on a one-core box.  The
    engine's host threads — workers, apply pools, transports, ~150 per
    cluster — convoy on the interpreter lock when the scheduler spreads
    them over many cores: the same 4-host mesh start-up took 48 s pinned to
    one core and 265 s on eight (PERF.md, PR 23).  Threads the test starts
    inherit the pin from the thread that spawns them, and some outlive the
    test (XLA's compile pool, process-global meters), so the teardown
    restores the affinity of EVERY thread of the process, not only the
    caller's."""
    if not hasattr(os, "sched_setaffinity"):
        yield
        return
    before = os.sched_getaffinity(0)
    cpus = sorted(before)
    gw = os.environ.get("PYTEST_XDIST_WORKER", "gw0")[2:]
    _set_affinity_all_threads(
        {cpus[int(gw) % len(cpus) if gw.isdigit() else 0]})
    try:
        yield
    finally:
        _set_affinity_all_threads(before)


# -- one-retry for timing-sensitive E2E modules -------------------------------
# The multi-NodeHost E2E tests run dozens of engine threads against
# wall-clock deadlines and occasionally miss them under full-suite load.  A
# failed test from these modules is retried once — a deterministic
# regression still fails twice and stays red.  Every other module (kernel
# differentials, model check, lint, codecs) gets no second attempt: a test
# there that fails one run in two is a bug to find, not load.

_RETRY_MODULES = (
    "test_nodehost", "test_node_ops", "test_tcp_transport", "test_gossip",
    "test_durable_nodehost", "test_monkey", "test_vfs",
    "test_snapshot_stream", "test_kernel_engine", "test_tools",
    "test_history", "test_tan", "test_encoded", "test_examples",
    "test_chaos_faults", "test_chaos_schedules", "test_health",
    # added in PR 23: its control loop keys on a 30 ms step-latency EWMA and
    # 30-45 s windows; beside five busy workers on an 8-core box seed 11
    # failed 1 run in 3-4 (pinned or not), either with no transfer planned
    # or with followers one entry behind for the whole 45 s convergence
    # window (PERF.md, Open questions).  It also runs last (below).
    "test_chaos_hotspot",
    # added in PR 35 for its part B alone (three NodeHosts whose clocks
    # differ by 30%, through both paths, asserting that NO term moves over
    # ~1.5 s of wall time: beside busy workers a starved follower's
    # election is load, not the subject); part A is the deterministic form
    # of the same story and fails twice where it fails
    "test_quiesce_group",
)

# module -> number of tests that needed the second attempt, THIS process.
# The silent-rerun policy above hides flake from the pass/fail signal, so
# this tally is the visibility valve: the terminal summary prints it,
# tests/.retry_report.json accumulates it across run_tests.sh's chunked
# pytest processes, and a module leaning on the crutch more than
# _RETRY_LIMIT times fails the run — "flaky but green" may not trend.
_RETRY_STATS: dict = {}
_RETRY_LIMIT = 3
_RETRY_REPORT = os.path.join(os.path.dirname(__file__),
                             ".retry_report.json")


def pytest_runtest_protocol(item, nextitem):
    from _pytest.runner import runtestprotocol

    if item.module.__name__ not in _RETRY_MODULES:
        return None
    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        mod = item.module.__name__
        _RETRY_STATS[mod] = _RETRY_STATS.get(mod, 0) + 1
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for r in reports:
        item.ihook.pytest_runtest_logreport(report=r)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True


_RETRY_MERGED: dict = {}     # computed once at sessionfinish


def _merged_retry_report() -> dict:
    """This process's tally merged into the on-disk report, computed at
    most once (sessionfinish rewrites the file, so a second merge would
    double-count this process).  Merging is opt-in via
    DBT_RETRY_REPORT_MERGE (run_tests.sh removes the file at run start
    and sets the flag for its chunked pytest processes); a bare
    ``pytest`` invocation overwrites, so a stale file from an old run
    can never fail a fresh one."""
    import json

    if _RETRY_MERGED.get("done"):
        return _RETRY_MERGED["report"]
    merged: dict = {}
    if os.environ.get("DBT_RETRY_REPORT_MERGE") == "1":
        try:
            with open(_RETRY_REPORT) as f:
                merged = {str(k): int(v) for k, v in json.load(f).items()}
        except (OSError, ValueError):
            merged = {}
    for mod, n in _RETRY_STATS.items():
        merged[mod] = merged.get(mod, 0) + n
    _RETRY_MERGED["done"] = True
    _RETRY_MERGED["report"] = merged
    return merged


def pytest_sessionfinish(session, exitstatus):
    import json

    merged = _merged_retry_report()
    if not merged and not os.path.exists(_RETRY_REPORT):
        return
    try:
        with open(_RETRY_REPORT, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
    except OSError:
        pass
    if exitstatus == 0 and any(n > _RETRY_LIMIT for n in merged.values()):
        session.exitstatus = 1


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    merged = _merged_retry_report()
    if not merged:
        return
    terminalreporter.section("flaky-retry tally")
    for mod in sorted(merged):
        here = _RETRY_STATS.get(mod, 0)
        over = " OVER LIMIT" if merged[mod] > _RETRY_LIMIT else ""
        terminalreporter.write_line(
            f"{mod}: {merged[mod]} retried test(s)"
            f" ({here} this process, limit {_RETRY_LIMIT}){over}")
    if any(n > _RETRY_LIMIT for n in merged.values()):
        terminalreporter.write_line(
            "FAILING RUN: retry budget exceeded — fix the flake or the "
            "test; the silent rerun is a crutch, not a policy.")


# -- a failed test's hosts do not outlive it ----------------------------------
# An end-to-end test that fails between building its cluster and its own
# ``finally`` leaves the hosts running: a few dozen engine, tick and apply
# threads each, for the rest of the worker's life.  Every later test of that
# worker then shares the interpreter with them (test_model_check's fast
# scope: 10 s alone, 261 s beside six hosts left open), the retry above runs
# beside the first attempt's hosts under the same addresses, and under
# ``--dist loadfile`` the files queued behind crawl until the run's time
# limit cuts them (CHANGES.md, PR 28).  After a failed test's own fixtures
# have finished, whatever NodeHost is still open in the process is closed
# here (no fixture of a wider scope than one test keeps a host; one that
# comes to would have to be spared).
_FAILED = pytest.StashKey[bool]()


def _close_hosts_left_open() -> int:
    import gc

    from dragonboat_tpu.nodehost import NodeHost

    closed = 0
    for obj in gc.get_objects():
        try:
            if not isinstance(obj, NodeHost) or getattr(obj, "_stopped", True):
                continue
        except Exception:       # noqa: BLE001 — a proxy that resists inspection
            continue
        closed += 1
        try:
            obj.close()
        except Exception as e:  # noqa: BLE001 — best effort, say so
            sys.stderr.write(f"\n[conftest] closing a left-open host: {e!r}\n")
    return closed


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    if outcome.get_result().failed:
        item.stash[_FAILED] = True


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item, nextitem):
    yield
    if item.stash.get(_FAILED, False):
        item.stash[_FAILED] = False     # a retry answers for itself
        n = _close_hosts_left_open()
        if n:
            sys.stderr.write(f"\n[conftest] {item.nodeid} failed and left {n} "
                             f"NodeHost(s) open: closed\n")


_age_counter = {"n": 0, "cleared": 0}

# The "late-process XLA abort" (run_tests.sh header) ROOT CAUSE,
# diagnosed 2026-07-31 by sampling /proc/self/maps across a full run:
# every jitted executable pins mmap'd code/cache segments in jax's
# process-wide caches, and this suite compiles hundreds of distinct
# kernel geometries — the map count crosses vm.max_map_count (65,530
# here) at almost exactly the historical crash position (64,733 maps at
# test 331 vs the deterministic ~340-test SIGABRT/SIGSEGV).  When the
# next compile/cache-load can't mmap, XLA dies inside
# backend_compile/deserialize.  The fence below drops the in-process
# executable caches before the limit; the persistent on-disk compile
# cache makes the re-loads cheap.  Not a product concern at deployment
# shapes (a serving host compiles a handful of geometries), but any
# long-lived process creating hundreds would want the same guard.
_MAP_FENCE = int(os.environ.get("DBT_MAP_FENCE", "45000"))


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return f.read().count(b"\n")
    except OSError:
        return -1


def pytest_runtest_setup(item):
    _age_counter["n"] += 1
    n = _age_counter["n"]
    if _MAP_FENCE and n % 5 == 0:
        maps = _map_count()
        if maps > _MAP_FENCE:
            jax.clear_caches()
            _age_counter["cleared"] += 1
            sys.stderr.write(
                f"\n[conftest] map-count fence: {maps} maps > "
                f"{_MAP_FENCE}, cleared jax caches "
                f"(#{_age_counter['cleared']})\n")
    # DBT_AGE_LOG=1: append (test#, rss, maps, threads, fds) every 10
    # tests — the diagnostic curve this fence was built from
    if os.environ.get("DBT_AGE_LOG") != "1":
        return
    if n % 10 != 1:
        return
    import resource
    import threading

    try:
        fds = len(os.listdir("/proc/self/fd"))
    except OSError:
        fds = -1
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    with open("/tmp/dbt_age.log", "a") as f:
        f.write(f"{n} rss_mb={rss // 1024} maps={_map_count()} "
                f"threads={threading.active_count()}"
                f" fds={fds} test={item.nodeid}\n")


def pytest_configure(config):
    """Under ``--dist loadfile`` hand the files out in collection order,
    which ``pytest_collection_modifyitems`` below decides.  xdist 3.8
    otherwise sorts them by their NUMBER of tests, most first, on its own:
    that undid the order below without a word, and sent the one-test
    ``test_zz_mesh_scale`` (190-300 s pinned to one core) out last of all.
    It started when 99% of the tests were done and ran on alone beside
    five idle workers for a third of the whole run's time; beside other
    load the run was cut at its time limit there (CHANGES.md, PR 28)."""
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


@pytest.hookimpl(trylast=True)      # after ``-m`` has deselected
def pytest_collection_modifyitems(session, config, items):
    """Big-shape jit tests run FIRST.

    Compiling — or even cache-LOADING — the large kernel executables
    (1k-lane engines, the 8-device mesh) after ~340 tests of process
    aging aborts inside XLA's compile/deserialize path (diagnosed
    2026-07-31: deterministic SIGABRT/SIGSEGV at the same collection
    position across four full-suite runs, while every subset and a
    fresh process pass).  A fresh process handles the big shapes
    reliably, so they go to the front of the run.  They are also the
    longest tests of the suite, each on one worker for minutes: begun
    first, they end while the other workers still have files to take.

    With them go the two files that rehearse whole benchmark cells inside
    the worker's process and then read process-wide counters (the compile
    tracker's rows add up over every engine the process ever had): in a
    fresh worker nothing an earlier file left can count against them
    (``test_last_line_shape`` read a retrace 2 runs of 4 that gave it
    test_round_budget's worker, 0 of 6 otherwise; CHANGES.md, PR 28).

    The other files follow by their number of tests, most first (the
    order xdist would have given them): the many short tests come before
    the few-test end-to-end files, so the workers run dry on files of a
    minute, not of five."""
    first = ("test_zz_", "test_benchmark_rehearsal", "test_benchmark_mesh_cell")
    per_file = collections.Counter(it.path for it in items)

    def order(it):
        if it.path.stem.startswith(first):
            return (0, 0)
        # ...and the most load-sensitive file runs LAST: under ``--dist
        # loadfile`` the last file goes to the first worker that runs dry,
        # when most of the others are draining or idle, not beside five
        # busy ones
        if it.path.stem == "test_chaos_hotspot":
            return (2, 0)
        return (1, -per_file[it.path])

    items.sort(key=order)
