"""Transfer-boundary pass (analysis/transfer.py): every TB rule must
fire on a tampered fixture and stay silent on the clean one, the real
repo must be clean, the static ledger must match the live METER counts
at depth 0 and depth 1, the budget gate must catch tampering, the
dynamic-leg cache must invalidate on a jax version change, and the
runtime guard must catch an actual host round-trip through the dispatch
seam."""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

from dragonboat_tpu.analysis import transfer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# A minimal transfer-clean repo: one dispatch entry pair whose every
# crossing is declared, staged through a to_device builder, synced only
# at the declared SYNC_POINTS qualname, and sized through a CONTRACTS
# literal carried in the engine fixture itself.
DISPATCH_FIX = '''\
SYNC_POINTS = {
    "Engine.pending": {"tag": "pending", "why": "deferred device count"},
}
DISPATCH_ENTRIES = {
    "step": {
        "module": "core/kernel.py",
        "function": "step",
        "donated": False,
        "waiver": "depth-0 oracle must leave inputs readable",
    },
    "step_donated": {
        "module": "core/kernel.py",
        "function": "step_donated",
        "donated": True,
        "waiver": "",
    },
}
TRANSFER_LEDGER = {
    "step": {
        "resident": ("ShardState",),
        "up": (
            {"value": ("Inbox", "StepInput"), "packed": True,
             "param": "up", "site": "_RoundStaging.to_device",
             "tag": "round_up", "per_step": True},
        ),
        "down": (
            {"value": ("[G, 8] bool", "StepOutput"), "packed": True,
             "site": "Engine._process_outputs",
             "tag": "round_down", "per_step": True},
            {"value": "[1, CAP] i32", "site": "Engine.fetch_row",
             "tag": "save_window_row", "masked": True},
        ),
    },
    "step_donated": {
        "resident": ("ShardState",),
        "up": (
            {"value": ("Inbox", "StepInput"), "packed": True,
             "param": "up", "site": "_RoundStaging.to_device",
             "tag": "round_up", "per_step": True},
        ),
        "down": (
            {"value": ("[G, 8] bool", "StepOutput"), "packed": True,
             "site": "Engine._process_outputs",
             "tag": "round_down", "per_step": True},
            {"value": "[1, CAP] i32", "site": "Engine.fetch_row",
             "tag": "save_window_row", "masked": True},
        ),
    },
    "_control": (
        {"dir": "up", "value": "ShardState", "site": "Engine.inject",
         "tag": "inject_up"},
        {"dir": "down", "value": "[] i32", "site": "Engine.pending",
         "tag": "pending"},
    ),
}
'''

ENGINE_FIX = '''\
import numpy as np
import jax.numpy as jnp

CONTRACTS = {
    "ShardState": {
        "term": "[G] i32 part=G",
        "log": "[G, CAP] i32 part=G",
    },
    "Inbox": {
        "mtype": "[G, K] i32 part=G",
        "ent": "[G, K, E] i32 part=G",
    },
    "StepInput": {
        "prop_valid": "[G, B] bool part=G",
    },
    "StepOutput": {
        "resp": "[G, K] i32 part=G",
        "flags": "[G, 8] bool part=G",
    },
}


class _RoundStaging:
    def to_device(self):
        return jnp.asarray(self.up)


class Engine:
    def inject(self, rows):
        self.state = jnp.asarray(rows)

    def pending(self):
        p = self._dispatch.dispatch(None, None, None, False)
        return int(p)

    def _process_outputs(self, out):
        return np.asarray(out)

    def fetch_row(self, ring, g):
        return np.asarray(ring[g])
'''

KERNEL_FIX = '''\
def step(kp, state, up):
    return state


def step_donated(kp, state, up):
    return state
'''


def _mini_repo(tmp_path, dispatch=DISPATCH_FIX, engine=ENGINE_FIX,
               kernel=KERNEL_FIX, budget=None):
    eng = tmp_path / "dragonboat_tpu" / "engine"
    eng.mkdir(parents=True)
    (eng / "dispatch.py").write_text(dispatch)
    (eng / "engine.py").write_text(engine)
    core = tmp_path / "core"
    core.mkdir()
    (core / "kernel.py").write_text(kernel)
    if budget is not None:
        bp = tmp_path / "dragonboat_tpu" / "analysis"
        bp.mkdir(parents=True, exist_ok=True)
        (bp / "transfer_budget.json").write_text(json.dumps(budget))
    return str(tmp_path)


def _run_fix(root):
    return transfer.run(root, files=[
        "dragonboat_tpu/engine/dispatch.py",
        "dragonboat_tpu/engine/engine.py",
    ])


def _rules(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------------ clean


def test_clean_fixture_has_no_findings(tmp_path):
    assert _run_fix(_mini_repo(tmp_path)) == []


def test_real_repo_static_is_clean():
    assert transfer.run(REPO, dynamic=False) == []


# ------------------------------------------------------------------ TB001


def test_tb001_entry_without_ledger_section(tmp_path):
    root = _mini_repo(tmp_path, dispatch=DISPATCH_FIX.replace(
        '"step_donated": {\n        "resident"', '"ghosted": {\n'
        '        "resident"'))
    fs = _run_fix(root)
    assert any(f.rule == "TB001" and "'step_donated'" in f.message
               and "no TRANSFER_LEDGER section" in f.message for f in fs)
    # ...and the renamed section matches no entry: stale declaration
    assert any(f.rule == "TB001" and "'ghosted'" in f.message
               and "stale" in f.message for f in fs)


def test_tb001_uncovered_entry_parameter(tmp_path):
    # a fourth array parameter appears on the jit entry with no
    # resident/upload declaration covering it
    root = _mini_repo(tmp_path, kernel=KERNEL_FIX.replace(
        "def step(kp, state, up):",
        "def step(kp, state, up, sideband):"))
    fs = _run_fix(root)
    assert any(f.rule == "TB001" and "'sideband'" in f.message
               and "undeclared host->device crossing" in f.message
               for f in fs)


def test_tb001_stale_site_qualname(tmp_path):
    root = _mini_repo(tmp_path, dispatch=DISPATCH_FIX.replace(
        '"site": "Engine.inject"', '"site": "Engine.vanished"'))
    fs = _run_fix(root)
    assert any(f.rule == "TB001" and "'Engine.vanished'" in f.message
               for f in fs)


def test_tb001_unsizable_row_value(tmp_path):
    root = _mini_repo(tmp_path, dispatch=DISPATCH_FIX.replace(
        '{"dir": "down", "value": "[] i32"',
        '{"dir": "down", "value": "[Q] i32"'))
    fs = _run_fix(root)
    assert any(f.rule == "TB001" and "cannot be sized" in f.message
               for f in fs)


def test_tb001_non_literal_ledger(tmp_path):
    root = _mini_repo(tmp_path, dispatch=(
        "SYNC_POINTS = {}\n"
        "TRANSFER_LEDGER = dict(step=1)\n"))
    fs = _run_fix(root)
    assert any(f.rule == "TB001" and "pure literal" in f.message
               for f in fs)


# ------------------------------------------------------------------ TB002


_PERMISSIVE = {
    "config": dict(transfer.DEFAULT_CONFIG),
    "budget": {
        "serial": {"up_bytes_per_step": 10**12,
                   "down_bytes_per_step": 10**12,
                   "up_crossings_per_step": 100,
                   "down_crossings_per_step": 100},
        "mesh": {"up_bytes_per_step": 10**12,
                 "down_bytes_per_step": 10**12,
                 "up_crossings_per_step": 100,
                 "down_crossings_per_step": 100},
    },
}


def test_tb002_budget_within_limits_is_clean(tmp_path):
    assert _run_fix(_mini_repo(tmp_path, budget=_PERMISSIVE)) == []


def test_tb002_tampered_byte_budget_fires(tmp_path):
    tight = json.loads(json.dumps(_PERMISSIVE))
    tight["budget"]["serial"]["up_bytes_per_step"] = 1
    fs = _run_fix(_mini_repo(tmp_path, budget=tight))
    assert any(f.rule == "TB002" and "serial" in f.message
               and "exceeds budget 1" in f.message for f in fs)


def test_tb002_missing_budget_fires_on_real_run_only(tmp_path):
    # fixture mode tolerates a missing budget; the default-mode real
    # run does not (the gate must exist to gate)
    assert "TB002" not in _rules(_run_fix(_mini_repo(tmp_path)))
    assert os.path.exists(os.path.join(REPO, transfer.BUDGET_FILE)), (
        "the seeded budget file must be checked in")


# ------------------------------------------------------------------ TB003


def test_tb003_wide_download_row_on_its_own(tmp_path):
    # a wide [G, axis] value fetched per step by a row that is neither
    # the packed download nor lane-masked: a crossing of its own
    root = _mini_repo(tmp_path, dispatch=DISPATCH_FIX.replace(
        '{"value": "[1, CAP] i32", "site": "Engine.fetch_row",\n'
        '             "tag": "save_window_row", "masked": True},',
        '{"value": "[G, CAP] i32", "site": "Engine.fetch_row",\n'
        '             "tag": "save_window_row", "per_step": True},', 1))
    fs = _run_fix(root)
    assert any(f.rule == "TB003" and "on its own" in f.message for f in fs)


def test_tb003_packed_wide_download_is_clean(tmp_path):
    # the round's one packed download carries every wide StepOutput
    # field by design; its bytes are every element at 4 (a bool is a
    # 0/1 int32 column)
    root = _mini_repo(tmp_path)
    assert "TB003" not in _rules(_run_fix(root))
    sized = transfer.build_ledger(
        root, decl=transfer._load_decl(root)[0],
        cfg=dict(transfer.DEFAULT_CONFIG),
        contracts=transfer._collect_contracts(
            {"e": transfer._parse(os.path.join(
                root, "dragonboat_tpu/engine/engine.py"))}, []))
    cfg = transfer.DEFAULT_CONFIG
    G, K, E, B = (cfg["num_groups"], cfg["inbox_cap"], cfg["msg_entries"],
                  cfg["proposal_cap"])
    step = sized["entries"]["step"]
    assert step["up"][0]["bytes"] == 4 * G * (K + K * E + B)
    assert step["down"][0]["bytes"] == 4 * G * (8 + K + 8)
    assert sized["per_step"]["serial"] == {
        "up_bytes": 4 * G * (K + K * E + B), "up_crossings": 1,
        "down_bytes": 4 * G * (8 + K + 8), "down_crossings": 1}


def test_tb003_eager_wide_field_fetch(tmp_path):
    root = _mini_repo(tmp_path, engine=ENGINE_FIX + '''

def sweep_everything(out):
    return np.asarray(out.resp)
''')
    fs = _run_fix(root)
    assert any(f.rule == "TB003" and ".resp" in f.message
               and "sweep_everything" in f.message for f in fs)


def test_tb003_narrow_numeric_fetch_is_clean(tmp_path):
    # the [G, 8] flags matrix pairs G with a numeric literal — that is
    # the deliberate narrow fetch, not a wide sweep
    fs = _run_fix(_mini_repo(tmp_path))
    assert "TB003" not in _rules(fs)


# ------------------------------------------------------------------ TB004


def test_tb004_upload_outside_staging_builder(tmp_path):
    root = _mini_repo(tmp_path, engine=ENGINE_FIX + '''

def sneak_upload(rows):
    return jnp.asarray(rows)
''')
    fs = _run_fix(root)
    assert any(f.rule == "TB004" and "sneak_upload" in f.message
               for f in fs)


def test_tb004_jax_numpy_spelling_is_caught(tmp_path):
    root = _mini_repo(tmp_path, engine=ENGINE_FIX + '''
import jax


def sneak_upload2(rows):
    return jax.numpy.asarray(rows)
''')
    fs = _run_fix(root)
    assert any(f.rule == "TB004" and "sneak_upload2" in f.message
               for f in fs)


def test_tb004_declared_site_and_builder_are_clean(tmp_path):
    # Engine.inject is a declared _control site and the staging is
    # *.to_device — both upload in the clean fixture
    fs = _run_fix(_mini_repo(tmp_path))
    assert "TB004" not in _rules(fs)


# ------------------------------------------------------------------ TB005


def test_tb005_sync_outside_declared_point(tmp_path):
    root = _mini_repo(tmp_path, engine=ENGINE_FIX + '''

def eager_count(dispatch):
    p = dispatch.dispatch(None, None, None, False)
    return int(p)
''')
    fs = _run_fix(root)
    assert any(f.rule == "TB005" and "eager_count" in f.message
               and "int()" in f.message for f in fs)


def test_tb005_declared_sync_point_is_clean(tmp_path):
    # Engine.pending int()s a device value but is declared
    fs = _run_fix(_mini_repo(tmp_path))
    assert "TB005" not in _rules(fs)


def test_tb005_item_and_block_until_ready(tmp_path):
    root = _mini_repo(tmp_path, engine=ENGINE_FIX + '''

def stall(box):
    y = box.to_device()
    y.block_until_ready()
    return y.item()
''')
    fs = _run_fix(root)
    msgs = [f.message for f in fs if f.rule == "TB005"]
    assert any("block_until_ready" in m for m in msgs)
    assert any(".item()" in m for m in msgs)


# ------------------------------------------------------------------ TB006


def test_tb006_tampered_crossing_budget_fires(tmp_path):
    tight = json.loads(json.dumps(_PERMISSIVE))
    tight["budget"]["serial"]["up_crossings_per_step"] = 0
    fs = _run_fix(_mini_repo(tmp_path, budget=tight))
    assert any(f.rule == "TB006" and "transfer count grew" in f.message
               for f in fs)


# -------------------------------------------------- the seeded regression


def test_seeded_regression_host_round_trip_in_seam(tmp_path):
    """The canonical regression the pass exists to catch: a dispatch
    path that pulls a device value to the host mid-seam and re-uploads
    it.  Both legs must fire — the sync (TB005) and the re-upload
    outside any declared site (TB004) — plus TB001 when the crossing is
    'declared' at a qualname that does not exist."""
    root = _mini_repo(tmp_path, engine=ENGINE_FIX + '''

def round_trip(dispatch, state):
    out = dispatch.dispatch(state, None, None, False)
    host = float(out)          # sync outside SYNC_POINTS
    return jnp.asarray(host)   # re-upload outside any declared site
''')
    fs = _run_fix(root)
    rules = _rules(fs)
    assert "TB005" in rules and "TB004" in rules


def test_runtime_guard_catches_host_round_trip():
    """The dynamic arm of the same regression: under METER.guard() an
    unsanctioned numpy tree entering the jitted dispatch entry raises
    at the JAX level instead of silently re-staging."""
    import jax
    import numpy as np

    from dragonboat_tpu import capacity
    from dragonboat_tpu.bench_loop import bench_params, make_cluster
    from dragonboat_tpu.core.kstate import pack_program
    from dragonboat_tpu.engine import kernel_engine as _ke
    from dragonboat_tpu.engine.dispatch import SerialDispatch

    kp = bench_params(3, platform="cpu")
    state = make_cluster(kp, 1, 3)
    G = int(state.term.shape[0])
    state = pack_program(kp)(state)      # the seam takes the resident form
    disp = SerialDispatch(kp)
    staging = _ke._RoundStaging(kp, G)
    state, _down = disp.dispatch(state, staging, donate=False)  # warm

    # the regression: state pulled to host numpy, fed straight back in
    state_np = jax.tree.map(np.array, state)
    with capacity.METER.guard():
        with pytest.raises(Exception, match="[Dd]isallow"):
            disp.dispatch(state_np, staging, donate=False)
    # sanctioned crossings still work inside the guard
    with capacity.METER.guard():
        state, _down = disp.dispatch(state, staging, donate=False)


# ------------------------------------- ledger vs live (depth 0 and 1)


def test_ledger_matches_live_counts():
    """The static TRANSFER_LEDGER and the live METER counters agree
    exactly at serial depth 0, serial depth 1 (donated) and — when the
    forced CPU mesh provides 2 devices — the 2-device mesh."""
    assert transfer.live_transfer_check(REPO, use_cache=False) == []


def test_tampered_ledger_diverges_from_live():
    """Deleting a declared per-step crossing makes the live diff fire:
    the seam still crosses, the ledger now says it must not."""
    decl, _, _ = transfer._load_decl(REPO)
    for entry in ("step", "step_donated"):
        rows = decl["TRANSFER_LEDGER"][entry]["up"]
        decl["TRANSFER_LEDGER"][entry]["up"] = tuple(
            r for r in rows if r.get("tag") != "round_up")
    fs = transfer.live_transfer_check(REPO, decl=decl, use_cache=False)
    assert any(f.rule == "TB006" and "'round_up'" in f.message
               for f in fs)


# ------------------------------------------------------ ledger artifact


def test_emit_ledger_artifact(tmp_path):
    out = str(tmp_path / "ledger.json")
    transfer.emit_ledger(REPO, out_path=out)
    with open(out, encoding="utf-8") as f:
        ledger = json.load(f)
    for entry in ("step", "step_donated", "serve_step",
                  "serve_step_donated", "fleet_digest"):
        assert entry in ledger["entries"], entry
    # the collection's three reports ride ONE packed row down
    (down,) = ledger["entries"]["fleet_digest"]["down"]
    assert down["packed"] and down["tag"] == "digest_down"
    assert down["value"] == ["FleetStats", "HealthReport",
                             "InvariantReport"]
    for _entry, section in ledger["entries"].items():
        for dirn in ("up", "down"):
            for row in section[dirn]:
                assert isinstance(row["bytes"], int) and row["bytes"] > 0
    # the budget seed equals the sized per-step profile exactly
    with open(os.path.join(REPO, transfer.BUDGET_FILE),
              encoding="utf-8") as f:
        budget = json.load(f)["budget"]
    for profile in ("serial", "mesh"):
        for key, val in ledger["per_step"][profile].items():
            assert budget[profile][f"{key}_per_step"] == val, (
                profile, key)


# PR 17 seeded the mesh per-step budget when the serving entry still
# downloaded a device->host pending scalar every step; round 17 derives
# drain-pending from the activity flags the host already fetches, and
# PR 26 packed each direction of a round into one array
_PR17_MESH_DOWN_CROSSINGS = 2


def test_per_step_crossings_stay_at_one(tmp_path):
    """A round makes ONE upload and ONE download on both backends: the
    reseeded budget must say so (the bytes grew by design — the packed
    download carries every StepOutput field; a crossing costs its
    millisecond whatever it carries), and the mesh download crossings
    must stay below PR 17's."""
    spec = transfer.reseed(REPO, budget_path=str(tmp_path / "b.json"))
    for profile in ("serial", "mesh"):
        got = spec["budget"][profile]
        assert got["up_crossings_per_step"] == 1, profile
        assert got["down_crossings_per_step"] == 1, profile
    assert (spec["budget"]["mesh"]["down_crossings_per_step"]
            < _PR17_MESH_DOWN_CROSSINGS)


def test_reseed_roundtrip(tmp_path):
    out = str(tmp_path / "budget.json")
    spec = transfer.reseed(REPO, budget_path=out)
    with open(out, encoding="utf-8") as f:
        assert json.load(f)["budget"] == spec["budget"]
    with open(os.path.join(REPO, transfer.BUDGET_FILE),
              encoding="utf-8") as f:
        assert json.load(f)["budget"] == spec["budget"], (
            "checked-in budget drifted from the declared ledger — "
            "run scripts/lint.py --reseed-transfer-budget")


# ------------------------------------------------- cache invalidation


def test_cache_invalidates_on_jax_version(tmp_path, monkeypatch):
    import jax

    key = transfer._source_key(REPO)
    cache = str(tmp_path / "cache.json")
    transfer._cache_save(cache, key, [])
    assert transfer._cache_load(cache, key) == []
    monkeypatch.setattr(jax, "__version__", "0.0.0-fake")
    assert transfer._source_key(REPO) != key
    assert transfer._cache_load(cache, transfer._source_key(REPO)) is None


def test_cache_invalidates_on_seam_source(tmp_path, monkeypatch):
    # any CACHE_SOURCES byte change shifts the key
    key = transfer._source_key(REPO)
    fake = tmp_path / "dragonboat_tpu" / "engine"
    fake.mkdir(parents=True)
    for f in transfer.CACHE_SOURCES:
        src = os.path.join(REPO, f)
        dst = tmp_path / f
        dst.parent.mkdir(parents=True, exist_ok=True)
        if os.path.exists(src):
            with open(src, "rb") as fh:
                dst.write_bytes(fh.read())
    with open(tmp_path / transfer.CACHE_SOURCES[0], "a",
              encoding="utf-8") as fh:
        fh.write("\n# tampered\n")
    assert transfer._source_key(str(tmp_path)) != key


# -------------------------------------------------- lint.py integration


def test_lint_registers_transfer_pass():
    lint = _load(os.path.join(REPO, "scripts", "lint.py"), "lint_tb")
    assert "transfer" in lint.PASSES
    assert lint.PASS_SCOPES["transfer"] == transfer.SCOPE


def test_lint_changed_only_invalidation():
    lint = _load(os.path.join(REPO, "scripts", "lint.py"), "lint_tb2")
    for changed in (["dragonboat_tpu/engine/dispatch.py"],
                    ["dragonboat_tpu/engine/kernel_engine.py"],
                    ["dragonboat_tpu/core/kernel.py"],
                    ["dragonboat_tpu/capacity.py"],
                    [transfer.BUDGET_FILE]):
        assert "transfer" in lint.select_changed(changed), changed
    assert "transfer" not in lint.select_changed(["README.md"])


def test_findings_flow_through_lint_summary(tmp_path):
    summary = _load(os.path.join(REPO, "scripts", "lint_summary.py"),
                    "lint_summary_tb")
    art = tmp_path / "findings.jsonl"
    art.write_text(json.dumps({
        "path": "dragonboat_tpu/engine/dispatch.py", "line": 1,
        "pass": "transfer", "rule": "TB001",
        "message": "undeclared crossing", "waived": False,
        "reason": None}) + "\n")
    rc = summary.main(["lint_summary.py", str(art)])
    assert rc == 1
