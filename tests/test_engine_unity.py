"""Engine-unity pass (analysis/engine_unity.py): every EU rule must
fire on a tampered fixture and stay silent on the clean one, the real
repo must be clean, the lint runner must treat engine/ edits as
invalidating the pass under --changed-only, and EU findings must flow
through the json artifact into lint_summary."""

from __future__ import annotations

import importlib.util
import json
import os
import textwrap

from dragonboat_tpu.analysis import engine_unity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# A minimal unified-engine repo: one step-loop owner, one subclass that
# only uses sanctioned seams, one dispatch backend wiring the declared
# donated + non-donated entry pair through TRACKER.wrap.  All fixture
# sources are column-0 so they compose by plain concatenation.
DISPATCH_SRC = '''\
STEP_LOOP_OWNER = "Owner"
STEP_LOOP_METHODS = ("step_all", "_kernel_call", "_process_outputs")
DISPATCH_SEAMS = ("_make_dispatch",)
ENGINE_FEATURE_KNOBS = ("pipeline_depth",)
ENGINE_FEATURE_CALLS = ("note_output_flags",)
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


class SerialBackend:
    def __init__(self, cap, step_fn, donated_fn):
        self.entries = {
            "step": cap.TRACKER.wrap("step", step_fn),
            "step_donated": cap.TRACKER.wrap("step_donated", donated_fn),
        }

    def dispatch(self, state, inbox, inp, donate):
        entry = self.entries["step_donated" if donate else "step"]
        return entry(state, inbox, inp)
'''

ENGINE_SRC = '''\
class Owner:
    def __init__(self):
        self._pending_ctx = None
        self._dispatch = self._make_dispatch()

    def _make_dispatch(self):
        return None

    def step_all(self):
        if self.pipeline_depth > 0 and self._pending_ctx is not None:
            pending, self._pending_ctx = self._pending_ctx, None
            self._process_outputs(pending)
        ctx = self._kernel_call()
        if self.pipeline_depth > 0:
            self._pending_ctx = ctx
        else:
            self._process_outputs(ctx)
        return True

    def _kernel_call(self):
        return self._dispatch.dispatch(
            None, None, None, donate=self.pipeline_depth > 0)

    def _process_outputs(self, ctx):
        return self._dispatch.note_output_flags(ctx)


class MeshSub(Owner):
    def _make_dispatch(self):
        return None
'''

KERNEL_SRC = '''\
import functools

import jax


def step(kp, state, inbox):
    return state


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2))
def step_donated(kp, state, inbox):
    return state
'''


def _mini_repo(tmp_path, dispatch=DISPATCH_SRC, engine=ENGINE_SRC,
               kernel=KERNEL_SRC, extra=None):
    eng = tmp_path / "dragonboat_tpu" / "engine"
    eng.mkdir(parents=True)
    (eng / "dispatch.py").write_text(dispatch)
    (eng / "engine.py").write_text(engine)
    core = tmp_path / "core"
    core.mkdir()
    (core / "kernel.py").write_text(kernel)
    for name, src in (extra or {}).items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


def _rules(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------------ clean


def test_clean_unified_fixture_has_no_findings(tmp_path):
    assert engine_unity.run(_mini_repo(tmp_path)) == []


def test_real_repo_is_clean():
    assert engine_unity.run(REPO) == []


# ------------------------------------------------------------------ EU001


def test_eu001_subclass_step_loop_override_fires(tmp_path):
    root = _mini_repo(tmp_path, engine=ENGINE_SRC + '''

class Rogue(MeshSub):
    def _process_outputs(self, ctx):
        return ctx
''')
    fs = engine_unity.run(root)
    eu1 = [f for f in fs if f.rule == "EU001"]
    assert len(eu1) == 1
    assert "Rogue._process_outputs" in eu1[0].message
    assert eu1[0].path.endswith("engine.py")


def test_eu001_sanctioned_seam_override_is_clean(tmp_path):
    # MeshSub overrides _make_dispatch (a DISPATCH_SEAMS member) in the
    # base fixture and produces nothing
    fs = engine_unity.run(_mini_repo(tmp_path))
    assert "EU001" not in _rules(fs)


# ------------------------------------------------------------------ EU002


def test_eu002_per_path_feature_drift_fires(tmp_path):
    # the subclass grows its own step_all that never consults
    # pipeline_depth: the knob gates dispatch on Owner only
    root = _mini_repo(tmp_path, engine=ENGINE_SRC + '''

class Drifted(Owner):
    def step_all(self):
        pending, self._pending_ctx = self._pending_ctx, None
        self._process_outputs(pending)
        self._pending_ctx = self._kernel_call()
        return True

    def _kernel_call(self):
        return self._dispatch.dispatch(None, None, None, donate=True)
''')
    fs = engine_unity.run(root)
    drift = [f for f in fs if f.rule == "EU002"]
    assert any("pipeline_depth" in f.message and "Drifted" in f.message
               for f in drift)


def test_eu002_dead_knob_fires_at_declaration(tmp_path):
    root = _mini_repo(tmp_path, dispatch=DISPATCH_SRC.replace(
        'ENGINE_FEATURE_KNOBS = ("pipeline_depth",)',
        'ENGINE_FEATURE_KNOBS = ("pipeline_depth", "ghost_knob")'))
    fs = engine_unity.run(root)
    dead = [f for f in fs if f.rule == "EU002"
            and "ghost_knob" in f.message]
    assert len(dead) == 1
    assert dead[0].path == engine_unity.DISPATCH_FILE
    assert "dead dispatch feature" in dead[0].message


# ------------------------------------------------------------------ EU003


def test_eu003_donated_entry_without_donate_argnums(tmp_path):
    root = _mini_repo(tmp_path, kernel='''\
def step(kp, state, inbox):
    return state


def step_donated(kp, state, inbox):
    return state
''')
    fs = engine_unity.run(root)
    assert any(f.rule == "EU003" and "no donate_argnums" in f.message
               for f in fs)


def test_eu003_non_donated_entry_without_waiver(tmp_path):
    root = _mini_repo(tmp_path, dispatch=DISPATCH_SRC.replace(
        '"waiver": "depth-0 oracle must leave inputs readable",',
        '"waiver": "",'))
    fs = engine_unity.run(root)
    assert any(f.rule == "EU003" and "declares no waiver" in f.message
               and "'step'" in f.message for f in fs)


def test_eu003_backend_selecting_undeclared_entry(tmp_path):
    root = _mini_repo(tmp_path, dispatch=DISPATCH_SRC + '''

class RogueBackend:
    def __init__(self, cap, fn):
        self.entries = {
            "step": cap.TRACKER.wrap("step", fn),
            "step_donated": cap.TRACKER.wrap("step_donated", fn),
        }

    def dispatch(self, state, inbox, inp, donate):
        return self.entries["bespoke_step"](state, inbox, inp)
''')
    fs = engine_unity.run(root)
    assert any(f.rule == "EU003" and "bespoke_step" in f.message
               and "undeclared" in f.message for f in fs)


def test_eu003_donated_entry_missing_kstate_donation(tmp_path):
    # a kstate DONATION table exists but never declares the entry
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/core/kstate.py": "DONATION = {}\n"})
    fs = engine_unity.run(root)
    assert any(f.rule == "EU003"
               and "kstate.DONATION" in f.message for f in fs)


def test_eu003_kstate_donation_declared_is_clean(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/core/kstate.py": """\
            DONATION = {
                "step_donated": {
                    "module": "core/kernel.py",
                    "function": "step_donated",
                },
            }
        """})
    assert "EU003" not in _rules(engine_unity.run(root))


# ------------------------------------------------------------------ EU004

ENGINE_DISPATCH_FIRST_SRC = '''\
class Owner:
    def __init__(self):
        self._pending_ctx = None
        self._dispatch = self._make_dispatch()

    def _make_dispatch(self):
        return None

    def step_all(self):
        ctx = self._kernel_call()
        if self.pipeline_depth > 0 and self._pending_ctx is not None:
            pending, self._pending_ctx = self._pending_ctx, None
            self._process_outputs(pending)
        self._pending_ctx = ctx
        return True

    def _kernel_call(self):
        return self._dispatch.dispatch(
            None, None, None, donate=self.pipeline_depth > 0)

    def _process_outputs(self, ctx):
        return self._dispatch.note_output_flags(ctx)
'''


def test_eu004_dispatch_before_retire_fires(tmp_path):
    root = _mini_repo(tmp_path, engine=ENGINE_DISPATCH_FIRST_SRC)
    fs = engine_unity.run(root)
    assert any(f.rule == "EU004"
               and "retire-before-dispatch order broken" in f.message
               for f in fs)


def test_eu004_no_carried_ctx_fires(tmp_path):
    root = _mini_repo(tmp_path, engine='''\
class Owner:
    def __init__(self):
        self._dispatch = self._make_dispatch()

    def _make_dispatch(self):
        return None

    def step_all(self):
        ctx = self._kernel_call()
        self._process_outputs(ctx)
        return True

    def _kernel_call(self):
        return self._dispatch.dispatch(
            None, None, None, donate=self.pipeline_depth > 0)

    def _process_outputs(self, ctx):
        return self._dispatch.note_output_flags(ctx)
''')
    fs = engine_unity.run(root)
    assert any(f.rule == "EU004" and "_pending_ctx" in f.message
               for f in fs)


DISPATCH_NO_DONATE_SRC = DISPATCH_SRC[:DISPATCH_SRC.index(
    "class SerialBackend")] + '''\
class SerialBackend:
    def __init__(self, cap, step_fn, donated_fn):
        self.entries = {
            "step": cap.TRACKER.wrap("step", step_fn),
        }

    def dispatch(self, state, inbox, inp, donate):
        return self.entries["step"](state, inbox, inp)
'''


def test_eu004_backend_without_donated_entry_fires(tmp_path):
    root = _mini_repo(tmp_path, dispatch=DISPATCH_NO_DONATE_SRC)
    fs = engine_unity.run(root)
    assert any(f.rule == "EU004" and "pipelining parity" in f.message
               and "SerialBackend" in f.message for f in fs)
    # the declared donated entry is also no longer tracker-wrapped
    assert any(f.rule == "EU005" and "never" in f.message for f in fs)


# ------------------------------------------------------------------ EU005


def test_eu005_untracked_jit_in_engine_layer(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/engine/rogue.py": """\
            import jax


            def make_entry(fn):
                return jax.jit(fn, donate_argnums=(0,))
        """})
    fs = engine_unity.run(root)
    assert any(f.rule == "EU005" and "jax.jit" in f.message
               and f.path.endswith("rogue.py") for f in fs)


def test_eu005_jit_inside_tracker_wrap_is_clean(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/engine/wrapped.py": """\
            import jax

            from dragonboat_tpu import capacity as _cap


            def make_entry(fn):
                return _cap.TRACKER.wrap("aux", jax.jit(fn))
        """})
    fs = engine_unity.run(root)
    assert not any(f.rule == "EU005" and f.path.endswith("wrapped.py")
                   for f in fs)


def test_eu005_direct_entry_call_bypassing_tracker(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/engine/direct.py": """\
            from core.kernel import step_donated as fast_step


            def sneak(state):
                return fast_step(None, state, None)
        """})
    fs = engine_unity.run(root)
    assert any(f.rule == "EU005" and "step_donated" in f.message
               and f.path.endswith("direct.py") for f in fs)


# ------------------------------------------------------------------ EU006


def test_eu006_private_import_from_kernel_internals(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/engine/leaky.py": """\
            from dragonboat_tpu.core.kernel import _ring_advance


            def poke(state):
                return _ring_advance(state)
        """})
    fs = engine_unity.run(root)
    assert any(f.rule == "EU006" and "_ring_advance" in f.message
               for f in fs)


def test_eu006_private_attribute_through_module_alias(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/engine/leaky.py": """\
            from dragonboat_tpu.parallel import ici as _ici


            def poke(kp, cluster, state, box, inp, cut):
                return _ici._jit_serve_step(
                    kp, cluster, state, box, inp, cut)
        """})
    fs = engine_unity.run(root)
    assert any(f.rule == "EU006" and "_jit_serve_step" in f.message
               for f in fs)


def test_eu006_public_imports_are_clean(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/engine/fine.py": """\
            from dragonboat_tpu.core import params as KP
            from dragonboat_tpu.parallel.ici import IciCluster


            def shape(spec):
                return KP.KernelParams, IciCluster
        """})
    fs = engine_unity.run(root)
    assert "EU006" not in _rules(fs)


# -------------------------------------------------- lint.py integration


def test_lint_registers_engine_unity_pass():
    lint = _load(os.path.join(REPO, "scripts", "lint.py"), "lint_eu")
    assert "engine-unity" in lint.PASSES
    assert lint.PASS_SCOPES["engine-unity"] == engine_unity.SCOPE


def test_changed_only_engine_edit_invalidates_pass():
    lint = _load(os.path.join(REPO, "scripts", "lint.py"), "lint_eu2")
    for changed in (["dragonboat_tpu/engine/kernel_engine.py"],
                    ["dragonboat_tpu/engine/dispatch.py"],
                    ["dragonboat_tpu/core/kernel.py"],
                    ["dragonboat_tpu/parallel/ici.py"]):
        assert "engine-unity" in lint.select_changed(changed), changed
    assert "engine-unity" not in lint.select_changed(["README.md"])


def test_eu_findings_flow_through_json_and_summary(tmp_path):
    root = _mini_repo(tmp_path, extra={
        "dragonboat_tpu/engine/leaky.py": """\
            from dragonboat_tpu.core.kernel import _ring_advance
        """})
    fs = engine_unity.run(root)
    assert fs
    lint = _load(os.path.join(REPO, "scripts", "lint.py"), "lint_eu3")
    sarif = lint.to_sarif(fs, [])
    assert any(r["ruleId"] == "EU006"
               for r in sarif["runs"][0]["results"])
    lines = [json.dumps({"path": f.path, "line": f.line,
                         "pass": f.pass_name, "rule": f.rule,
                         "message": f.message, "waived": False,
                         "reason": None}) for f in fs]
    summary = _load(os.path.join(REPO, "scripts", "lint_summary.py"),
                    "lint_summary_eu")
    report, unwaived = summary.summarize(lines)
    assert unwaived == len(fs)
    assert "engine-unity" in report and "EU006" in report
