#!/usr/bin/env python
"""Static-analysis runner: the nine lint passes over the repo.

Passes (dragonboat_tpu/analysis/):

  tracer-safety   Python control flow / host coercions on traced values
                  in every function reachable from a jit/vmap call site
  hlo-budget      optimized-HLO gather/scatter/while counts of the step
                  kernel vs the checked-in analysis/hlo_budget.json
  concurrency     `# guarded-by:` annotation discipline on shared
                  mutable state in the threaded modules, plus the CC003
                  lock-order graph (static deadlock detection)
  determinism     wall clock / unseeded RNG / set-iteration order in
                  the core/ and rsm/ replay paths
  contracts       machine-checked shape/dtype/domain/ring-mask
                  contracts over the batched Raft step (abstract
                  interpretation of core/kernel.py against the
                  CONTRACTS declarations, plus an eval_shape diff of
                  declared vs actual structures)
  partition       SPMD partition safety for the G axis: cross-group
                  data flow outside declared collectives, shard_map
                  in/out_specs vs the part= contract tags, donation
                  sharding identity, host callbacks inside shard_map
                  bodies, implicit device→host syncs in the engine hot
                  paths, and a 2-device dynamic diff of declared vs
                  actual output shardings
  engine-unity    one step loop, one dispatch abstraction: subclass
                  step-loop overrides (EU001), per-path dispatch
                  feature drift (EU002), donation/waiver parity of the
                  declared dispatch entries (EU003), the pipelined
                  retire-before-dispatch protocol on every path
                  (EU004), CompileTracker coverage of every jit entry
                  the engine layer touches (EU005), and engine-layer
                  imports of kernel internals (EU006) — all against
                  the literal contract in engine/dispatch.py
  safety          Raft protocol safety: the kstate INVARIANTS
                  declarations lint (RS001/RS006), provenance-checked
                  store obligations on committed / vote / last in
                  core/kernel.py (RS002-RS004), and the cached
                  small-scope exhaustive model check of the real jitted
                  kernel step (scripts/model_check.py fast scope,
                  RS005)
  transfer        the device<->host boundary as a checked contract:
                  every crossing into/out of the jitted dispatch
                  entries declared in engine/dispatch.py
                  TRANSFER_LEDGER and sized in closed form from the
                  CONTRACTS grammar — undeclared crossings (TB001),
                  per-step byte budgets vs
                  analysis/transfer_budget.json (TB002), wide downloads
                  outside the round's packed download (TB003), uploads
                  bypassing the staging builders (TB004), syncs outside
                  the declared SYNC_POINTS (TB005, the engine-wide
                  sharpening of PS006), per-step crossing-count growth
                  (TB006), plus a dynamic leg that steps the real
                  dispatch seams under jax.transfer_guard("disallow")
                  at three geometries and diffs the live METER counts
                  against the static ledger

Passes run in parallel worker processes (one fork per pass; jax
initializes per-child so the AST-only passes never pay for it).  Use
`--jobs 1` to force the serial path, `--changed-only` to run only the
passes whose input files differ from git HEAD (the tight-edit-loop
mode; scripts/run_tests.sh lint-fast wraps it).

Exit status is non-zero iff any unwaived finding remains.  Waivers live
in dragonboat_tpu/analysis/waivers.toml; waived findings are still
printed (with their reasons) so suppressions stay visible.  On a full
run (no --pass filter, no --changed-only) the waivers themselves are
linted: an entry whose path pattern matches no file (SW001) or that
suppressed zero findings (SW002) is stale and fails the run.

`--format json` emits one finding per line (JSON object with path,
line, pass, rule, message, waived, reason) so CI can diff findings
between commits; `--format sarif` emits a single SARIF 2.1.0 document
(one run, one result per finding, waived findings at level=note) for
code-scanning UIs; the default human format is unchanged.

The hlo-budget pass compiles the bench kernel (~10 s on CPU) only when
a hashed kernel source changed since the cached measurement
(analysis/.hlo_budget_cache.json); skip it entirely during tight edit
loops with `--pass` selecting the AST passes, or refresh its budget
after a justified kernel change with `--reseed-hlo-budget` (then
record why in PERF.md).  The partition pass's dynamic mesh check
caches the same way (analysis/.partition_cache.json), as does the
safety pass's model-check gate (analysis/.safety_cache.json) and the
transfer pass's live seam diff (analysis/.transfer_cache.json); the
transfer budget reseeds with `--reseed-transfer-budget`.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import subprocess
import sys

# a CPU-only analysis tool: lowering must never grab a TPU just to count ops
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the partition pass's dynamic check needs a 2-device mesh; the flag
# must be set before any child (or this process) initializes jax
_xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=2").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from dragonboat_tpu.analysis import (  # noqa: E402
    common,
    concurrency,
    contracts,
    determinism,
    engine_unity,
    hlo_budget,
    partition,
    safety,
    tracer_safety,
    transfer,
)

PASSES = {
    "tracer-safety": tracer_safety.run,
    "concurrency": concurrency.run,
    "determinism": determinism.run,
    "hlo-budget": hlo_budget.run,
    "contracts": contracts.run,
    "partition": partition.run,
    "engine-unity": engine_unity.run,
    "safety": safety.run,
    "transfer": transfer.run,
}

# repo-relative inputs of each pass, for --changed-only (entries may be
# fnmatch globs — determinism scopes whole directories)
PASS_SCOPES = {
    "tracer-safety": tracer_safety.DEFAULT_MODULES,
    "concurrency": concurrency.DEFAULT_MODULES,
    "determinism": determinism.DEFAULT_GLOBS,
    "hlo-budget": hlo_budget.CACHE_SOURCES,
    "contracts": (contracts.CONTRACT_FILES + (contracts.PARAMS_FILE,)
                  + contracts.DONATION_MODULES),
    "partition": partition.SCOPE,
    "engine-unity": engine_unity.SCOPE,
    "safety": safety.SCOPE,
    "transfer": transfer.SCOPE,
}

WAIVERS_FILE = "dragonboat_tpu/analysis/waivers.toml"


def _repo_rel_files(root: str) -> list[str]:
    """Repo-relative paths of all source files (skips ignored dirs)."""
    skip = {"__pycache__", ".git", ".pytest_cache", ".hypothesis"}
    out: list[str] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in skip]
        for fn in filenames:
            out.append(common.rel(root, os.path.join(dirpath, fn)))
    return out


def stale_waiver_findings(waivers: list[common.Waiver],
                          root: str) -> list[common.Finding]:
    """SW001/SW002: waivers that outlived the code they excused.

    Only meaningful after a FULL run — a --pass / --changed-only subset
    legitimately leaves other passes' waivers unexercised — so the
    caller gates on that.
    """
    relpath = common.rel(root, os.path.join(root, WAIVERS_FILE))
    files = _repo_rel_files(root)
    findings = []
    for w in waivers:
        if not any(fnmatch.fnmatch(p, w.path) for p in files):
            findings.append(common.Finding(
                "stale-waiver", relpath, w.line, "SW001",
                f"waiver path pattern {w.path!r} (pass {w.pass_name}) "
                "matches no file in the repo — delete the entry"))
        elif w.hits == 0:
            findings.append(common.Finding(
                "stale-waiver", relpath, w.line, "SW002",
                f"waiver for pass {w.pass_name}, path {w.path!r} "
                "suppressed zero findings this run — the code it "
                "excused is gone; delete the entry"))
    return findings


def changed_files(root: str) -> list[str] | None:
    """Repo-relative changed paths vs HEAD (staged + unstaged +
    untracked), or None when git is unavailable (callers run
    everything)."""
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", "HEAD"], cwd=root,
            capture_output=True, text=True, timeout=30)
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard"],
            cwd=root, capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.SubprocessError):
        return None
    if diff.returncode != 0:
        return None
    out = [ln.strip() for ln in diff.stdout.splitlines() if ln.strip()]
    if untracked.returncode == 0:
        out += [ln.strip() for ln in untracked.stdout.splitlines()
                if ln.strip()]
    return sorted(set(out))


def select_changed(changed: list[str]) -> list[str]:
    """Which passes a change set touches.  Any edit to the analyzers or
    this runner invalidates everything — and so does a waivers.toml
    edit (spelled out even though the analysis/ prefix covers it: a
    changed waiver can un-suppress a finding in ANY pass, so no pass's
    prior verdict survives it)."""
    if any(c == WAIVERS_FILE
           or c.startswith("dragonboat_tpu/analysis/")
           or c.startswith("scripts/lint") for c in changed):
        return sorted(PASSES)
    out = []
    for name in sorted(PASSES):
        scope = PASS_SCOPES[name]
        if any(fnmatch.fnmatch(c, pat) or c == pat
               for c in changed for pat in scope):
            out.append(name)
    return out


def _run_pass(name: str) -> list[common.Finding]:
    """Worker entry: one pass, raw (unwaived) findings.  Waivers are
    applied in the parent so hit-counting (stale-waiver lint) sees every
    pass's results."""
    return PASSES[name](ROOT)


def run_passes(selected: list[str],
               jobs: int) -> dict[str, list[common.Finding]]:
    """Run passes, in parallel when possible; results keyed by pass."""
    if jobs != 1 and len(selected) > 1:
        try:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor

            nworkers = min(len(selected),
                           jobs if jobs > 0 else (os.cpu_count() or 2))
            # fork so workers inherit the imported analyzers; jax is
            # only ever initialized inside a child
            with ProcessPoolExecutor(
                    max_workers=nworkers,
                    mp_context=multiprocessing.get_context("fork"),
            ) as pool:
                futs = {name: pool.submit(_run_pass, name)
                        for name in selected}
                return {name: fut.result() for name, fut in futs.items()}
        except Exception as e:  # no fork/semaphores: degrade, don't fail
            print(f"note: parallel pass execution unavailable "
                  f"({type(e).__name__}: {e}); running serially",
                  file=sys.stderr)
    return {name: _run_pass(name) for name in selected}


def to_sarif(unwaived: list[common.Finding],
             waived: list[tuple[common.Finding, common.Waiver]]) -> dict:
    """One SARIF 2.1.0 run: rules derived from the findings, waived
    findings downgraded to level=note with the waiver reason attached."""
    rules: dict[str, dict] = {}
    results = []
    for f, reason in ([(f, None) for f in unwaived]
                      + [(f, wv.reason) for f, wv in waived]):
        rules.setdefault(f.rule, {
            "id": f.rule,
            "properties": {"pass": f.pass_name},
            "shortDescription": {"text": f"{f.pass_name} {f.rule}"},
        })
        res = {
            "ruleId": f.rule,
            "level": "note" if reason is not None else "error",
            "message": {"text": f.message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": max(f.line, 1)},
                },
            }],
            "properties": {"pass": f.pass_name,
                           "waived": reason is not None},
        }
        if reason is not None:
            res["properties"]["waiverReason"] = reason
        results.append(res)
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "dragonboat-tpu-lint",
                "rules": [rules[k] for k in sorted(rules)],
            }},
            "results": results,
        }],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pass", dest="passes", action="append",
                    choices=sorted(PASSES),
                    help="run only this pass (repeatable; default: all)")
    ap.add_argument("--changed-only", action="store_true",
                    help="run only passes whose input files changed vs "
                         "git HEAD (skips the stale-waiver lint)")
    ap.add_argument("--jobs", type=int, default=0,
                    help="worker processes (0 = one per pass up to CPU "
                         "count; 1 = serial)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings blob on stdout "
                         "(legacy; prefer --format json)")
    ap.add_argument("--format", choices=("human", "json", "sarif"),
                    default="human",
                    help="json = one finding per line "
                         "(path, line, pass, rule, message, waived, "
                         "reason); sarif = one SARIF 2.1.0 document; "
                         "default: human")
    ap.add_argument("--reseed-hlo-budget", action="store_true",
                    help="re-measure the kernel and overwrite "
                         "analysis/hlo_budget.json (justify in PERF.md)")
    ap.add_argument("--reseed-transfer-budget", action="store_true",
                    help="re-size the declared transfer ledger and "
                         "overwrite analysis/transfer_budget.json "
                         "(justify in PERF.md)")
    args = ap.parse_args(argv)

    if args.reseed_hlo_budget:
        spec = hlo_budget.reseed(ROOT)
        print(f"reseeded {hlo_budget.BUDGET_FILE}:")
        print(json.dumps(spec["budget"], indent=2, sort_keys=True))
        return 0

    if args.reseed_transfer_budget:
        spec = transfer.reseed(ROOT)
        print(f"reseeded {transfer.BUDGET_FILE}:")
        print(json.dumps(spec["budget"], indent=2, sort_keys=True))
        return 0

    try:
        waivers = common.load_waivers(os.path.join(ROOT, WAIVERS_FILE))
    except common.WaiverError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    selected = args.passes or sorted(PASSES)
    skipped: list[str] = []
    if args.changed_only:
        changed = changed_files(ROOT)
        if changed is not None:
            wanted = select_changed(changed)
            skipped = [n for n in selected if n not in wanted]
            selected = [n for n in selected if n in wanted]
    human = args.format == "human" and not args.json
    if human and skipped:
        print(f"-- changed-only: skipping {', '.join(skipped)} "
              "(inputs unchanged)")

    results = run_passes(selected, args.jobs)
    unwaived: list[common.Finding] = []
    waived: list[tuple[common.Finding, common.Waiver]] = []
    for name in selected:
        u, w = common.apply_waivers(results[name], waivers)
        unwaived += u
        waived += w
        if human:
            print(f"== {name}: {len(u)} finding(s), {len(w)} waived ==")
            for f in u:
                print(f"  {f.format()}")
            for f, wv in w:
                print(f"  [waived: {wv.reason}] {f.format()}")

    if args.passes is None and not args.changed_only:
        # full run: a waiver that excuses nothing is itself a finding
        # (not waivable — a waiver cannot excuse its own staleness)
        stale = stale_waiver_findings(waivers, ROOT)
        unwaived += stale
        if human and (stale or waivers):
            print(f"== stale-waiver: {len(stale)} finding(s) ==")
            for f in stale:
                print(f"  {f.format()}")

    def row(f: common.Finding, reason: str | None) -> dict:
        return {"path": f.path, "line": f.line, "pass": f.pass_name,
                "rule": f.rule, "message": f.message,
                "waived": reason is not None, "reason": reason}

    if args.format == "json":
        for f in unwaived:
            print(json.dumps(row(f, None), sort_keys=True))
        for f, wv in waived:
            print(json.dumps(row(f, wv.reason), sort_keys=True))
    elif args.format == "sarif":
        print(json.dumps(to_sarif(unwaived, waived), indent=2,
                         sort_keys=True))
    elif args.json:
        print(json.dumps({
            "findings": [f.__dict__ for f in unwaived],
            "waived": [{"finding": f.__dict__, "reason": wv.reason}
                       for f, wv in waived],
        }, indent=2))
    elif unwaived:
        print(f"\nFAIL: {len(unwaived)} unwaived finding(s)")
    else:
        print("\nOK: no unwaived findings")
    return 1 if unwaived else 0


if __name__ == "__main__":
    sys.exit(main())
