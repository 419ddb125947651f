"""Where does the device step go?  Compile the bench step for the live
backend and report (a) XLA's own cost analysis, (b) optimized-HLO op
histogram with the serializing suspects called out (while loops,
scatters, gathers, dynamic slices), (c) measured step time at a small
shape for cross-checking.  Pure diagnosis — no state is mutated.

Usage: python scripts/tpu_profile.py [groups] [--hlo-dump FILE]
"""

import collections
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from dragonboat_tpu.hostenv import enable_compile_cache

enable_compile_cache()

from dragonboat_tpu.bench_loop import bench_params, make_cluster, run_steps
from dragonboat_tpu.core.kstate import empty_inbox


def op_histogram(hlo_text: str) -> dict:
    """Count optimized-HLO instructions by opcode (fusion bodies included:
    the text form inlines called computations, which is what we want —
    a serializing scatter inside a fusion still serializes)."""
    counts = collections.Counter()
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[\w\[\]{},/ ]+?\s"
                         r"([a-z][\w\-]*)\(", hlo_text, re.M):
        counts[m.group(1)] += 1
    return dict(counts)


def main() -> None:
    g = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 1024
    plat = jax.devices()[0].platform
    print(f"backend: {plat}  groups: {g}", flush=True)

    kp = bench_params(3)
    # no election: the compiled graph is state-independent, and elect_all
    # is a compile of its own
    state = make_cluster(kp, g, 3)
    box = empty_inbox(kp, g * 3)
    jax.block_until_ready(state.term)

    # the exact bench inner loop (same jit key as the bench: run_steps
    # itself is jitted with static (kp, replicas, iters))
    t0 = time.time()
    lowered = run_steps.lower(kp, 3, 20, True, True, state, box)
    compiled = lowered.compile()
    print(f"compile: {time.time() - t0:.1f}s", flush=True)

    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    if ca:
        keys = ["flops", "bytes accessed", "transcendentals",
                "optimal_seconds"]
        print("cost_analysis: " + "  ".join(
            f"{k}={ca[k]:.3g}" for k in keys if k in ca), flush=True)

    ma = compiled.memory_analysis()
    if ma is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                print(f"memory.{attr}: {v:,}")

    hlo = compiled.as_text()
    print(f"optimized HLO: {len(hlo.splitlines()):,} lines")
    hist = op_histogram(hlo)
    suspects = ("while", "scatter", "gather", "dynamic-slice",
                "dynamic-update-slice", "sort", "all-reduce", "conditional",
                "rng-bit-generator", "custom-call")
    for name in suspects:
        if hist.get(name):
            print(f"  SUSPECT {name}: {hist[name]}")
    top = sorted(hist.items(), key=lambda kv: -kv[1])[:25]
    print("  top ops: " + ", ".join(f"{k}={v}" for k, v in top))

    if "--hlo-dump" in sys.argv:
        i = sys.argv.index("--hlo-dump") + 1
        if i >= len(sys.argv):
            print("--hlo-dump needs a filename; skipping dump")
        else:
            path = sys.argv[i]
            with open(path, "w") as f:
                f.write(hlo)
            print(f"dumped HLO to {path}")

    # measured time via the jitted entry (same executable via cache)
    out = run_steps(kp, 3, 20, True, True, state, box)
    jax.block_until_ready(out[0].term)
    t0 = time.time()
    out = run_steps(kp, 3, 20, True, True, *out)
    jax.block_until_ready(out[0].term)
    dt = time.time() - t0
    print(f"measured: {dt / 20 * 1000:.2f} ms/step at G={g}", flush=True)


if __name__ == "__main__":
    main()
