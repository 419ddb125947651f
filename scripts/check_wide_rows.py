#!/usr/bin/env python
"""On the chip: the row-for-row comparisons of ``tests/test_wide_rows.py``
at the KernelParams a NodeHost picks on a TPU (``log_cap`` 1,024, one-hot
ring reads), for the round, the admission program and the collection at
4,096 rows of one program.

    chiprun -- python3 scripts/check_wide_rows.py [--steps N] [--seed N]

Prints one JSON line a comparison and exits non-zero on the first row that
differs (the assertion names program, step, row and cell).  It refuses to
run off the chip: tier-1 runs the same functions on the CPU at a small ring.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

from dragonboat_tpu.config import ExpertConfig  # noqa: E402
from dragonboat_tpu.nodehost import NodeHost  # noqa: E402
from tests import test_wide_rows as wide  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=44)
    ap.add_argument("--seed", type=int, default=4096)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"check_wide_rows: no accelerator, jax reports "
                 f"{device.platform!r}")
    host = SimpleNamespace(config=SimpleNamespace(expert=ExpertConfig()))
    kp = NodeHost._kernel_params(host)
    assert kp.onehot_reads and kp.log_cap == 1024, kp

    def say(what, t0, **fields):
        print(json.dumps({"compared": what, "rows": wide.WIDE,
                          "device": str(device), "kind": device.device_kind,
                          "seconds": round(time.monotonic() - t0, 1),
                          **fields}), flush=True)

    t0 = time.monotonic()
    lived = wide.compare_round(kp, steps=args.steps, seed=args.seed)
    tall = lived.pop("state")
    say("round", t0, steps=args.steps, top_quarter=lived)
    for seed in (args.seed + 1, args.seed + 2):
        t0 = time.monotonic()
        say("inject_rows", t0, seed=seed,
            admitted=wide.compare_inject(kp, seed))
    t0 = time.monotonic()
    report = wide.compare_collection(kp, tall, args.seed + 3)
    say("collection", t0, role_count=report["role_count"],
        term_max=report["term_max"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
