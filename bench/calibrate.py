#!/usr/bin/env python3
"""Readings from which a cell's comparison limits are set.

    python3 bench/calibrate.py --workload <cell> --seconds 3 --seeds 1 2 3 ...

Runs the cell's timed path once per seed in this one process (a short
window each, as `bench/run.py` runs it) and compares the checked rows with
the reference at the configuration's precision, and, with ``--control``,
compares the control in the program's place: the reference at ``bf16_3x``
(three bfloat16 passes, XLA's "high") on the same scenarios.  Prints one JSON line per
seed, then the lower reading (the largest sound gap per number) and the
upper reading (the smallest control gap per number).  A limit is set
between them by hand, in ``bench/cells/<cell>.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness, reference

    harness.use_compile_cache()
    lower: dict[str, float] = {}
    upper: dict[str, float] = {}
    refs = None
    for seed in args.seeds:
        line, rows, cell, system = harness.measure(
            args.workload, seed, args.seconds, False)
        if refs is None:           # one reference per precision, reused
            refs = {p: reference.Reference(
                cell.model, cell.widths, cell.config, system.data,
                system.link_eps, precision=p) for p in ("highest", "bf16_3x")}
        out = {"seed": seed, "metrics": line["metrics"],
               "failed": line["failed"],
               "sound": harness.compare(rows, refs["highest"])}
        for k, v in out["sound"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        if args.control:
            out["control"] = harness.compare(rows, refs["highest"],
                                             refs["bf16_3x"])
            for k, v in out["control"].items():
                upper[k] = min(upper.get(k, float("inf")), v)
        print(json.dumps(out), flush=True)
    print(json.dumps({"lower": lower, "upper": upper if args.control else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
