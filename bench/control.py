#!/usr/bin/env python3
"""Precision control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it builds the cell's records at the cell's own size, puts the
reference computed in bfloat16 (one precision step below the float32 the
configuration states) in the program's place, and prints the numbers
``bench/check.py`` compares, beside their limits, one JSON line per seed.
The control has to come out not correct; the limits were set between its
readings and those of sound runs (``PERF.md``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control(workload: str, seed: int, n: int | None = None,
            spec: dict | None = None) -> dict:
    """Readings of the bfloat16 control for one seed (``n`` and ``spec``
    as for ``bench.run.run_cell``)."""
    import numpy as np

    from bench import check, corpus, reference
    from bench.run import load_cell
    cell = load_cell(workload, spec)
    cfg = dict(cell.cfg, n=n or cell.cfg["n"])
    rec = corpus.make_corpus(cfg, cell.traffic, seed)
    w, matcher = cfg["er"]["window"], cfg["matcher"]
    ref = reference.resolve(rec, w, matcher)
    blocked, matched, _ = reference.resolve(rec, w, matcher,
                                            dtype=reference.BF16)
    numbers = check.compare(rec, matcher, ref, blocked, matched)
    limits = {k: cfg["limits"][k] for k in numbers}
    checks, ok = check.verdict(numbers, limits)
    return {"workload": workload, "seed": seed, "n": cfg["n"],
            "correct": ok, "checks": checks,
            "disagreements": int(np.setxor1d(matched, ref[1]).size)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for seed in args.seeds:
        t = time.perf_counter()
        out = control(args.workload, seed)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
