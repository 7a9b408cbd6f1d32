"""The readings that a cell's limits are set from, in one process.

    python3 -m perfbench.readings --workload q1_sf1 --seconds 4 \\
        --seeds 101,102,... --control-seeds 201,202,203

For each program seed, one run of the cell as ``perfbench.run`` makes it
(set-up, a short window at the cell's own load, the reference and the
comparison of every answer); the set-up's programs are compiled once and
shared by the later seeds. For each control seed, the query's float32
control is put in the engine's place and compared with the reference at
the cell's own size. Each reading is one JSON line; the last line gives,
for each number compared, the largest program reading (the lower
reading) and the smallest control reading (the upper reading). The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional, Sequence

from perfbench import run, spec


def _seeds(text: str) -> List[int]:
    return [int(s) for s in text.split(",") if s]


def _worst(rows: List[Dict[str, float]], pick) -> Dict[str, float]:
    keys = sorted({k for r in rows for k in r})
    return {k: pick(r[k] for r in rows if k in r) for k in keys}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    run.use_checkout_cache()
    try:  # the control runs on one device; the program on the cell's
        devices, peaks = run.require_devices(cell.chips if args.seeds else 1)
    except run.NoDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3

    program: List[Dict[str, float]] = []
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, devices, peaks,
                           t0=time.perf_counter())
        nums = {k: c["value"] for k, c in res["checks"].items()}
        program.append(nums)
        print(json.dumps({"kind": "program", "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "checks": nums,
                          "metrics": res["metrics"]}), flush=True)

    q, lit = cell.query, cell.traffic["literals"]
    control: List[Dict[str, float]] = []
    for seed in args.control_seeds:
        host = q.generate(cell.config["rows"], seed)
        t = time.perf_counter()
        got = q.control(host, **lit)
        nums = q.compare(got, q.reference(host, **lit))
        control.append(nums)
        print(json.dumps({"kind": "control", "seed": seed, "checks": nums,
                          "correct": all(v <= q.LIMITS[k]
                                         for k, v in nums.items()),
                          "seconds": time.perf_counter() - t}), flush=True)
        del host

    print(json.dumps({"workload": cell.name,
                      "lower": _worst(program, max),
                      "upper": _worst(control, min)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
