#!/usr/bin/env python3
"""The control of the correctness check: the reference itself, in float32,
put in the program's place.

    python bench/control.py --workload <cell> --seeds 1 2 3 --batches 40

For each seed it builds the cell's traffic, runs the float32 reference
through the warm start, ``--warm`` warm-up batches and ``--batches`` window
batches (or ``--batches`` static solves), and hands its outputs to the same
comparison a run uses. Each line prints the largest L1 gap, the limit, and
whether the control failed the check, as it has to. It needs no chip: the
reference runs on the host.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.gen.traffic import make_traffic  # noqa: E402
from bench.harness import _rehearsal, check_outputs, load_cell  # noqa: E402
from bench.reference import ReferenceStream  # noqa: E402


def control_gaps(workload: str, seed: int, batches: int, warm: int,
                 rehearse: bool = False):
    """L1 gaps of the float32 control's window outputs."""
    _, _, cfg, mix, limits = load_cell(workload)
    if rehearse:
        cfg = _rehearsal(cfg)
    traffic = make_traffic(cfg, mix, seed)
    stream = mix["loop"] == "stream"
    ctl = ReferenceStream(traffic.n, traffic.base_src, traffic.base_dst,
                          cfg["guarantees"], np.float32)
    if stream:
        outs = []
        for i, b in enumerate(traffic.batches[:warm + batches]):
            r = ctl.apply(b.del_src, b.del_dst, b.ins_src, b.ins_dst)
            if i >= warm:
                outs.append(r.copy())
    else:
        outs = [ctl.ranks] * batches
    return check_outputs(traffic, cfg, warm, outs, stream)[0], \
        limits["l1_gap"]["limit"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batches", type=int, required=True)
    ap.add_argument("--warm", type=int, default=0)
    args = ap.parse_args()
    for seed in args.seeds:
        gaps, limit = control_gaps(args.workload, seed, args.batches,
                                   args.warm)
        worst = max(gaps)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "compared": len(gaps), "l1_gap_max": worst,
                          "l1_gap_min": min(gaps), "limit": limit,
                          "control_failed": not worst <= limit}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
