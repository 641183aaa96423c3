"""Readings that a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

It refuses to run without the cell's chips, as a run does. The cell's
set-up once; then for each of ``--seeds`` seeds ``--units`` units of work
at the cell's own load, of which the sample a run takes is compared with
the float64 reference, as a run compares it (the program's readings: the
lower end of each limit). For the first ``--control-seeds`` seeds the
reference computed in bfloat16 is compared with the float64 reference in
the same way (the control's readings: the upper end). Prints one JSON
object; the limits in the configuration are set from it by hand, as
PERF.md records.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    import ml_dtypes

    import run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--units", type=int, default=4,
                   help="units of work per seed, at the cell's own load")
    p.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = p.parse_args(argv)

    cell = run.load_cell(args.workload)
    run.device_info(cell["chips"])
    from repro.engine import setup_persistent_cache

    import traffic

    setup_persistent_cache()
    out = {"workload": args.workload, "program": [], "control": []}
    work_gen = traffic.make(cell["cfg"], cell["mix"], args.first_seed)
    work_gen.warm()
    if work_gen.backend not in run.DEVICE_BACKENDS:
        raise SystemExit(f"calibrate: the engine resolved {work_gen.backend!r}"
                         f", not a device backend")
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        work_gen.reseed(seed)   # same stream and grid, the seed's markets
        t = time.perf_counter()
        for k in range(args.units):
            work_gen.unit(k)
        t_units = time.perf_counter() - t
        got = work_gen.check(seed)
        got.update(seed=seed, units_s=t_units)
        out["program"].append(got)
        print(json.dumps({"program": got}), flush=True)
        if i < args.control_seeds:
            ctl = work_gen.control(seed, ml_dtypes.bfloat16)
            ctl.update(seed=seed)
            out["control"].append(ctl)
            print(json.dumps({"control": ctl}), flush=True)
    for side in ("program", "control"):
        for k in out["program"][0]:
            vals = [r[k] for r in out[side]]
            if vals and k != "seed":
                out[f"{side}_{k}"] = {"min": min(vals), "max": max(vals)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
