"""Readings that set the limits of `correct` (PERF.md, "How correct is
decided"): for each seed, one short window at the cell's own size and
load, then the numbers compared, once against the reference and once
against the control (the same reference in int32 / float32, the
precision below the configuration's int64 / float64). Every seed runs
in this one process. Not run by the benchmark's own runs.

    python3 benchmark/control.py --workload <cell> --seeds a,b,c --seconds 5
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(workload: str, seeds, seconds: float, scale: float = 1.0):
    """[(seed, program's numbers, control's numbers)]."""
    from benchmark import harness, scenario

    bench = harness.load_benchmark()
    cell = harness.cell(bench, workload)
    harness.device(int(cell["chips"]))
    harness.configure_cache()
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        scn = scenario.build(cell["config"], cell["traffic"], seed, scale)
        drv = harness.driver(scn.traffic["kind"]).Driver(scn, seed)
        drv.setup()
        drv.window(seconds)
        drv.release()
        t1 = time.perf_counter()
        high = {k: v for k, (v, _) in drv.check("high").items()}
        t2 = time.perf_counter()
        low = {k: v for k, (v, _) in drv.check("low").items()}
        f32 = {k: v for k, (v, _) in drv.check("f32").items()}
        row = {"seed": seed, "program": high, "control": low, "f32_fractions": f32,
               "run_s": t1 - t0, "reference_s": t2 - t1}
        print(json.dumps(row), flush=True)
        out.append((seed, high, low))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    readings(args.workload, [int(s) for s in args.seeds.split(",")], args.seconds, args.scale)
    return 0


if __name__ == "__main__":
    sys.exit(main())
