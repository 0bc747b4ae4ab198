"""Read the numbers `correct` compares for sound runs, the control and the
planted faults of one cell, on the chip, in one process.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds <s> \
        [--faults frozen,half,altered]

For each seed: set-up, a window of ``--seconds`` at the cell's own size, and
then the check of the window's answers as they are (``sound``), with the
configuration's reference computed in bfloat16 in the program's place
(``control``), and with each fault planted in a copy of the answers. One JSON
line per seed. The benchmark's own runs never run this; it is how the limits
in ``bench/configs/<config>.json`` were set (see PERF.md). Refuses to run
without a TPU.
"""
from __future__ import annotations

import argparse
import copy
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="frozen,half,altered")
    args = ap.parse_args(argv)
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    try:
        cell = run.Cell(args.workload)
        run.prepare()
        run.device_info(cell.workload["chips"])
    except run.Refused as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    faults = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        r = cell.make_run(seed)
        r.setup()
        r.window(args.seconds)
        r.finish()
        out = {"seed": seed, "counters": r.counters,
               "sound": dict((n, v) for n, v, _ in r.check()),
               "control": dict((n, v) for n, v, _ in r.check(control=True))}
        for fault in faults:
            broken = copy.deepcopy(r, memo={id(r.config): r.config})
            broken.plant(fault)
            out[fault] = dict((n, v) for n, v, _ in broken.check())
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
