#!/usr/bin/env python3
"""Readings of the control on the chip, for setting the check limits.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \\
        [--slots 2000]

The control is the plain reference computed in bfloat16, the precision
below the configuration's float32, put in the program's place: it is
compared with the float32 reference exactly as a run compares the
program, at the cell's own size (`--slots` served slots, as many as a
run's measured call replays; a fleet cell compares as many calls as a
run does). Prints one JSON line of readings per seed. The benchmark's
own runs never run it. Needs the chip, as run.py does.
"""
import argparse
import json
import sys

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--slots", type=int, default=2000)
    args = ap.parse_args(argv)
    plan = run.cell_plan(run.read_json(run.ROOT / "BENCHMARK.json"),
                         args.workload, trace=False)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.check_device(plan["cell"]["chips"],
                     run.read_json(run.BENCH / "peaks.json")["devices"])
    path = run.load_module(plan["path"])
    for seed in (int(s) for s in args.seeds.split(",")):
        if plan["mix"]["path"] == "serve":
            checks = path.control(plan["config"], plan["mix"], seed,
                                  args.slots)
        else:
            checks = path.control(plan["config"], plan["mix"], seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": checks}), flush=True)


if __name__ == "__main__":
    main()
