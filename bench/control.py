#!/usr/bin/env python3
"""Read a cell's check numbers for the program and for its control.

    python3 bench/control.py --workload stablelm-3b.rb --seeds 1,2,3 \\
        --seconds 10

The control is the plain reference put in the program's place, computed
in bfloat16, the precision below the float32 the device engine states
(``device_dtype`` in the configuration): for a deterministic search, the
reference's own search in bfloat16 answers every request; for annealing,
each of the program's designs is scored in bfloat16 and that score
stands for both objectives the answer claims. Each seed runs the cell's
window after one set-up and warm-up, as ``run.py`` does, then prints one
JSON line: the seed, the program's readings and the control's, and the
limits. The benchmark's own runs never run this; it is how the limits
were set (``PERF.md``) and shows that the control fails them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import program as prog_door  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    try:
        spec = run.load_cell(args.workload)
        if not prog_door.importable():
            raise run.SetupError("the program's sources are not beside "
                                 "the benchmark", 2)
        prog_door.compilation_cache()
        devs = run.chips(spec["cell"])
    except run.SetupError as err:
        print(f"control: {err}", file=sys.stderr)
        return err.code
    clock = run.CompileClock()
    traffic, config = spec["traffic"], spec["config"]
    check = run.module("checks", traffic["check"])
    loop = run.module("loops", traffic["loop"])
    program = prog_door.Program(config, traffic)
    seeds = [int(s) for s in args.seeds.split(",")]
    loop.warm_up(program, traffic, seeds[0])
    cache: dict = {}
    for seed in seeds:
        win = loop.window(program, traffic, seed, args.seconds)
        answers = win["answers"]
        mine = check.check(answers, config, traffic)
        claims = check.control_claims(answers, config, traffic, cache)
        ctrl = check.check(answers, config, traffic, claims=claims)
        print(json.dumps({
            "seed": seed, "designs": len(answers), "failed": win["failed"],
            "device": f"{devs[0].device_kind} x{len(devs)}",
            "compiles": clock.reading()[1],
            "program": {k: run._num(v) for k, v in mine.items()},
            "control": {k: run._num(v) for k, v in ctrl.items()},
            "limits": traffic["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
