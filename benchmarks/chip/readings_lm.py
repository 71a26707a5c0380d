#!/usr/bin/env python3
"""The readings that a served LM cell's limits are set from, many seeds in
one process.

    python3 benchmarks/chip/readings_lm.py --workload <name> --seeds 1,2 \\
        [--program-seconds 5]

For each seed, one JSON line on standard output: `program`, a run of the
cell as the benchmark makes it (short window), every number compared and
its limit; and `control`, the same numbers when the plain reference with
both operands of every product rounded to float8 e4m3 (one precision step
below the configuration's bfloat16) is put in the program's place on the
same batches (the driver's `control_numbers`); and `runner_up`, the
program's numbers with each exiting prompt answered by the runner-up of its
exit logits (`runner_up_numbers`), a fault `edge_gap` must catch. A sound
limit lies above every program reading, and the control fails at least one.

Not part of a benchmark run: the limits in the traffic file were set from
these readings (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--program-seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import harness

    bench = harness.load_benchmark()
    for seed in (int(s) for s in args.seeds.split(",")):
        control, runner_up = [], []
        res = harness.run_cell(bench, args.workload, seed, args.program_seconds, False,
                               hooks={"control": control.append,
                                      "runner_up": runner_up.append})
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": {k: v["value"] for k, v in res["checks"].items()},
                          "program_correct": res["correct"],
                          "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                          "device": res["device"], "control": control[0],
                          "runner_up": runner_up[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
