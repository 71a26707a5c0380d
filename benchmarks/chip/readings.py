#!/usr/bin/env python3
"""The readings that a cell's limits are set from, many seeds in one process.

    python3 benchmarks/chip/readings.py --workload <name> --seeds 1,2,3 \\
        [--program-seconds 3] [--control]

For each seed, one JSON line on standard output:

* `program`: a run of the cell as the benchmark makes it (short window),
  with every number compared and its limit;
* with `--control`, `control`: the same numbers when the plain reference,
  computed one precision step below the configuration's (`high`, three
  bfloat16 passes, for float32 at `highest`), is put in the program's place
  on the same kind of batches. A sound limit lies above every program
  reading and below every control reading.

Not part of a benchmark run: the limits in the traffic files were set from
these readings (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

#: the precision one step below each a configuration can state
LOWER = {"highest": "high"}


def control_numbers(cell, seed: int) -> dict:
    """The control's numbers for one seed: `check_batches` batches of the
    cell's window pool, answered by the reference at the lower precision."""
    import imagegen

    cfg, tr = cell.config, cell.traffic
    drv = cell.driver()
    ref = cell.reference()
    seeds = imagegen.streams(seed)
    st = dict(ref=ref, params=ref.make_params(imagegen.key(seeds["weights"]), cfg))
    tmpl = imagegen.templates(imagegen.key(seeds["templates"]), cfg["classes"],
                              tuple(cfg["input"]))
    st["val_x"], st["val_y"] = imagegen.image_split(
        seeds["val"], tmpl, cfg["calibration"]["val_images"], tr["images"])
    n = tr["check_batches"] * tr["batch"]
    x, _ = imagegen.image_split(seeds["window"], tmpl, n, tr["images"])
    batches = list(x.reshape(tr["check_batches"], tr["batch"], *x.shape[1:]))
    cal, refs = drv.reference_answers(st, cfg, tr, batches, cfg["matmul_precision"])
    ctl_cal, ctl = drv.reference_answers(st, cfg, tr, batches, LOWER[cfg["matmul_precision"]])
    checks = drv.compare(ctl_cal["p_tar"], ctl, cal, refs, [None] * len(batches), tr["limits"])
    # the control's codec is the reference's own wire format: nothing to compare
    return {k: v["value"] for k, v in checks.items() if not k.startswith("codec_")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--program-seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    import harness

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = {"workload": args.workload, "seed": seed}
        res = harness.run_cell(bench, args.workload, seed, args.program_seconds, False)
        line["program"] = {k: v["value"] for k, v in res["checks"].items()}
        line["program_correct"] = res["correct"]
        line["metrics"] = {k: v["value"] for k, v in res["metrics"].items()}
        if args.control:
            line["control"] = control_numbers(cell, seed)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
