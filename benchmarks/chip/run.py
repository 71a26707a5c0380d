#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Cells are the `workloads` of `BENCHMARK.json` at the root of the checkout.
`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics: those on the host's clock from the measured window, the
device's from a profiler trace of a second window that follows it. The last
line of standard output is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and
`checks` last: each number compared with its limit). Without a TPU, or with
fewer chips than the cell asks for, it exits with code 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness

    return harness.main(args)


if __name__ == "__main__":
    sys.exit(main())
