"""The control, the plain reference one precision step below the
configuration's (`high` for float32 at `highest`) put in the program's
place, comes out not correct; the reference in its own place comes out
correct. At a test's size on the CPU; the chip readings are in PERF.md."""
import json

import harness
import readings
from conftest import CHECKOUT, small_root

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def _fails(numbers, limits):
    return any(v > limits[k] for k, v in numbers.items())


def test_control_fails_and_reference_passes(tmp_path, restore_jax_config):
    name = BENCH["workloads"][0]["name"]
    cell = harness.find_cell(BENCH, name, root=small_root(tmp_path, name))
    limits = cell.traffic["limits"]
    for seed in (3, 2**32 + 1):
        assert _fails(readings.control_numbers(cell, seed), limits)
    saved = readings.LOWER
    try:
        readings.LOWER = {"highest": "highest"}
        assert not _fails(readings.control_numbers(cell, 3), limits)
    finally:
        readings.LOWER = saved
