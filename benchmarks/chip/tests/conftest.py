"""Tests of the chip benchmark's own files; they run on the CPU and never
look for a chip. Sizes here are cut for a test run; the cells' sizes are in
`traffic/` and `configs/`."""
import json
import shutil
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
for p in (CHIP, CHECKOUT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def small_root(tmp: Path, workload: str) -> Path:
    """A copy of the benchmark's files whose traffic and validation split
    are cut to a size a test run holds (batch 16); widths are untouched."""
    root = tmp / "chip"
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    tpath = root / "traffic" / f"{cell['traffic']}.json"
    traffic = json.loads(tpath.read_text())
    traffic.update(batch=16, pool_images=128, window_batches=16, warm_images=128,
                   check_batches=16)
    tpath.write_text(json.dumps(traffic))
    cpath = root / "configs" / f"{cell['config']}.json"
    config = json.loads(cpath.read_text())
    config["calibration"]["val_images"] = 256
    cpath.write_text(json.dumps(config))
    return root


@pytest.fixture
def restore_jax_config():
    """The served driver sets the process's matmul precision; put it back."""
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)
