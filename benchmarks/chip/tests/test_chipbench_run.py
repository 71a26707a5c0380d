"""`run.py` measures nothing off the chip: without a TPU, and in a directory
that holds only the benchmark's own files, it exits non-zero and prints no
result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import CHECKOUT, CHIP

ARGS = ["--workload", "alexnet-b1-int8-offload80", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj), line


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p.stdout)


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p.stdout)


def test_traced_run_reads_the_host_clock_from_the_untraced_window(tmp_path,
                                                                   restore_jax_config):
    """With `--trace 1` the host-clock metrics come from the measured
    window and the trace from a second one; off the chip the device's
    metrics find nothing to read and are left out."""
    import harness
    from conftest import small_root

    w = "alexnet-b1-int8-offload10"
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    res = harness.run_cell(bench, w, seed=2**32 + 9, seconds=0.5, trace=True,
                           root=small_root(tmp_path, w), require_accelerator=False,
                           cache=False)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    assert {"edge_ms", "cloud_ms", "infer_host_ms", "compiles_in_window.served",
            "setup_programs.served"} <= set(m)
    assert not {"device_idle_share.served", "exit_gate_roofline", "codec_roofline",
                "mfu.served", "served_images_per_s", "setup_s"} & set(m)
    assert res["device"]["window_s"] == pytest.approx(0.5, abs=0.3)
