"""The harness finds cells, traffic mixes and metrics by name, so a new one
is added with files and entries alone; and BENCHMARK.json points at files
that exist."""
import json
import shutil
from types import SimpleNamespace

import pytest

import harness
from conftest import CHECKOUT, CHIP

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def test_every_cell_metric_and_configuration_has_its_files():
    for cfg in BENCH["configs"]:
        assert (CHECKOUT / cfg["file"]).is_file()
        assert cfg["file"] == f"benchmarks/chip/configs/{cfg['name']}.json"
        assert (CHIP / "configs" / f"{cfg['name']}.py").is_file()
    for w in BENCH["workloads"]:
        cell = harness.find_cell(BENCH, w["name"])
        assert (CHIP / "drivers" / f"{cell.traffic['driver']}.py").is_file()
        assert cell.end_to_end and cell.per_layer
    for m in BENCH["per_layer"]:
        assert hasattr(harness.load_module(CHIP / "metrics" / f"{m['name']}.py"), "read")


def test_cell_traffic_and_metric_added_by_files_only(tmp_path):
    root = tmp_path / "chip"
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    base = BENCH["workloads"][0]
    traffic = json.loads((root / "traffic" / f"{base['traffic']}.json").read_text())
    traffic["offload_share"] = 0.5
    (root / "traffic" / "b256_offload50.json").write_text(json.dumps(traffic))
    (root / "metrics" / "offloaded_share.py").write_text(
        "def read(f):\n    return 100.0 * f.offloaded / f.images\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(dict(base, name="alexnet-b1-int8-offload50",
                                   traffic="b256_offload50"))
    bench["per_layer"].append({"name": "offloaded_share", "unit": "%", "better": "lower",
                               "source": "program_counter", "layer": "engine host path",
                               "moves": "served_images_per_s",
                               "workloads": ["alexnet-b1-int8-offload50"]})
    cell = harness.find_cell(bench, "alexnet-b1-int8-offload50", root=root)
    assert cell.traffic["offload_share"] == 0.5
    assert cell.config == json.loads((CHIP / "configs" / "b_alexnet.json").read_text())
    assert [m["name"] for m in cell.per_layer][-1] == "offloaded_share"
    assert "offloaded_share" not in [m["name"] for m in
                                     harness.find_cell(bench, base["name"], root).per_layer]
    reader = cell.reader("offloaded_share")
    assert reader.read(SimpleNamespace(offloaded=64, images=256)) == 25.0
    assert cell.driver().run is not None


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.find_cell(BENCH, "no-such-cell")
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v0 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
