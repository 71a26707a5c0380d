"""The served engine's phase counters (`EngineStats`), taken over the
measured window of a traced run as the benchmark's host-clock metrics are:
they lie inside the benchmark's own clock around `infer` and cover it."""
import contextlib
import json

from conftest import CHECKOUT

#: `infer`'s host phases: with the two partitions they tile each call
HOST = ("gate_time_s", "gate_sync_time_s", "gather_time_s", "codec_time_s",
        "fetch_time_s")


def test_engine_phases_tile_the_measured_infer_time(tmp_path, monkeypatch,
                                                    restore_jax_config):
    import harness
    from conftest import small_root

    engines, window = [], {}
    inner = harness.Run.window

    @contextlib.contextmanager
    def measured(run):
        before = dict(vars(engines[0].stats))
        with inner(run):
            yield
        after = vars(engines[0].stats)
        window.update({k: after[k] - before[k] for k in before})

    monkeypatch.setattr(harness.Run, "window", measured)
    w = "alexnet-b1-int8-offload80"
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    res = harness.run_cell(bench, w, seed=2**32 + 11, seconds=0.5, trace=True,
                           root=small_root(tmp_path, w), require_accelerator=False,
                           hooks={"engine": engines.append}, cache=False)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    batches = window["edge_calls"]
    assert batches > 0 and window["cloud_calls"] > 0
    per_batch = {k: window[k] / batches * 1e3 for k in HOST + ("edge_dispatch_s",)}
    assert all(v > 0 for v in per_batch.values()), per_batch
    host_ms = sum(per_batch[k] for k in HOST)
    assert host_ms <= m["infer_host_ms"]["value"] + 1e-3
    assert host_ms >= 0.9 * m["infer_host_ms"]["value"]
    assert per_batch["edge_dispatch_s"] <= m["edge_ms"]["value"]
