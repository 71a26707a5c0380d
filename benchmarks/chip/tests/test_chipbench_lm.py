"""The served LM cell's own files: its driver run whole on the CPU at a cut
size, sound and with its cloud answers broken; its readers on recorded
trace facts; its operation counts against counts made by hand; and the
reading of op scopes from a trace's metadata."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import workcount_lm
import xplane
import xplane_ops
from conftest import CHECKOUT, CHIP, small_root

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
WORKLOAD = "granite-h-micro-p1k-offload80"
CFG = json.loads((CHIP / "configs" / "granite_4_0_h_micro.json").read_text())
TRAFFIC = json.loads((CHIP / "traffic" / "lm_b8_s1024_offload80.json").read_text())
DATA = Path(__file__).resolve().parent / "data"


def lm_small_root(tmp: Path) -> Path:
    """`small_root`, then the configuration cut to four layers (Mamba,
    attention, Mamba, attention; the exit after the first attention layer)
    at hidden size 128, and the traffic to batches of 4 prompts of 32
    tokens. Every Granite scaling and each kind of layer is kept."""
    root = small_root(tmp, WORKLOAD)
    cpath = root / "configs" / "granite_4_0_h_micro.json"
    cfg = json.loads(cpath.read_text())
    cfg.update(num_hidden_layers=4, layer_types=["mamba", "attention"] * 2, hidden_size=128,
               num_attention_heads=2, num_key_value_heads=1, mamba_d_head=32,
               mamba_n_heads=8, mamba_d_state=16, mamba_chunk_size=16,
               shared_intermediate_size=256, vocab_size=512, classes=512)
    cfg["exit"] = dict(cfg["exit"], after_layer=1)
    cfg["calibration"]["val_prompts"] = 32
    cpath.write_text(json.dumps(cfg))
    tpath = root / "traffic" / "lm_b8_s1024_offload80.json"
    traffic = json.loads(tpath.read_text())
    traffic.update(batch=4, seq_len=32, pool_prompts=32, window_batches=16, warm_prompts=16,
                   check_batches=2, reference_rows=2)
    tpath.write_text(json.dumps(traffic))
    return root


def _cloud_answer_altered(engine):
    import jax.numpy as jnp

    cloud = engine.cloud_fn

    def broken(h):
        out = cloud(h)
        return {"logits": jnp.roll(out["logits"], 1, axis=-1)}

    engine.cloud_fn = broken


def _payload_rows_reversed(engine):
    """The edge ships every row's hidden state in reverse order: the cloud
    answers what it is given right, from the wrong prompt."""
    edge = engine.edge_fn

    def broken(batch):
        out = edge(batch)
        return dict(out, payload=out["payload"][::-1])

    engine.edge_fn = broken


def _edge_answer_runner_up(engine):
    """Exiting prompts answered with the runner-up of their exit logits."""
    import dataclasses

    import jax.numpy as jnp

    gate = engine.plan.gate

    def broken(z, **k):
        g = gate(z, **k)
        return dataclasses.replace(g, prediction=jnp.argsort(z, axis=-1)[:, -2])

    engine.plan.gate = broken


FAULTS = {"none": (None, None),
          "cloud_answer": (_cloud_answer_altered, "cloud_logit_err"),
          "payload_rows": (_payload_rows_reversed, "payload_err"),
          "edge_answer": (_edge_answer_runner_up, "edge_gap")}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_lm_run_is_correct_only_when_sound(fault, tmp_path, restore_jax_config):
    from repro.kernels import compress

    encode, decode = compress.encode, compress.decode
    hook, caught_by = FAULTS[fault]
    hooks = {"engine": hook} if hook else {}
    try:
        res = harness.run_cell(BENCH, WORKLOAD, seed=2**33 + 11, seconds=0.5, trace=False,
                               root=lm_small_root(tmp_path), require_accelerator=False,
                               hooks=hooks, cache=False)
    finally:
        compress.encode, compress.decode = encode, decode
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"served_images_per_s", "served_batch_p95_ms", "setup_s"}
    assert res["correct"] is (fault == "none"), res["checks"]
    if caught_by:
        checks = res["checks"]
        assert checks[caught_by]["value"] > checks[caught_by]["limit"], caught_by


def test_lm_partitions_compile_once_in_set_up(tmp_path, restore_jax_config):
    """`compile_partitions` compiles the edge at the batch shape and the
    cloud at every refused count; the validation, the warm-up and the
    window then find each program compiled, and compile no other."""
    import jax
    from repro.offload import engine as engine_mod
    from repro.kernels import compress

    sizes = lambda: (engine_mod._lm_edge._cache_size(), engine_mod._lm_cloud._cache_size())
    seen = {}
    jax.clear_caches()
    encode, decode = compress.encode, compress.decode
    try:
        res = harness.run_cell(BENCH, WORKLOAD, seed=2**33 + 12, seconds=0.5, trace=False,
                               root=lm_small_root(tmp_path), require_accelerator=False,
                               hooks={"engine": lambda engine: seen.update(built=sizes())},
                               cache=False)
    finally:
        compress.encode, compress.decode = encode, decode
    assert res["correct"], res["checks"]
    assert seen["built"] == sizes() == (1, 4)


def test_lm_reference_calibration_is_the_alexnet_references():
    """The reference's threaded float64 NLL, temperature fit and tied
    interval give what the B-AlexNet reference's give: the same NLL and
    confidences, a fit at the same optimal NLL, the same tied interval."""
    ref = harness.load_module(CHIP / "configs" / "granite_4_0_h_micro.py")
    common = ref._COMMON
    rng = np.random.default_rng(5)
    for n, v, scale in ((300, 50, 2.0), (64, 1000, 0.3), (40, 4000, 0.02)):
        z = (rng.normal(size=(n, v)) * scale).astype(np.float32)
        y = rng.integers(0, v, n)
        y[: n // 3] = z[: n // 3].argmax(-1)
        with ref.ThreadPoolExecutor(3) as pool:
            nll = ref.Nll(z, y, pool, 3)
            for t in (0.05, 0.7, 3.0):
                assert nll(t) == pytest.approx(common.nll(z, y, t), rel=1e-12)
                np.testing.assert_allclose(nll.confidence(t), common.softmax64(z, t).max(-1),
                                           rtol=1e-12)
            t = ref.fit_temperature(nll, 0.05, 20.0)
            lo, hi = ref.tied_interval(nll, t, 0.05, 20.0, 2e-6)
        t_ = common.fit_temperature(z, y, 0.05, 20.0)
        lo_, hi_ = common.tied_interval(z, y, t_, 0.05, 20.0, 2e-6)
        # the optimum is flat: both fits reach its NLL to float64 resolution
        assert common.nll(z, y, t) == pytest.approx(common.nll(z, y, t_), rel=1e-14)
        assert (lo, hi) == pytest.approx((lo_, hi_), rel=1e-10)


def test_lm_configuration_is_the_programs():
    """The driver's reading of the configuration file is the repo's
    registered configuration, name and citation aside."""
    from repro.configs import get_config

    drv = harness.load_module(CHIP / "drivers" / "served_lm_offload.py")
    want = get_config("granite-4.0-h-micro")
    assert drv.model_config(CFG).replace(name=want.name, source=want.source) == want


def _facts(**kw):
    cell = SimpleNamespace(config=CFG, traffic=TRAFFIC)
    base = dict(trace=xplane.reduce_trace(DATA), cell=cell,
                peaks=harness.peaks_for("TPU v5 lite"), images=96, offloaded=77,
                window_s=20.0, traced=dict(batches=12, images=96, offloaded=2))
    return SimpleNamespace(**dict(base, **kw))


def _scopes(red):
    """A name stack for every recorded op: copies under `mamba`, fusions
    under `attention`, the rest under neither."""
    out = {}
    for key in red.op_s:
        name = key.split("/", 1)[-1]
        text = red.op_text.get(key, key)
        scope = "jit(_lm_edge)/while/body/mamba/copy:" if "copy" in text else (
            "jit(_lm_cloud)/attention/dot_general:" if "%fusion" in text else "jit(x)/add:")
        out[name] = scope
    return out


def test_lm_readers_read_trace_facts_and_nothing_without_them():
    red = xplane.reduce_trace(DATA)
    f = _facts(op_scopes=_scopes(red))
    values = {m: harness.load_module(CHIP / "metrics" / f"{m}.py").read(f)
              for m in ("mamba_mixer_share.lm", "attention_mixer_share.lm",
                        "codec_roofline.lm", "mfu.lm")}
    assert all(isinstance(v, float) and v > 0 for v in values.values()), values
    assert values["mamba_mixer_share.lm"] + values["attention_mixer_share.lm"] <= 100.0
    seq = TRAFFIC["seq_len"]
    flops = 96 * workcount_lm.edge_flops(CFG, seq) + 77 * workcount_lm.cloud_flops(CFG, seq)
    assert values["mfu.lm"] == pytest.approx(flops / 20.0 / 197e12 * 100.0)
    empty = _facts(op_scopes=None, peaks=None)
    for m in values:
        assert harness.load_module(CHIP / "metrics" / f"{m}.py").read(empty) is None, m
    unscoped = _facts(op_scopes={k.split("/", 1)[-1]: "jit(x)/add:" for k in red.op_s})
    assert harness.load_module(CHIP / "metrics" / "mamba_mixer_share.lm.py").read(unscoped) \
        is None


def test_op_scopes_are_read_from_the_trace_metadata():
    red = xplane.reduce_trace(DATA)
    scopes = xplane_ops.op_scopes(DATA)
    named = [k for k in red.op_s if k in scopes]
    assert named and all(scopes[k].startswith(("jit(", "args", "logits", "words"))
                         for k in named)
    conv = [k for k in named if scopes[k] == "jit(<lambda>)/conv_general_dilated:"]
    assert conv
    secs, calls = xplane_ops.time_in_scope(red, scopes, "jit(_encode_wire)")
    assert calls > 0 and 0 < secs < red.busy_s
    assert xplane_ops.time_in_scope(red, scopes, "mamba") == (0, 0)


def test_lm_work_counts_by_hand():
    seq = 1024
    d, ff, v = 2048, 8192, 100352
    mlp = 3 * 2 * seq * d * ff
    # attention layer 5: q and o 2048 x 2048, k and v 2048 x 512, causal
    # scores and values over 32 heads of 64
    attn = 2 * seq * d * (2048 + 1024) + 2 * seq * 2048 * d \
        + 2 * 2 * (seq * (seq + 1) // 2) * 32 * 64
    assert workcount_lm.layer_flops(CFG, 5, seq) == attn + mlp
    # Mamba layer 0: in_proj 2048 x 8512, out_proj 4096 x 2048, conv 4 over
    # 4352 channels, and per chunk of 256: C.B, its weights times x over 64
    # heads of 64, and the states in and out at state size 128
    proj = 2 * seq * d * 8512 + 2 * seq * 4096 * d + 2 * seq * 4 * 4352
    ssd = 4 * (2 * 256 * 256 * 128 + 2 * 256 * 256 * 64 * 64 + 4 * 256 * 64 * 64 * 128)
    assert workcount_lm.layer_flops(CFG, 0, seq) == proj + ssd + mlp
    assert workcount_lm.edge_flops(CFG, seq) == \
        9 * (proj + ssd + mlp) + attn + mlp + 2 * d * v
    assert workcount_lm.edge_flops(CFG, seq) / 1e12 == pytest.approx(1.57, abs=0.01)
    assert workcount_lm.cloud_flops(CFG, seq) / 1e12 == pytest.approx(4.72, abs=0.01)
    # one bf16 payload row: 4 MiB read, 2 MiB of int8 words and 16,384
    # float32 scales written; and back
    n = workcount_lm.payload_elements(CFG, seq)
    per_row = 2 * (2 * n + n + 4 * n // 128)
    assert workcount_lm.codec_bytes(3, n, 8, 128, 2) == 3 * per_row
