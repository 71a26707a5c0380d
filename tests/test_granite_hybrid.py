"""granite-4.0-h-micro (Mamba-2 + GQA NoPE hybrid with Granite's scalings)
against its plain reference (`benchmarks/chip/configs/granite_4_0_h_micro.py`),
on seeded random weights at the SMOKE size, float32, on the CPU; the served
path through `lm_engine`; that each scaling is live; that every other
configuration computes what it computed before the scalings existed; and
the codec's dtype round trip."""
import hashlib
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.calibration import TemperatureScaling
from repro.core.policy import OffloadPlan
from repro.kernels import compress
from repro.models import registry, transformer
from repro.offload import engine as engine_mod

REFERENCE = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "configs"
             / "granite_4_0_h_micro.py")
SMOKE = get_smoke("granite-4.0-h-micro").replace(dtype="float32")
#: float32 on both sides, products at HIGHEST; the two differ only in the
#: order of their sums and the SSD's form (chunked scan against the
#: step-by-step recurrence): a few float32 ulps through four layers, well under this
#: share of the logits' scale (the root mean square of the reference's)
TOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("granite_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ref_config(cfg):
    """The reference's configuration dict (published key names) for `cfg`."""
    return dict(
        hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
        layer_types=["attention" if m == "attn" else "mamba" for m, _ in cfg.layer_plan()],
        num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.num_kv_heads,
        mamba_expand=cfg.ssm_expand, mamba_n_heads=cfg.ssm_heads,
        mamba_d_head=cfg.ssm_head_dim, mamba_d_state=cfg.ssm_state,
        mamba_n_groups=cfg.ssm_n_groups, mamba_d_conv=cfg.ssm_conv,
        shared_intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size,
        rms_norm_eps=cfg.norm_eps, embedding_multiplier=cfg.embedding_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        residual_multiplier=cfg.residual_multiplier, logits_scaling=cfg.logits_scaling,
        exit={"after_layer": cfg.exit_layers[0]},
    )


@pytest.fixture(scope="module")
def weights(ref):
    """Seeded weights from the reference, in float32 for both sides."""
    params = ref.make_params(jax.random.PRNGKey(0), ref_config(SMOKE))
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def tokens():
    # 48 tokens: three SSD chunks of 16, so the inter-chunk states are used
    return jax.random.randint(jax.random.PRNGKey(1), (3, 48), 0, SMOKE.vocab_size)


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / np.sqrt(np.mean(b * b))


def test_prefill_and_partitions_match_the_reference(ref, weights, tokens):
    rc = ref_config(SMOKE)
    with jax.default_matmul_precision("highest"):
        full = transformer.forward_prefill(weights, SMOKE, {"tokens": tokens})
        edge = transformer.edge_forward(weights, SMOKE, {"tokens": tokens})
        cloud = transformer.cloud_forward(weights, SMOKE, edge["hidden"])
    z, h = ref.edge(weights, tokens, "f32", rc)
    final = ref.cloud(weights, h, "f32", rc)
    assert _err(full["exit_logits"][0][:, 0], z) < TOL
    assert _err(edge["exit_logits"][:, 0], z) < TOL
    assert _err(edge["hidden"], h) < TOL
    assert _err(full["logits"][:, 0], final) < TOL
    assert _err(cloud["logits"][:, 0], final) < TOL


def test_decode_through_the_cache_matches_the_reference(ref, weights, tokens):
    """Prefill then step-by-step decode: each step's logits are the
    reference's full forward pass over the prefix."""
    rc = dict(ref_config(SMOKE), exit={"after_layer": SMOKE.num_layers - 1})
    b, s = tokens.shape
    caches = registry.init_cache(SMOKE, b, s)
    with jax.default_matmul_precision("highest"):
        for t in range(s):
            out, caches = registry.decode_step(weights, SMOKE, tokens[:, t:t + 1], caches,
                                               jnp.int32(t))
    _, h = ref.edge(weights, tokens, "f32", rc)
    final = ref.head(weights["final_norm"], weights["embed"]["w"], h[:, -1], ref.dims(rc),
                     "f32", "bd,vd->bv")
    assert _err(out["logits"][:, 0], final) < TOL


def _plan(ref, weights, rc, tokens, share):
    z, _ = ref.edge(weights, tokens, "f32", rc)
    conf = ref.softmax64(np.asarray(z), 0.7).max(-1)
    plan = OffloadPlan(p_tar=float(np.quantile(conf, share)),
                       calibrators=[TemperatureScaling.from_temperature(0.7)])
    return plan.with_compression(1), conf


def test_lm_engine_gates_and_answers_as_the_reference(ref, weights, monkeypatch):
    """The served path, codec level 1: the exit mask is the reference's gate
    away from p_tar, and each refused row's cloud logits are the
    reference's cloud half on the payload the codec decoded."""
    rc = ref_config(SMOKE)
    toks = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, SMOKE.vocab_size)
    plan, conf = _plan(ref, weights, rc, toks, 0.5)
    decoded, logits = [], []
    dec = compress.decode
    monkeypatch.setattr(compress, "decode", lambda e: decoded.append(dec(e)) or decoded[-1])
    with jax.default_matmul_precision("highest"):
        engine = engine_mod.lm_engine(weights, SMOKE, plan, exit_index=0, use_kernel=True)
        cloud = engine.cloud_fn
        engine.cloud_fn = lambda h: logits.append(cloud(h)) or logits[-1]
        out = engine.infer({"tokens": toks})
    clear = np.abs(conf - plan.p_tar) > 1e-4
    assert clear.sum() >= 6
    np.testing.assert_array_equal(out["on_device"][clear], (conf >= plan.p_tar)[clear])
    refused = ~out["on_device"]
    assert refused.any() and len(decoded) == 1 and decoded[0].dtype == jnp.float32
    want = ref.cloud(weights, decoded[0], "f32", rc)
    assert _err(logits[0]["logits"], want) < TOL
    np.testing.assert_array_equal(out["prediction"][refused], np.asarray(want).argmax(-1))


SCALINGS = {
    "embedding_multiplier": 1.0,
    "attention_multiplier": None,
    "residual_multiplier": 1.0,
    "logits_scaling": 1.0,
    "norm_eps": 1e-6,
}


@pytest.mark.parametrize("field", list(SCALINGS))
def test_each_granite_scaling_is_live(field, weights, tokens):
    """Set back to its neutral value, each scaling changes the logits, so
    none can drop out of the program unnoticed."""
    neutral = SMOKE.replace(**{field: SCALINGS[field]})
    a = transformer.forward_prefill(weights, SMOKE, {"tokens": tokens})
    b = transformer.forward_prefill(weights, neutral, {"tokens": tokens})
    diff = max(_err(b["logits"], a["logits"]), _err(b["exit_logits"][0], a["exit_logits"][0]))
    assert diff > 1e-5, field


#: sha256 (first 16 hex digits) of each configuration's prefill logits,
#: exit logits and first decode step's logits, seeded, before the Granite
#: fields existed: the fields' defaults compute the same bits
BEFORE = {
    ("olmo-1b", "float32"): "6644cc04d96bd21f",
    ("olmo-1b", "bfloat16"): "99496b5719d050dd",
    ("qwen3-8b", "float32"): "72369bc236a34d7c",
    ("qwen3-8b", "bfloat16"): "be11d9d3c2ac6b7d",
    ("qwen2-72b", "float32"): "ea645bd7e02e1215",
    ("qwen2-72b", "bfloat16"): "8474cea29763827b",
    ("mamba2-130m", "float32"): "431a42e9169cdc6e",
    ("mamba2-130m", "bfloat16"): "a64d8ebccad38422",
    ("jamba-v0.1-52b", "float32"): "814988fa2ce18e70",
    ("jamba-v0.1-52b", "bfloat16"): "585ff982471e4a83",
    ("granite-moe-3b-a800m", "float32"): "f927fe8e6659a25a",
    ("granite-moe-3b-a800m", "bfloat16"): "7ed5b0b389eb5cce",
    ("chameleon-34b", "float32"): "2db85b27a9eab7d9",
    ("chameleon-34b", "bfloat16"): "a3e32203d37abeee",
    ("internlm2-20b", "float32"): "ca02e186d6ce76ad",
    ("internlm2-20b", "bfloat16"): "26e837708539b0f1",
}


@pytest.mark.parametrize("arch,dtype", list(BEFORE))
def test_other_configurations_compute_the_same_bits(arch, dtype):
    cfg = get_smoke(arch).replace(dtype=dtype)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    out = registry.forward_prefill(params, cfg, {"tokens": toks})
    caches = registry.init_cache(cfg, 2, 16)
    dec, _ = registry.decode_step(params, cfg, toks[:, :1], caches, jnp.int32(0))
    arrs = [out["logits"], *out["exit_logits"], dec["logits"], *dec["exit_logits"]]
    digest = hashlib.sha256(b"".join(np.asarray(a, np.float32).tobytes() for a in arrs))
    assert digest.hexdigest()[:16] == BEFORE[(arch, dtype)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_codec_decodes_to_the_dtype_it_was_given(dtype):
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 8, 64)).astype(dtype)
    enc = compress.encode(x, 1)
    back = compress.decode(enc)
    assert back.dtype == dtype and back.shape == x.shape
    # the same float32 values as from the payload's float32 image, rounded
    want = compress.decode(compress.encode(x.astype(jnp.float32), 1)).astype(dtype)
    np.testing.assert_array_equal(np.asarray(back, np.float32), np.asarray(want, np.float32))


def test_lm_engine_partitions_take_the_weights_as_arguments(weights):
    """Neither partition's program embeds a weight: lowered, it holds no
    constant of more than a few elements, and the embedding (the edge's
    lookup table, the cloud's tied head) is one of its parameters."""
    import re

    plan = OffloadPlan(p_tar=0.5, calibrators=[TemperatureScaling.from_temperature(1.0)])
    engine = engine_mod.lm_engine(weights, SMOKE, plan)
    toks = jnp.zeros((2, 16), jnp.int32)
    hidden = jnp.zeros((2, 16, SMOKE.d_model), jnp.float32)
    embed = "tensor<%dx%dxf32>" % (SMOKE.vocab_size, SMOKE.d_model)
    for fn, arg in ((engine.edge_fn, {"tokens": toks}), (engine.cloud_fn, hidden)):
        text = fn.func.lower(*fn.args, arg, **fn.keywords).as_text()
        sizes = [int(np.prod([int(d) for d in shape.split("x")[:-1]] or [1]))
                 for shape in re.findall(r"constant[^\n]*: tensor<([0-9x]*[a-z0-9]+)>", text)]
        assert max(sizes, default=0) < 64, max(sizes)
        main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S).group(1)
        assert embed in main
