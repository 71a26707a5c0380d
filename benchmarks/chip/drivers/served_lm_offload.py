"""Served LM offload cells: a hybrid LM's prefill through `lm_engine` and
`OffloadEngine.infer`, classifying each prompt by its last position.

Set-up (all counted in `setup_s`):

1. weights from the seed, on the device (`configs/<c>.py` `make_params`,
   in the layout of the program's stack, in the configuration's dtype);
2. the program's `ModelConfig`, read from the configuration file, and an
   engine from `lm_engine` (both partitions jitted with the weights as
   arguments), gating with the Pallas exit-gate kernel; its edge partition
   at the batch shape and its cloud partition at every refused count 1 to
   `batch` are compiled side by side, on threads (`compile_partitions`);
3. the validation split (`calibration.val_prompts` prompts of uniform
   random token ids): the program's edge partition at the window's batch
   shape gives the exit's logits and the payload, its cloud partition on
   the uncompressed payload the full model's answer, whose argmax is each
   prompt's label; `make_plan`'s temperature fit on them, and `p_tar` at
   the quantile that offloads the traffic's `offload_share`, with the
   configuration's codec level;
4. the window's traffic, on the host: a pool of `pool_prompts` prompts from
   the window stream and `window_batches` batches drawn from it (slices of
   seeded permutations, each a fresh draw);
5. warm-up: one batch of each refused count, 0 to `batch`, made from the
   warm stream's prompts and served, so that every shape of the gather,
   codec and cloud programs is compiled before the window.

Window: a closed loop with one batch in flight, as `served_offload`. A
seeded reservoir keeps `check_batches` of the measured window's batches
with the exit logits, codec input, wire image, decoded payload and cloud
logits that the program produced for each. With `--trace 1` the loop goes
on for a second, traced window, and the trace's op names are mapped to the
source scopes (`mamba`, `attention`, `mlp`) they came from (`xplane_ops`).

Check, once the window has closed: the plain reference (`configs/<c>.py`,
float32) runs the kept batches' prompts through the exit, whose hidden
state the program's payload of the refused rows is held to, and the
program's decoded payload of those rows through the cloud half; it
calibrates itself on the validation prompts against the same labels; and
`compare` sets each number against its limit from the traffic file.

Each step's seconds go to standard error (`time: <step> <s>`).
"""
from __future__ import annotations

import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

import imagegen
import xplane_ops
from harness import load_module

NUMBERS = ("p_tar_err", "gate_flips", "edge_conf_err", "edge_logit_err", "edge_gap",
           "payload_err", "cloud_logit_err", "cloud_gap", "codec_mismatch", "codec_uncaptured")

_SERVED = load_module(Path(__file__).with_name("served_offload.py"))
draw_batches = _SERVED.draw_batches
_outside = _SERVED._outside


def prompts(stream_seed: int, n: int, seq: int, vocab: int) -> np.ndarray:
    """(n, seq) int32 token ids, uniform over the vocabulary."""
    return np.random.default_rng(stream_seed).integers(0, vocab, (n, seq), dtype=np.int32)


def model_config(cfg):
    """The program's `ModelConfig` for the configuration file `cfg`."""
    from repro.configs.base import ModelConfig

    kinds = cfg["layer_types"]
    attn = [i for i, k in enumerate(kinds) if k == "attention"]
    every = attn[1] - attn[0] if len(attn) > 1 else len(kinds)
    mc = ModelConfig(
        name=cfg["name"], family="hybrid", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["shared_intermediate_size"],
        vocab_size=cfg["vocab_size"], use_rope=cfg["position_embedding_type"] != "nope",
        attn_every=every, attn_offset=attn[0] % every, ssm_state=cfg["mamba_d_state"],
        ssm_expand=cfg["mamba_expand"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        ssm_n_groups=cfg["mamba_n_groups"],
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        embedding_multiplier=cfg["embedding_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        exit_layers=(cfg["exit"]["after_layer"],), dtype=cfg["dtype"])
    plan = ["attention" if m == "attn" else "mamba" for m, _ in mc.layer_plan()]
    if plan != kinds or mc.ssm_heads != cfg["mamba_n_heads"]:
        raise ValueError("the configuration's layer types or Mamba heads do not fit "
                         "one attention period")
    return mc


class Capture:
    """For a batch the reservoir keeps: what the program produced at each
    step, recorded where the engine receives it (outside any test hook)."""

    def __init__(self):
        self.keep = False
        self.taken: dict = {}

    def begin(self, keep: bool):
        self.keep, self.taken = keep, {}

    def install(self, engine, compress):
        edge, cloud = engine.edge_fn, engine.cloud_fn
        enc, dec = compress.encode, compress.decode

        def edge_fn(batch):
            out = edge(batch)
            if self.keep:
                self.taken["exit_logits"] = out["exit_logits"]
            return out

        def cloud_fn(h):
            out = cloud(h)
            if self.keep:
                self.taken["cloud_logits"] = out["logits"]
            return out

        def encode(x, level):
            out = enc(x, level)
            if self.keep:
                self.taken.update(codec_in=x, words=out.words, scales=out.scales)
            return out

        def decode(e):
            out = dec(e)
            if self.keep:
                self.taken["decoded"] = out
            return out

        engine.edge_fn, engine.cloud_fn = edge_fn, cloud_fn
        compress.encode, compress.decode = encode, decode
        return lambda: (setattr(compress, "encode", enc), setattr(compress, "decode", dec))


def _took(step: str, t0: float) -> float:
    """Print the seconds since `t0` that `step` took; return the clock."""
    now = time.perf_counter()
    print(f"time: {step} {now - t0:.1f} s", file=sys.stderr, flush=True)
    return now


def compile_partitions(engine, tr) -> None:
    """The edge partition at the batch shape and the cloud partition at
    each refused count 1 to `batch`, each run once on zeros from its own
    thread, so that their compiles (one program each) proceed side by side
    rather than one after another in the set-up's first calls. The zeros
    take the shapes, dtypes and placement of the served path's inputs: the
    host's token ids, and the edge's payload on the default device."""
    import jax
    import jax.numpy as jnp

    batch = tr["batch"]
    tokens = np.zeros((batch, tr["seq_len"]), np.int32)
    payload = jax.eval_shape(engine.edge_fn, {"tokens": tokens})["payload"]

    def cloud(k):
        return engine.cloud_fn(jnp.zeros((k,) + payload.shape[1:], payload.dtype))

    with ThreadPoolExecutor(batch + 1) as pool:
        jobs = [pool.submit(engine.edge_fn, {"tokens": tokens})]
        jobs += [pool.submit(cloud, k) for k in range(1, batch + 1)]
        jax.block_until_ready([j.result() for j in jobs])


def _blocks(x, rows):
    return [x[i:i + rows] for i in range(0, len(x), rows)]


def _confidence(engine, z):
    return np.asarray(engine.plan.gate(z, branch=engine.branch,
                                       use_kernel=engine.use_kernel).confidence)


def _edge_confidence(engine, x, rows):
    """The program's calibrated exit confidence for each prompt of x (a
    multiple of `rows`), through its edge partition and gate."""
    return np.concatenate([_confidence(engine, engine.edge_fn({"tokens": blk})["exit_logits"])
                           for blk in _blocks(x, rows)])


def build(r, devices):
    """Set-up steps 1 to 3: (engine, state) for run `r`."""
    from repro.core import make_plan
    from repro.core.calibration import TemperatureScaling
    from repro.core.policy import OffloadPlan
    from repro.offload.engine import lm_engine

    cfg, tr = r.cell.config, r.cell.traffic
    t0 = time.perf_counter()
    ref = r.cell.reference()
    seeds = imagegen.streams(r.seed)
    served, batch = cfg["served"], tr["batch"]
    mc = model_config(cfg)
    params = ref.make_params(imagegen.key(seeds["weights"]), cfg)
    t0 = _took("weights", t0)
    ident = OffloadPlan(p_tar=0.5, calibrators=[TemperatureScaling.from_temperature(1.0)])
    engine = lm_engine(params, mc, ident, exit_index=served["exit_index"], use_kernel=True)
    compile_partitions(engine, tr)
    t0 = _took("partitions compiled", t0)
    val = prompts(seeds["val"], cfg["calibration"]["val_prompts"], tr["seq_len"],
                  cfg["vocab_size"])
    z_val, labels = [], []
    for blk in _blocks(val, batch):
        out = engine.edge_fn({"tokens": blk})
        z_val.append(np.asarray(out["exit_logits"], np.float32))
        labels.append(np.asarray(engine.cloud_fn(out["payload"])["logits"]).argmax(-1))
    z_val, labels = np.concatenate(z_val), np.concatenate(labels).astype(np.int32)
    t0 = _took("validation prompts served", t0)
    plan = make_plan([z_val], labels, p_tar=0.5)
    engine.plan = plan
    conf = np.concatenate([_confidence(engine, z) for z in _blocks(z_val, batch)])
    p_tar = float(np.quantile(conf, tr["offload_share"]))
    engine.plan = plan.with_p_tar(p_tar).with_compression(served["codec_level"])
    print(f"calibration: T {float(plan.temperatures[0])!r}, exit agrees with the full model "
          f"on {float((z_val.argmax(-1) == labels).mean()):.3f} of the validation prompts, "
          f"confidence quantiles (0, .2, .5, .8, 1) "
          f"{np.quantile(conf, [0, .2, .5, .8, 1]).round(5).tolist()}", file=sys.stderr)
    _took("plan fitted", t0)
    state = dict(ref=ref, params=params, seeds=seeds, val=val, labels=labels, p_tar=p_tar,
                 temperature=float(plan.temperatures[0]))
    return engine, state


def warm(engine, st, cfg, tr) -> int:
    """Set-up step 5: serve one batch of each refused count 0 to `batch`,
    made from the warm stream's prompts; return how many came out with
    another count."""
    batch = tr["batch"]
    x = prompts(st["seeds"]["warm"], tr["warm_prompts"], tr["seq_len"], cfg["vocab_size"])
    refused = _edge_confidence(engine, x, batch) < st["p_tar"]
    off, on = np.flatnonzero(refused), np.flatnonzero(~refused)
    other = 0
    for k in range(batch + 1):
        pick = np.concatenate([np.resize(off, k), np.resize(on, batch - k)])
        other += int((~engine.infer({"tokens": x[pick]})["on_device"]).sum() != k)
    return other


def run(r, devices) -> dict:
    from repro.kernels import compress

    cfg, tr = r.cell.config, r.cell.traffic
    engine, st = build(r, devices)
    if "engine" in r.hooks:
        r.hooks["engine"](engine)
    cap = Capture()
    restore = cap.install(engine, compress)
    try:
        batch = tr["batch"]
        pool = prompts(st["seeds"]["window"], tr["pool_prompts"], tr["seq_len"],
                       cfg["vocab_size"])
        batches = draw_batches(st["seeds"]["window"], len(pool), batch, tr["window_batches"])
        t0 = time.perf_counter()
        warm_other = warm(engine, st, cfg, tr)
        _took("warm-up", t0)
        keep_rng = np.random.default_rng(st["seeds"]["check"])
        n_keep = tr["check_batches"]
        kept = [None] * n_keep
        served = dict(i=0, lat=[], failed=0)

        def serve(seconds, keep):
            """The closed loop, one batch in flight, for `seconds`."""
            start, s0 = served["i"], dict(vars(engine.stats))
            deadline = time.perf_counter() + seconds
            while time.perf_counter() < deadline:
                i = served["i"]
                bid = i % len(batches)
                slot = i if i < n_keep else int(keep_rng.integers(0, i + 1))
                cap.begin(keep and slot < n_keep)
                t0 = time.perf_counter()
                out = engine.infer({"tokens": pool[batches[bid]]})
                served["lat"].append(time.perf_counter() - t0)
                if keep and slot < n_keep:
                    kept[slot] = dict(bid=bid, out=out, **cap.taken)
                served["failed"] += batch - int(np.isfinite(out["confidence"]).sum())
                served["i"] += 1
            s1 = vars(engine.stats)
            return dict({k: s1[k] - s0[k] for k in ("edge_time_s", "edge_calls", "cloud_time_s",
                                                    "cloud_calls", "offloaded")},
                        batches=served["i"] - start)

        with r.window():
            w = serve(r.seconds, keep=True)
        lat = list(served["lat"])
        traced, op_scopes = None, None
        if r.trace:
            with r.traced_window():
                traced = serve(r.seconds, keep=False)
            t0 = time.perf_counter()
            op_scopes = xplane_ops.op_scopes(r.trace_dir)
            _took("trace op scopes read", t0)
            for scope in ("mamba", "attention", "mlp"):
                stacks = [v for v in op_scopes.values() if f"/{scope}/" in v]
                print(f"trace: {len(stacks)} ops under {scope}, e.g. "
                      f"{stacks[0] if stacks else None}", file=sys.stderr)
        r.read_memory_peak(devices)
        print(f"window: {w['batches']} batches, {w['offloaded']} of {w['batches'] * batch} "
              f"prompts offloaded; warm-up batches with another count: {warm_other}",
              file=sys.stderr)
        facts = dict(
            images=w["batches"] * batch, batches=w["batches"], infer_s=float(sum(lat)),
            edge_s=w["edge_time_s"], edge_calls=w["edge_calls"],
            cloud_s=w["cloud_time_s"], cloud_calls=w["cloud_calls"],
            offloaded=w["offloaded"], op_scopes=op_scopes,
            traced=None if traced is None else dict(
                batches=traced["batches"], images=traced["batches"] * batch,
                offloaded=traced["offloaded"]),
        )
    finally:
        restore()
    e2e = {"served_images_per_s": facts["images"] / r.window_s,
           "served_batch_p95_ms": float(np.percentile(lat, 95)) * 1e3}
    kept = [dict(k, tokens=pool[batches[k["bid"]]]) for k in kept if k is not None]
    del engine
    t0 = time.perf_counter()
    checks = check(st, kept, cfg, tr)
    _took("check", t0)
    if "control" in r.hooks:
        r.hooks["control"](control_numbers(st, kept, cfg, tr))
    if "runner_up" in r.hooks:
        r.hooks["runner_up"](runner_up_numbers(st, tr))
    return dict(attempted=served["i"] * batch, failed=served["failed"], end_to_end=e2e,
                facts=facts, checks=checks)


# ------------------------------------------------------------- the check
def _relative_err(a, b):
    """max |a - b| over the root mean square of b (0 for no rows)."""
    if not b.size:
        return 0.0
    return float(np.abs(a - b).max() / np.sqrt(np.mean(b * b)))


def _relative_gap(logits, pred):
    """How far each answer's logit lies below the largest, over the logits'
    root mean square: 0 where the answer is the argmax (or ties it)."""
    if not logits.size:
        return 0.0
    gap = logits.max(-1) - logits[np.arange(len(pred)), pred]
    return float(gap.max() / np.sqrt(np.mean(logits * logits)))


def reference_answers(st, kept, cfg, tr, precision):
    """The reference at `precision`: its calibration on the validation
    prompts, and for each kept batch its exit logits, their confidence at
    the ends of the tied temperature interval, and its hidden state after
    the exit (float32, every row)."""
    ref, params, rows = st["ref"], st["params"], tr["reference_rows"]
    t0 = time.perf_counter()
    cal = ref.calibrate(params, st["val"], st["labels"], precision, tr["offload_share"],
                        rows, cfg)
    t0 = _took(f"reference calibrated ({precision})", t0)
    out = []
    for k in kept:
        z, xs = ref.edge_blocks(params, k["tokens"], precision, cfg, rows)
        z = np.asarray(z, np.float64)
        out.append(dict(edge_logits=z, edge_conf_lo=ref.softmax64(z, cal["t_hi"]).max(-1),
                        edge_conf_hi=ref.softmax64(z, cal["t_lo"]).max(-1),
                        hidden=np.concatenate([np.asarray(x, np.float32) for x in xs])))
    _took(f"reference exit on the kept batches ({precision})", t0)
    return cal, out


def _cloud_reference(st, payload, tr, precision, cfg):
    return np.asarray(st["ref"].cloud(st["params"], payload, precision, cfg,
                                      tr["reference_rows"]), np.float64)


def compare(p_tar, answers, cal, refs, limits) -> dict:
    """Every number compared, with its limit.

    answers: per kept batch, the served `on_device`, `prediction`,
    `confidence`, the program's `exit_logits`, and for its refused rows the
    `payload` they were shipped with (before the codec), the program's
    `cloud_logits` and the reference's `cloud_ref` on the same decoded
    payload, and the codec's capture (`codec`, None where none was made: a
    batch with refused rows and no capture counts in `codec_uncaptured`).
    cal, refs: `reference_answers`.

    The exit's confidences and the threshold are held to the reference's
    at any temperature its NLL cannot tell from the optimum; a gate
    decision counts as flipped only where the reference's confidence lies
    outside the threshold's interval by more than the p_tar and confidence
    limits together. An exiting answer is held to the argmax of the exit
    logits it was gated on (`edge_gap`), and those logits to the
    reference's (`edge_logit_err`): a rounding near-tie can flip the
    reference's argmax, but never the program's own. The payload is held
    to the reference's hidden state of the same prompts (`payload_err`),
    so that rows shipped in another order, or from another layer, fail
    even where the cloud answers its input right.
    """
    cat = lambda key, xs: np.concatenate([np.asarray(x[key]) for x in xs])
    on, pred = cat("on_device", answers), cat("prediction", answers)
    conf = cat("confidence", answers).astype(np.float64)
    z_p = cat("exit_logits", answers).astype(np.float64)
    z_r = cat("edge_logits", refs)
    lo, hi = cat("edge_conf_lo", refs), cat("edge_conf_hi", refs)
    band = limits["p_tar_err"] + limits["edge_conf_err"]
    sure_on, sure_off = lo >= cal["p_hi"] + band, hi < cal["p_lo"] - band
    f_p = np.concatenate([np.asarray(a["cloud_logits"], np.float64) for a in answers
                          if "cloud_logits" in a] or [np.zeros((0, z_r.shape[1]))])
    f_r = np.concatenate([a["cloud_ref"] for a in answers if "cloud_logits" in a]
                         or [np.zeros((0, z_r.shape[1]))])
    shipped = [(np.asarray(a["payload"], np.float32), r["hidden"][~np.asarray(a["on_device"])])
               for a, r in zip(answers, refs) if "payload" in a]
    h_p, h_r = (np.concatenate(x) for x in zip(*shipped)) if shipped else (np.zeros(0),) * 2
    nums = {
        "p_tar_err": float(_outside(p_tar, cal["p_lo"], cal["p_hi"])),
        "gate_flips": int(((on & sure_off) | (~on & sure_on)).sum()),
        "edge_conf_err": float(_outside(conf, lo, hi)[on].max(initial=0.0)),
        "edge_logit_err": _relative_err(z_p, z_r),
        "edge_gap": _relative_gap(z_p[on], pred[on]),
        "payload_err": _relative_err(h_p, h_r),
        "cloud_logit_err": _relative_err(f_p, f_r),
        "cloud_gap": _relative_gap(f_r, pred[~on]),
        "codec_mismatch": 0,
        "codec_uncaptured": 0,
    }
    for a in answers:
        c = a.get("codec")
        if c is None:
            nums["codec_uncaptured"] += int((~a["on_device"]).any())
            continue
        words, scales = c["ref"].encode(np.asarray(c["codec_in"]), c["bits"], c["tile"])
        nums["codec_mismatch"] += int((np.asarray(c["words"]) != words).sum())
        nums["codec_mismatch"] += int((np.asarray(c["scales"]).view(np.uint32)
                                       != scales.view(np.uint32)).sum())
        dec = np.asarray(c["decoded"])
        back = c["ref"].decode(words, scales, dec.shape, c["bits"], c["tile"]).astype(dec.dtype)
        bits = np.uint16 if dec.itemsize == 2 else np.uint32
        nums["codec_mismatch"] += int((dec.view(bits) != back.view(bits)).sum())
    return {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS}


def check(st, kept, cfg, tr) -> dict:
    """The reference over the kept batches (its answers kept in `st` for
    `control_numbers`)."""
    served = cfg["served"]
    cal, refs = st["reference"] = reference_answers(st, kept, cfg, tr, "f32")
    print(f"calibration: program T {st['temperature']!r} p_tar {st['p_tar']!r}; reference T "
          f"{cal['t']!r} in [{cal['t_lo']!r}, {cal['t_hi']!r}] p_tar {cal['p_tar']!r}",
          file=sys.stderr)
    answers = []
    for k in kept:
        a = dict(k["out"], exit_logits=k["exit_logits"])
        if "codec_in" in k:
            a["payload"] = k["codec_in"]
        if "cloud_logits" in k and "decoded" in k:
            a.update(cloud_logits=k["cloud_logits"],
                     cloud_ref=_cloud_reference(st, k["decoded"], tr, "f32", cfg))
        if "words" in k and "decoded" in k:
            a["codec"] = dict(k, ref=st["ref"], bits=served["codec_bits"],
                              tile=served["codec_tile"])
        answers.append(a)
    st["answers"] = answers
    return compare(st["p_tar"], answers, cal, refs, tr["limits"])


def control_numbers(st, kept, cfg, tr) -> dict:
    """The numbers of the control: the reference with both operands of
    every product rounded to float8 e4m3 in the program's place, calibrated
    by itself on the same labels, its payload through the reference codec;
    compared with the float32 reference like the program. A sound limit
    lies above every program reading and the control fails at least one."""
    served, ref = cfg["served"], st["ref"]
    cal, refs = st["reference"]
    ctl_cal, ctl = reference_answers(st, kept, cfg, tr, "f8")
    answers = []
    for k, c in zip(kept, ctl):
        conf = ref.softmax64(c["edge_logits"], ctl_cal["t"]).max(-1)
        on = conf >= ctl_cal["p_tar"]
        pred = c["edge_logits"].argmax(-1)
        a = dict(on_device=on, prediction=pred, confidence=conf, exit_logits=c["edge_logits"])
        if (~on).any():
            h = c["hidden"][~on]
            words, scales = ref.encode(h, served["codec_bits"], served["codec_tile"])
            wire = ref.decode(words, scales, h.shape, served["codec_bits"],
                              served["codec_tile"])
            f = _cloud_reference(st, wire, tr, "f8", cfg)
            pred[~on] = f.argmax(-1)
            a.update(payload=h, cloud_logits=f,
                     cloud_ref=_cloud_reference(st, wire, tr, "f32", cfg))
        answers.append(a)
    checks = compare(ctl_cal["p_tar"], answers, cal, refs, tr["limits"])
    # the control's codec is the reference's own wire format: nothing to compare
    return {k: v["value"] for k, v in checks.items() if not k.startswith("codec_")}


def runner_up_numbers(st, tr) -> dict:
    """The program's numbers with each exiting prompt answered by the
    runner-up of its exit logits: the fault `edge_gap` is there to catch."""
    cal, refs = st["reference"]
    answers = []
    for a in st["answers"]:
        z = np.asarray(a["exit_logits"], np.float32)
        pred = np.where(a["on_device"], np.argsort(z, axis=-1)[:, -2], a["prediction"])
        answers.append({k: v for k, v in a.items() if k != "codec"} | {"prediction": pred})
    checks = compare(st["p_tar"], answers, cal, refs, tr["limits"])
    return {k: v["value"] for k, v in checks.items() if not k.startswith("codec_")}
