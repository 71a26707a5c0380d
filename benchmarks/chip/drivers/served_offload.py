"""Served offload cells: the program's `OffloadEngine.infer` over request batches.

Set-up (all counted in `setup_s`):

1. the matmul precision the configuration states, for the whole process;
2. weights from the seed, one jitted call on the device (`configs/<c>.py`);
3. the validation split (the traffic's image spec, `imagegen`), the
   program's branch logits on it at the window's batch shape, `make_plan`'s
   temperature fit, and `p_tar` at the quantile that offloads the traffic's
   `offload_share` of it, with the configuration's codec level;
4. an `OffloadEngine` over the edge and cloud partitions of the program's
   convnet, jitted with the weights as arguments (so that every program is
   found in the persistent cache whatever the seed), gating with the Pallas
   exit-gate kernel;
5. the window's traffic, on the host: a pool of `pool_images` images from
   the window stream, and `window_batches` batches drawn from it by
   `draw_batches`, each a fresh draw whose refused count falls where its
   images do (about Binomial(batch, offload_share));
6. warm-up: each refused count is one shape of the gather, codec and
   cloud programs, so one batch of each count in `warm_counts` is made
   from the warm stream's images and served: every count within
   `warm_sigmas` standard deviations of Binomial(batch, offload_share),
   the same for every seed, so that the cache holds them all after a
   cell's first run, and any count of the window's draws (the deployed
   gate's decision on the pool, computed once) outside them. A count that
   still comes out otherwise compiles in the window and is counted there.

Window: a closed loop with one batch in flight. Each `infer` call gets the
next drawn batch (a host array gathered from the pool) and returns host
arrays. A seeded reservoir keeps `check_batches` of the measured window's
batches, with the codec's input, wire image and decoded payload for each.
With `--trace 1` the loop goes on for a second, traced window
(`Run.traced_window`).

Check, once the window has closed and the peak memory is read: the plain
reference (`configs/<c>.py`, at the configuration's precision, calibrated
by itself) serves the kept batches, and `compare` sets each number
against its limit from the traffic file.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import imagegen

NUMBERS = ("p_tar_err", "gate_flips", "edge_conf_err", "edge_gap",
           "cloud_conf_err", "cloud_gap", "codec_mismatch", "codec_uncaptured")


# ------------------------------------------------------------- the system
@functools.lru_cache(maxsize=None)
def _partitions(branch: int):
    """The program's edge and cloud partitions, weights as arguments."""
    import jax
    from repro.models import convnet

    edge = jax.jit(lambda p, x: convnet.edge_forward(p, x, branch=branch))
    cloud = jax.jit(lambda p, h: convnet.cloud_forward(p, h, from_branch=branch))
    return edge, cloud


class Capture:
    """For a batch the reservoir keeps, the codec's input, wire image and
    decoded payload."""

    def __init__(self):
        self.keep = False
        self.taken: dict = {}

    def begin(self, keep: bool):
        self.keep, self.taken = keep, {}

    def install(self, compress):
        enc, dec = compress.encode, compress.decode

        def encode(x, level):
            out = enc(x, level)
            if self.keep:
                self.taken.update(codec_in=x, words=out.words, scales=out.scales,
                                  shape=out.shape)
            return out

        def decode(e):
            out = dec(e)
            if self.keep:
                self.taken["decoded"] = out
            return out

        compress.encode, compress.decode = encode, decode
        return lambda: (setattr(compress, "encode", enc), setattr(compress, "decode", dec))


def draw_batches(seed: int, pool: int, batch: int, n: int) -> np.ndarray:
    """(n, batch) indices into a pool of images: consecutive slices of
    fresh seeded permutations of the pool, so each batch is a new draw, its
    refused count falls where the images do, and every image is served
    once per pass over the pool."""
    rng = np.random.default_rng(seed)
    per = pool // batch
    idx = np.concatenate([rng.permutation(pool)[:per * batch]
                          for _ in range(-(-n // per))])
    return idx[:n * batch].reshape(n, batch)


def _blocks(x, rows):
    """Host array -> list of `rows`-row blocks, the last padded with zeros."""
    out = []
    for i in range(0, len(x), rows):
        blk = x[i:i + rows]
        if len(blk) < rows:
            blk = np.concatenate([blk, np.zeros((rows - len(blk),) + blk.shape[1:], blk.dtype)])
        out.append(blk)
    return out


def _edge_confidence(engine, x, rows):
    """The program's calibrated branch confidence for each image of x,
    through its own edge partition and gate at the window's batch shape."""
    conf = []
    for blk in _blocks(x, rows):
        z = engine.edge_fn({"images": blk})["exit_logits"]
        conf.append(np.asarray(engine.plan.gate(z, branch=engine.branch,
                                                use_kernel=engine.use_kernel).confidence))
    return np.concatenate(conf)[:len(x)]


def build(r, devices):
    """Set-up steps 1 to 4: (engine, state) for run `r`."""
    import jax
    from repro.core import make_plan
    from repro.offload.engine import OffloadEngine

    cfg, tr = r.cell.config, r.cell.traffic
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    ref = r.cell.reference()
    seeds = imagegen.streams(r.seed)
    served = cfg["served"]
    branch, batch = served["branch"], tr["batch"]
    params = ref.make_params(imagegen.key(seeds["weights"]), cfg)
    tmpl = imagegen.templates(imagegen.key(seeds["templates"]), cfg["classes"],
                              tuple(cfg["input"]))
    val_x, val_y = imagegen.image_split(seeds["val"], tmpl,
                                        cfg["calibration"]["val_images"], tr["images"])
    edge_j, cloud_j = _partitions(branch)

    def edge_fn(b):
        z, h = edge_j(params, b["images"])
        return {"exit_logits": z, "payload": h}

    def cloud_fn(h):
        return {"logits": cloud_j(params, h)}

    z_val = np.concatenate([np.asarray(edge_fn({"images": blk})["exit_logits"])
                            for blk in _blocks(val_x, batch)])[:len(val_x)]
    plan = make_plan([z_val], val_y, p_tar=0.5)
    engine = OffloadEngine(edge_fn, cloud_fn, plan, branch=0, use_kernel=True)
    p_tar = float(np.quantile(_edge_confidence(engine, val_x, batch), tr["offload_share"]))
    engine.plan = plan.with_p_tar(p_tar).with_compression(served["codec_level"])
    state = dict(ref=ref, params=params, tmpl=tmpl, val_x=val_x, val_y=val_y,
                 seeds=seeds, p_tar=p_tar, temperature=float(plan.temperatures[0]))
    return engine, state


def warm_counts(tr, counts) -> np.ndarray:
    """The refused counts set-up warms: the central ones of
    Binomial(batch, offload_share), and those of the window's draws."""
    n, p = tr["batch"], tr["offload_share"]
    mu, reach = n * p, tr["warm_sigmas"] * (n * p * (1 - p)) ** 0.5
    central = np.arange(max(0, int(np.ceil(mu - reach))), min(n, int(mu + reach)) + 1)
    return np.union1d(central, counts)


def warm(engine, state, tr, counts) -> int:
    """Set-up step 6: serve one batch for each refused count in `counts`,
    made from the warm stream's images; return how many came out with
    another count."""
    batch = tr["batch"]
    x, _ = imagegen.image_split(state["seeds"]["warm"], state["tmpl"], tr["warm_images"],
                                tr["images"])
    refused = _edge_confidence(engine, x, batch) < state["p_tar"]
    off, on = np.flatnonzero(refused), np.flatnonzero(~refused)
    other = 0
    for k in np.unique(counts):
        pick = np.concatenate([np.resize(off, k), np.resize(on, batch - k)])
        other += int((~engine.infer({"images": x[pick]})["on_device"]).sum() != k)
    return other


def run(r, devices) -> dict:
    import sys

    from repro.kernels import compress

    cfg, tr = r.cell.config, r.cell.traffic
    engine, st = build(r, devices)
    cap = Capture()
    restore = cap.install(compress)
    try:
        if "engine" in r.hooks:
            r.hooks["engine"](engine)
        batch = tr["batch"]
        pool, _ = imagegen.image_split(st["seeds"]["window"], st["tmpl"], tr["pool_images"],
                                       tr["images"])
        batches = draw_batches(st["seeds"]["window"], len(pool), batch, tr["window_batches"])
        refused = _edge_confidence(engine, pool, batch) < st["p_tar"]
        counts = refused[batches].sum(axis=1)
        warmed = warm_counts(tr, counts)
        warm_other = warm(engine, st, tr, warmed)
        keep_rng = np.random.default_rng(st["seeds"]["check"])
        n_keep = tr["check_batches"]
        kept = [None] * n_keep
        served = dict(i=0, lat=[], failed=0, count_other=0)

        def serve(seconds, keep):
            """The closed loop: the next drawn batch, one in flight, until
            `seconds` have passed; returns the number of batches served."""
            start, s0 = served["i"], dict(vars(engine.stats))
            deadline = time.perf_counter() + seconds
            while time.perf_counter() < deadline:
                i = served["i"]
                bid = i % len(batches)
                x = pool[batches[bid]]
                slot = i if i < n_keep else int(keep_rng.integers(0, i + 1))
                cap.begin(keep and slot < n_keep)
                t0 = time.perf_counter()
                out = engine.infer({"images": x})
                served["lat"].append(time.perf_counter() - t0)
                if keep and slot < n_keep:
                    kept[slot] = dict(bid=bid, out=out, **cap.taken)
                served["failed"] += batch - int(np.isfinite(out["confidence"]).sum())
                served["count_other"] += int((~out["on_device"]).sum() != counts[bid])
                served["i"] += 1
            s1 = vars(engine.stats)
            return dict({k: s1[k] - s0[k] for k in ("edge_time_s", "edge_calls", "cloud_time_s",
                                                    "cloud_calls", "offloaded")},
                        batches=served["i"] - start)

        with r.window():
            w = serve(r.seconds, keep=True)
        lat = list(served["lat"])
        traced = None
        if r.trace:
            with r.traced_window():
                traced = serve(r.seconds, keep=False)
            traced_s = (r.trace_window[1] - r.trace_window[0]) * 1e-9
            print(f"tracing cost: {w['batches'] / r.window_s:.1f} batches/s measured, "
                  f"{traced['batches'] / traced_s:.1f} traced", file=sys.stderr)
        r.read_memory_peak(devices)
        print(f"shapes: {len(warmed)} refused counts warmed ({warmed.min()} to "
              f"{warmed.max()}); the window's {len(np.unique(counts))} run {counts.min()} to "
              f"{counts.max()}; with another count: {warm_other} warm-up and "
              f"{served['count_other']} window batches", file=sys.stderr)
        facts = dict(
            images=w["batches"] * batch, batches=w["batches"], infer_s=float(sum(lat)),
            edge_s=w["edge_time_s"], edge_calls=w["edge_calls"],
            cloud_s=w["cloud_time_s"], cloud_calls=w["cloud_calls"],
            offloaded=w["offloaded"],
            traced=None if traced is None else dict(
                batches=traced["batches"], images=traced["batches"] * batch,
                offloaded=traced["offloaded"]),
        )
    finally:
        restore()
    e2e = {"served_images_per_s": facts["images"] / r.window_s,
           "served_batch_p95_ms": float(np.percentile(lat, 95)) * 1e3}
    del engine
    kept = [k for k in kept if k is not None]
    checks = check(st, kept, [pool[batches[k["bid"]]] for k in kept], cfg, tr)
    return dict(attempted=served["i"] * batch, failed=served["failed"], end_to_end=e2e,
                facts=facts, checks=checks)


# ------------------------------------------------------------- the check
def reference_answers(st, cfg, tr, images_list, precision):
    """The plain reference's calibration and answers at `precision`."""
    ref = st["ref"]
    cal = ref.calibrate(st["params"], st["val_x"], st["val_y"], cfg["served"]["branch"],
                        precision, tr["offload_share"])
    return cal, [ref.serve(st["params"], x, cal, precision) for x in images_list]


def _outside(x, lo, hi):
    """How far x lies outside [lo, hi] (0 inside)."""
    return np.maximum(np.maximum(lo - x, x - hi), 0.0)


def compare(p_tar, answers, cal, refs, codec, limits) -> dict:
    """Every number compared, with its limit.

    answers: per batch, the served `on_device`, `prediction`, `confidence`;
    p_tar: the threshold they were served under; cal, refs: the reference's
    `calibrate` and its `serve` for the same batches; codec: per batch, the
    program's codec input, wire image and decoded payload, or None where
    none was captured: a batch with offloaded rows and no capture counts in
    `codec_uncaptured`, so the codec check cannot pass by going unseen.

    Edge confidences and the threshold are held to the reference's at any
    temperature its NLL cannot tell from the optimum (`calibrate`); a gate
    decision counts as flipped only where the reference's decision is the
    same at every such temperature, by a margin of the confidence limit.
    """
    cat = lambda key, xs: np.concatenate([x[key] for x in xs])
    on, pred = cat("on_device", answers), cat("prediction", answers)
    conf = cat("confidence", answers).astype(np.float64)
    lo, hi = cat("edge_conf_lo", refs), cat("edge_conf_hi", refs)
    r_cloud = cat("cloud_confidence", refs)
    z = cat("edge_logits", refs).astype(np.float64)
    f = cat("final_logits", refs).astype(np.float64)
    rows = np.arange(len(pred))
    band = limits["edge_conf_err"]
    sure_on = lo >= cal["p_hi"] + band
    sure_off = hi < cal["p_lo"] - band
    nums = {
        "p_tar_err": float(_outside(p_tar, cal["p_lo"], cal["p_hi"])),
        "gate_flips": int(((on & sure_off) | (~on & sure_on)).sum()),
        "edge_conf_err": float(_outside(conf, lo, hi)[on].max(initial=0.0)),
        "edge_gap": float((z.max(-1) - z[rows, pred])[on].max(initial=0.0)),
        "cloud_conf_err": float(np.abs(conf - r_cloud)[~on].max(initial=0.0)),
        "cloud_gap": float((f.max(-1) - f[rows, pred])[~on].max(initial=0.0)),
        "codec_mismatch": 0,
        "codec_uncaptured": 0,
    }
    for a, c in zip(answers, codec):
        if c is None:  # offloaded rows that did not pass the codec as captured
            nums["codec_uncaptured"] += int((~a["on_device"]).any())
            continue
        words, scales = c["ref"].encode(np.asarray(c["codec_in"]), c["bits"], c["tile"])
        nums["codec_mismatch"] += int((np.asarray(c["words"]) != words).sum())
        nums["codec_mismatch"] += int((np.asarray(c["scales"]).view(np.uint32)
                                       != scales.view(np.uint32)).sum())
        back = c["ref"].decode(words, scales, c["shape"], c["bits"], c["tile"])
        nums["codec_mismatch"] += int((np.asarray(c["decoded"]).view(np.uint32)
                                       != back.view(np.uint32)).sum())
    return {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS}


def check(st, kept, images, cfg, tr) -> dict:
    """The reference over the kept batches (`images`, one array each)."""
    import sys

    served = cfg["served"]
    cal, refs = reference_answers(st, cfg, tr, images, cfg["matmul_precision"])
    print(f"calibration: program T {st['temperature']!r} p_tar {st['p_tar']!r}; "
          f"reference T {cal['t']!r} in [{cal['t_lo']!r}, {cal['t_hi']!r}] "
          f"p_tar {cal['p_tar']!r}", file=sys.stderr)
    codec = [dict(k, ref=st["ref"], bits=served["codec_bits"], tile=served["codec_tile"])
             if "words" in k and "decoded" in k else None for k in kept]
    return compare(st["p_tar"], [k["out"] for k in kept], cal, refs, codec, tr["limits"])
