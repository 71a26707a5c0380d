import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, with NO device allocation (ShapeDtypeStruct inputs).

For each pair it runs jax.jit(step).lower(**specs).compile() and records:
  * memory_analysis()  -- bytes per device (proves the sharding fits),
  * cost_analysis()    -- HLO FLOPs / bytes for the roofline,
  * the collective schedule -- bytes moved per collective kind, parsed from
    the optimized HLO (operand sizes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding
from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.training import optim
from repro.training.loop import make_train_step
from repro.launch.serve import make_prefill_step, make_serve_step


ASSIGNED = [a for a in list_archs() if a != "b_alexnet"]

COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def shape_adapted_config(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """long_500k on attention-quadratic archs -> sliding-window attention.

    SSM/hybrid run natively (O(1)/bounded state). Dense/MoE/VLM/audio get a
    4096-token window so the 524k decode is sub-quadratic, per the shape's
    requirement (noted in DESIGN.md: implemented rather than skipped).
    """
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm", "audio"):
        if cfg.sliding_window == 0:
            cfg = cfg.replace(sliding_window=4096)
    return cfg


def _sds(tree, shardings):
    """Attach shardings to ShapeDtypeStructs."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree,
        shardings,
    )


VARIANTS = {
    "baseline": {},
    "moe_shard_capacity": {"moe_shard_capacity": True},
    "decode_unroll": {"decode_unroll": True},
    "mamba_split_proj": {"mamba_split_proj": True},
    "all_opt": {
        "moe_shard_capacity": True,
        "decode_unroll": True,
        "mamba_split_proj": True,
    },
}


def build_lowering(
    arch: str, shape_name: str, mesh, zero1: bool = False, variant: str = "baseline"
):
    cfg = shape_adapted_config(get_config(arch), INPUT_SHAPES[shape_name])
    cfg = cfg.replace(**VARIANTS[variant])
    shape = INPUT_SHAPES[shape_name]
    sharding.set_mesh(mesh)
    ns = lambda spec: NamedSharding(mesh, spec)

    param_shapes = registry.param_specs_shapes(cfg)
    pspecs = sharding.param_specs(param_shapes)
    psh = jax.tree.map(ns, pspecs)
    batch_shapes = registry.input_specs(cfg, shape)
    bsh = jax.tree.map(ns, sharding.batch_specs_tree(batch_shapes))

    if shape.kind == "train":
        opt_cfg = optim.AdamWConfig()
        step = make_train_step(cfg, opt_cfg)
        opt_shapes = jax.eval_shape(optim.init, param_shapes)
        dp_size = 1
        for ax in sharding.dp_axes():
            dp_size *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
        ospecs = optim.state_specs(
            pspecs,
            zero1=zero1,
            dp_axes=sharding.dp_axes(),
            param_shapes=param_shapes,
            dp_size=dp_size,
        )
        osh = jax.tree.map(ns, ospecs)
        jitted = jax.jit(step, out_shardings=(psh, osh, None))
        args = (_sds(param_shapes, psh), _sds(opt_shapes, osh), _sds(batch_shapes, bsh))
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg)
        jitted = jax.jit(step)
        args = (_sds(param_shapes, psh), _sds(batch_shapes, bsh))
    else:  # decode
        step = make_serve_step(cfg)
        cache_shapes = registry.cache_specs(cfg, shape)
        cspecs = sharding.cache_specs_tree(
            cache_shapes, batch_sharded=shape.global_batch > 1
        )
        csh = jax.tree.map(ns, cspecs)
        # donate the cache: serving reuses the buffer every step; without
        # aliasing, an unrolled decode materializes a copy per layer update
        jitted = jax.jit(step, donate_argnums=(2,))
        tok_sh = jax.tree.map(ns, sharding.batch_specs_tree(batch_shapes))
        args = (
            _sds(param_shapes, psh),
            _sds(batch_shapes["token"], tok_sh["token"]),
            _sds(cache_shapes, csh),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=ns(P())),
        )
    return cfg, jitted, args


def collective_bytes(hlo_text: str):
    """Sum operand bytes per collective kind from optimized HLO."""
    dsize = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
             "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8, "c64": 8, "s16": 2, "u16": 2}

    def shape_bytes(s):
        total = 0
        for m in re.finditer(r"(\w+)\[([\d,]*)\]", s):
            dt, dims = m.group(1), m.group(2)
            if dt not in dsize:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * dsize[dt]
        return total

    # map instr name -> output shape bytes
    defs = {}
    for m in re.finditer(r"(%[\w.\-]+) = ((?:\([^)]*\)|[\w\[\],{}\s/]*?)) (\w[\w\-]*)\(", hlo_text):
        defs[m.group(1)] = shape_bytes(m.group(2))

    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for m in re.finditer(
        r"= ((?:\([^)]*\)|[\w\[\],{}\s/]*?)) ((?:all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)[\w\-]*)\(([^)]*)\)",
        hlo_text,
    ):
        kind = next(k for k in COLLECTIVES if m.group(2).startswith(k))
        operands = re.findall(r"%[\w.\-]+", m.group(3))
        b = sum(defs.get(o, 0) for o in operands)
        if b == 0:  # fall back to output size
            b = shape_bytes(m.group(1))
        out[kind] += b
        counts[kind] += 1
    return out, counts


def run_one(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    outdir: str,
    zero1=False,
    variant: str = "baseline",
):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with jax.sharding.set_mesh(mesh):
        cfg, jitted, args = build_lowering(
            arch, shape_name, mesh, zero1=zero1, variant=variant
        )
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll, coll_counts = collective_bytes(hlo)
    # Recursive while-trip-count-aware cost model (XLA cost_analysis counts
    # scan bodies once; see repro.launch.hlo_cost docstring).
    from repro.launch.hlo_cost import analyze_text

    model_cost = analyze_text(hlo)
    n_chips = mesh.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": n_chips,
        "ok": True,
        "compile_s": round(time.time() - t0, 1),
        "flops": model_cost["flops"],
        "bytes_accessed": model_cost["bytes"],
        "collective_bytes": model_cost["collective_bytes"],
        "collective_counts": model_cost["collective_counts"],
        "xla_raw_flops": cost.get("flops", 0.0),
        "xla_raw_bytes": cost.get("bytes accessed", 0.0),
        "raw_collective_bytes": coll,
        "raw_collective_counts": coll_counts,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "model_params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "sliding_window": cfg.sliding_window,
        "zero1": zero1,
        "variant": variant,
    }
    os.makedirs(outdir, exist_ok=True)
    sfx = "" if variant == "baseline" and not zero1 else (
        f"__{variant}" + ("_zero1" if zero1 else "")
    )
    stem = f"{arch}__{shape_name}__{mesh_name}{sfx}"
    fn = os.path.join(outdir, stem + ".json")
    with open(fn, "w") as f:
        json.dump(result, f, indent=1)
    # archive the optimized HLO so cost-model refinements re-derive terms
    # without recompiling (benchmarks/recost.py)
    try:
        import zstandard

        hlodir = os.path.join(os.path.dirname(outdir) or ".", "hlo")
        os.makedirs(hlodir, exist_ok=True)
        with open(os.path.join(hlodir, stem + ".hlo.zst"), "wb") as f:
            f.write(zstandard.ZstdCompressor(level=9).compress(hlo.encode()))
    except Exception:
        pass
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--zero1", action="store_true", help="ZeRO-1 optimizer sharding")
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    ap.add_argument("--outdir", default="experiments/dryrun")
    args = ap.parse_args()

    pairs = []
    if args.all:
        pairs = [(a, s) for a in ASSIGNED for s in INPUT_SHAPES]
    else:
        pairs = [(args.arch, args.shape)]

    failures = []
    for arch, shape in pairs:
        try:
            r = run_one(
                arch, shape, args.multi_pod, args.outdir,
                zero1=args.zero1, variant=args.variant,
            )
            print(
                f"OK   {arch:24s} {shape:12s} {r['mesh']:8s} "
                f"flops={r['flops']:.3e} bytes={r['bytes_accessed']:.3e} "
                f"coll={sum(r['collective_bytes'].values()):.3e} "
                f"({r['compile_s']}s)"
            )
        except Exception as e:
            failures.append((arch, shape, str(e)))
            print(f"FAIL {arch:24s} {shape:12s}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures")


if __name__ == "__main__":
    main()
