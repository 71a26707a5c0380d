#!/usr/bin/env python3
"""Bring-up smoke run: the system's two accelerator workloads on a TPU.

    python chip_smoke.py             # one chip: served path + compiled fleet
    python chip_smoke.py --chips 4   # four chips: sharded compiled fleet only

One process drives every phase (a child process could not reach a chip
its parent holds). Phases:

* served -- B-AlexNet at the repo's widths (`configs/b_alexnet.py`) with
  seeded random weights on 32x32x3 `cifar_like` images. A plan is fit
  with `make_plan`/`select_partition` on validation logits; engines built
  by `convnet_engine(..., use_kernel=True)` at branches 1 and 2 and codec
  levels 0, 1, 2 serve a 256-image batch through `OffloadEngine.infer`,
  and `ServingRuntime` over `EngineCore` answers a few hundred requests.
  The Pallas gate is checked against the jnp `gate_statistics`, the codec
  words and scales against the numpy oracle (`kernels/ref.py`) bit for
  bit, every served answer against the unpartitioned model, and each
  kernel for being compiled (`tpu_custom_call` in the lowered program).
* fleet -- `reference_fleet(64 cells, 1,600 requests per cell)` on the
  compiled fleet backend, compared per request with the host simulator.
* fleet4 (`--chips 4` only) -- the same scenario with the cell axis
  sharded over an explicit four-device mesh, compared per request with
  the host simulator; its outputs must span all four devices.

Every phase prints one JSON line. The last line of standard output is
``{"ok": true, "device": {...}}`` when every phase passed. Without a TPU
the script exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: per-request latency parity with the host simulator: the compiled
#: program evaluates the same max-plus algebra in a tree-shaped order
#: (the CPU tests pin the same bound)
LAT_RTOL, LAT_ATOL = 1e-9, 1e-12
#: kernel gate vs jnp gate statistics
GATE_CONF_ATOL = 1e-5
#: served answers vs the unpartitioned model (different batch shapes)
SERVED_CONF_ATOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileCounter:
    """Counts XLA compilations (and persistent-cache hits) by listening to
    JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.cache_hits, self.compile_s

    def since(self, snap) -> dict:
        c, h, s = snap
        return dict(compilations=self.compiles - c,
                    cache_hits=self.cache_hits - h,
                    compile_s=self.compile_s - s)


def _timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _is_compiled_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def _softmax_max(logits):
    import numpy as np

    z = np.asarray(logits, np.float64)
    z = z - z.max(-1, keepdims=True)
    p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    s = np.sort(p, axis=-1)
    return p.max(-1), np.argmax(z, -1), s[:, -1] - s[:, -2]


# ------------------------------------------------------------ served path
def served_phase(seed: int, counter: CompileCounter, n_val: int = 1024,
                 n_batch: int = 256, n_requests: int = 300) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_plan, select_partition
    from repro.core.exits import gate_statistics
    from repro.data.synthetic import cifar_like
    from repro.kernels import compress
    from repro.kernels.ops import exit_gate
    from repro.kernels.ref import (CODEC_BITS, decode_codec_ref,
                                   encode_codec_ref)
    from repro.models import convnet
    from repro.offload import latency as L
    from repro.offload.engine import convnet_engine
    from repro.serving import (EngineCore, LogitsCore, RuntimeConfig,
                               ServingRuntime, poisson_workload)

    snap = counter.snapshot()
    branches = (1, 2)
    data = cifar_like(n_train=0, n_val=n_val, n_test=n_batch, seed=seed)
    params = convnet.init_params(jax.random.PRNGKey(seed))
    forward = jax.jit(convnet.forward)  # the unpartitioned reference model
    edge_ref = jax.jit(convnet.edge_forward, static_argnames="branch")
    cloud_ref = jax.jit(convnet.cloud_forward, static_argnames="from_branch")

    val = forward(params, jnp.asarray(data.val_x))
    val_z = [np.asarray(z) for z in val["exit_logits"]]
    prof = L.paper_2020()
    plan = make_plan(val_z, data.val_y, p_tar=0.8)
    plan, _ = select_partition(
        plan, val_z,
        edge_times_s=[L.edge_time(prof, b) for b in branches],
        cloud_times_s=[L.cloud_time(prof, b) for b in branches],
        payload_bytes=[L.payload_bytes_for(b) for b in branches],
        exit_layer_indices=[0, 1], uplink_bps=prof.uplink_bps,
    )

    images = jnp.asarray(data.test_x)
    ref = forward(params, images)
    final = np.asarray(ref["logits"])
    plans, gate_ref = {}, {}
    for b in branches:
        z = ref["exit_logits"][b - 1]
        t = jnp.float32(plan.temperatures[b - 1])
        ck, pk, _ = gate_statistics(z, t, use_kernel=True)
        cr, pr, _ = gate_statistics(z, t)
        ck, pk, cr, pr = map(np.asarray, (ck, pk, cr, pr))
        check(_is_compiled_kernel(exit_gate, z, t),
              f"branch {b}: exit gate not compiled to a TPU kernel")
        check(np.array_equal(pk, pr),
              f"branch {b}: kernel gate predictions differ from jnp")
        err = float(np.abs(ck - cr).max())
        check(err <= GATE_CONF_ATOL,
              f"branch {b}: kernel gate confidence off by {err}")
        # p_tar between the two middle confidences: both paths get traffic
        s = np.sort(cr)
        plans[b] = plan.with_p_tar(0.5 * (s[len(s) // 2 - 1] + s[len(s) // 2]))
        gate_ref[b] = (cr, pr)
        emit("served.gate", branch=b, temperature=float(t),
             conf_max_abs_err=err, predictions_equal=True, compiled=True)

    # --- codec: kernel wire image vs the numpy oracle, bit for bit
    hidden = {b: edge_ref(params, images, branch=b)[1] for b in branches}
    for b in branches:
        h = np.asarray(hidden[b])
        for level in sorted(CODEC_BITS):
            enc = compress.encode(hidden[b], level)
            w_ref, s_ref = encode_codec_ref(h, level)
            dec = np.asarray(compress.decode(enc))
            check(np.array_equal(np.asarray(enc.words), w_ref),
                  f"branch {b} level {level}: codec words differ")
            check(np.array_equal(np.asarray(enc.scales).view(np.uint32),
                                 s_ref.view(np.uint32)),
                  f"branch {b} level {level}: codec scales differ")
            check(np.array_equal(
                dec, decode_codec_ref(w_ref, s_ref, h.shape, level)),
                f"branch {b} level {level}: codec decode differs")
            check(_is_compiled_kernel(
                jax.jit(lambda x, lv=level: compress.encode(x, lv).words),
                hidden[b]), f"level {level}: encode not compiled to a TPU kernel")
            check(_is_compiled_kernel(
                jax.jit(lambda w, s, e=enc: compress.decode(
                    dataclasses.replace(e, words=w, scales=s))),
                enc.words, enc.scales),
                f"level {level}: decode not compiled to a TPU kernel")
            emit("served.codec", branch=b, level=level, payload=list(h.shape),
                 wire_bytes=enc.nbytes, raw_bytes=int(h.nbytes),
                 words_bitexact=True, scales_bitexact=True,
                 decode_bitexact=True, compiled=True)

    # --- OffloadEngine.infer on the batch, every (branch, level)
    refused_counts = set()
    for b in branches:
        cr, pr = gate_ref[b]
        p_tar = plans[b].p_tar
        for level in (0, *sorted(CODEC_BITS)):
            eng = convnet_engine(params, plans[b].with_compression(level),
                                 branch=b, use_kernel=True)
            batch = {"images": images}
            out, cold_s = _timed(eng.infer, batch)
            out2, warm_s = _timed(eng.infer, batch)
            mask = out["on_device"]
            check(np.array_equal(mask, out2["on_device"])
                  and np.array_equal(out["prediction"], out2["prediction"]),
                  f"branch {b} level {level}: infer is not deterministic")
            check(np.isfinite(out["confidence"]).all(),
                  f"branch {b} level {level}: non-finite confidence")
            near = np.abs(cr - p_tar) <= GATE_CONF_ATOL
            check(np.array_equal(mask[~near], (cr >= p_tar)[~near]),
                  f"branch {b} level {level}: gate mask differs from jnp")
            check(np.array_equal(out["prediction"][mask], pr[mask]),
                  f"branch {b} level {level}: on-device answers differ")
            idx = np.flatnonzero(~mask)
            refused_counts.add(len(idx))
            conf_err, ties = 0.0, 0
            if len(idx):
                h = np.asarray(hidden[b])[idx]
                if level:
                    w, s = encode_codec_ref(h, level)
                    h = decode_codec_ref(w, s, h.shape, level)
                logits = final[idx] if level == 0 else np.asarray(
                    cloud_ref(params, jnp.asarray(h), from_branch=b))
                conf_r, pred_r, margin = _softmax_max(logits)
                conf_err = float(np.abs(out["confidence"][idx] - conf_r).max())
                sure = margin > SERVED_CONF_ATOL
                ties = int((~sure).sum())
                check(conf_err <= SERVED_CONF_ATOL,
                      f"branch {b} level {level}: cloud confidence off by "
                      f"{conf_err}")
                check(np.array_equal(out["prediction"][idx][sure],
                                     pred_r[sure]),
                      f"branch {b} level {level}: cloud answers differ")
            emit("served.infer", branch=b, level=level, batch=n_batch,
                 p_tar=p_tar, offload_rate=float(eng.stats.offload_rate),
                 offloaded=len(idx), payload_bytes=eng.stats.payload_bytes,
                 cloud_conf_max_abs_err=conf_err, near_ties=ties,
                 cold_s=cold_s, warm_s=warm_s)

    # --- ServingRuntime over EngineCore, per request
    deployed = plan.exit_index + 1
    rt_plan = plans[deployed]
    reqs = poisson_workload(50.0, n_requests, n_batch, deadline_s=0.1,
                            seed=seed)
    labels = np.asarray(data.test_y)
    runs = {}
    for level in (0, 2):
        engines = {b: convnet_engine(params, plans[b].with_compression(level),
                                     branch=b, use_kernel=True)
                   for b in branches}
        core = EngineCore(engines, {"images": images}, labels=labels)
        t0 = time.perf_counter()
        tel = ServingRuntime(core, prof, rt_plan.with_compression(level),
                             reqs, config=RuntimeConfig(max_batch=1)).run()
        wall = time.perf_counter() - t0
        recs = {r.req_id: r for r in tel.records}
        check(len(recs) == n_requests,
              f"runtime level {level}: {len(recs)} of {n_requests} answered")
        check(all(np.isfinite(r.latency_s) for r in recs.values()),
              f"runtime level {level}: non-finite latency")
        runs[level] = recs
        s = tel.summary()
        emit("served.runtime", level=level, branch=deployed,
             requests=len(recs), offload_rate=s["offload_rate"],
             accuracy=s.get("accuracy"), p99_sim_ms=s["p99_ms"],
             edge_calls=engines[deployed].stats.edge_calls,
             cloud_calls=engines[deployed].stats.cloud_calls, wall_s=wall)
    # level 0 against the precomputed-logits core: the runtime gates one
    # request at a time, so the reference logits come from the same
    # partitions at batch 1 (float32 results on the chip depend on the
    # batch shape in the last bits)
    rows = [edge_ref(params, images[i:i + 1], branch=deployed)
            for i in range(n_batch)]
    z1 = np.concatenate([np.asarray(z) for z, _ in rows])
    final1 = np.concatenate([np.asarray(cloud_ref(
        params, h, from_branch=deployed)) for _, h in rows])
    lcore = LogitsCore({deployed: z1}, final1, rt_plan, labels=labels)
    lrecs = {r.req_id: r for r in ServingRuntime(
        lcore, prof, rt_plan, reqs, config=RuntimeConfig(max_batch=1)
    ).run().records}
    cr1 = np.asarray(gate_statistics(
        jnp.asarray(z1), jnp.float32(plan.temperatures[deployed - 1]))[0])
    gate_diff = answer_diff = excused = 0
    for rid, r in runs[0].items():
        smp, lr = reqs[rid].sample, lrecs[rid]
        if abs(cr1[smp] - rt_plan.p_tar) <= GATE_CONF_ATOL:
            excused += 1
            continue
        gate_diff += r.on_device != lr.on_device
        answer_diff += r.correct != lr.correct
    check(gate_diff == 0, f"runtime: {gate_diff} gate decisions differ")
    check(answer_diff == 0, f"runtime: {answer_diff} answers differ")
    emit("served.summary", refused_counts=sorted(refused_counts),
         distinct_refused_counts=len(refused_counts),
         runtime_matches_logits_core=True, runtime_requests_excused=excused,
         **counter.since(snap))


# ------------------------------------------------------------ fleet program
def _per_request_parity(a, b) -> dict:
    """Host vs compiled telemetry, cell by cell: every non-latency column
    must match exactly, latencies within (LAT_RTOL, LAT_ATOL)."""
    import numpy as np

    check(a.n_cells == b.n_cells, "cell counts differ")
    mismatched = {}
    max_abs = max_rel = 0.0
    n = 0
    for ca, cb in zip(a._cells, b._cells):
        check(len(ca) == len(cb), "per-cell request counts differ")
        n += len(ca)
        for f in ca.FIELDS:
            va, vb = ca.column(f), cb.column(f)
            if f == "latency_s":
                d = np.abs(vb - va)
                if d.size:
                    max_abs = max(max_abs, float(d.max()))
                    max_rel = max(max_rel, float(
                        (d / np.maximum(np.abs(va), 1e-300)).max()))
                    bad = int((d > LAT_ATOL + LAT_RTOL * np.abs(va)).sum())
                    if bad:
                        mismatched[f] = mismatched.get(f, 0) + bad
            else:
                bad = int((~((va == vb) | ((va != va) & (vb != vb)))).sum())
                if bad:
                    mismatched[f] = mismatched.get(f, 0) + bad
    return dict(requests=n, mismatched=mismatched,
                latency_max_abs_err=max_abs, latency_max_rel_err=max_rel)


def fleet_phase(seed: int, counter: CompileCounter, n_cells: int = 64,
                requests_per_cell: int = 1600, mesh=None,
                phase: str = "fleet") -> None:
    from repro.fleet.compiled import CompiledFleetSimulator
    from repro.fleet.scenarios import fleet_gate_table, reference_fleet
    from repro.fleet.simulator import FleetConfig, FleetSimulator
    from repro.offload import latency as L
    from repro.serving.scenarios import (fit_drift_plans,
                                         synthetic_distorted_cascade)

    snap = counter.snapshot()
    val, test = synthetic_distorted_cascade(
        seed=seed, directions={"gaussian_blur": "under"})
    _, _, bank = fit_drift_plans(val)
    scn = reference_fleet(n_cells=n_cells, requests_per_cell=requests_per_cell,
                          seed=seed, val=val, test=test)
    prof, cfg = L.paper_2020(), FleetConfig(window_s=0.5)

    t0 = time.perf_counter()
    host = FleetSimulator(fleet_gate_table(bank, scn), scn.topology, prof,
                          config=cfg).run()
    host_s = time.perf_counter() - t0
    sim = CompiledFleetSimulator(
        fleet_gate_table(bank, scn, backend="compiled"), scn.topology, prof,
        config=cfg, mesh=mesh)
    t0 = time.perf_counter()
    cold = sim.run()
    cold_s = time.perf_counter() - t0
    compile_stats = counter.since(snap)
    t0 = time.perf_counter()
    warm = sim.run()
    warm_s = time.perf_counter() - t0

    parity = _per_request_parity(host, cold)
    again = _per_request_parity(cold, warm)
    check(parity["requests"] == scn.topology.n_requests,
          f"{parity['requests']} of {scn.topology.n_requests} requests served")
    check(again["latency_max_abs_err"] == 0.0 and not again["mismatched"],
          "compiled fleet is not deterministic across runs")
    devices = sorted(sim.output_devices)
    want = 1 if mesh is None else mesh.size
    check(len(devices) == want,
          f"outputs on devices {devices}, expected {want} device(s)")
    emit(phase, cells=n_cells, requests=parity["requests"],
         output_devices=devices, host_s=host_s, cold_s=cold_s, warm_s=warm_s,
         offload_rate=cold.fleet_summary().get("offload_rate"),
         latency_rtol=LAT_RTOL, latency_atol=LAT_ATOL,
         **{k: parity[k] for k in ("mismatched", "latency_max_abs_err",
                                   "latency_max_rel_err")},
         **compile_stats)
    check(not parity["mismatched"],
          f"compiled fleet differs from the host simulator: "
          f"{parity['mismatched']}")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the compiled fleet sharded over four "
                    "chips, against the host simulator")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); "
              "this script measures nothing elsewhere", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", compile_cache=cache_dir, jax=jax.__version__, **device)

    counter = CompileCounter()
    if args.chips == 4:
        from repro.sharding import fleet_mesh

        phases = [("fleet4", lambda: fleet_phase(
            args.seed, counter, mesh=fleet_mesh(4), phase="fleet4"))]
    else:
        phases = [("served", lambda: served_phase(args.seed, counter)),
                  ("fleet", lambda: fleet_phase(args.seed, counter))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            failed.append(name)
        emit(f"{name}.done", ok=name not in failed,
             seconds=time.perf_counter() - t0)
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
