"""Benchmark entry point: one function per paper table/figure + the kernel
microbench, the serving-runtime bench, the distortion-drift bench, and the
roofline summary. Prints ``name,us_per_call,derived`` CSV; the serving and
distortion benches also write the machine-readable ``BENCH_serving.json``
and ``BENCH_distortion.json`` artifacts.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--epochs N]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time_call(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_exit_gate_jnp(rows=256, vocab=50280):
    """The gate's jnp reference path (what the Pallas kernel replaces)."""
    from repro.core.exits import gate_statistics

    z = jax.random.normal(jax.random.PRNGKey(0), (rows, vocab))
    f = jax.jit(lambda z: gate_statistics(z, 1.7))
    us = _time_call(f, z)
    traffic = rows * vocab * 4 * 3  # softmax+max+entropy: ~3 passes
    return us, f"hbm_bytes_naive={traffic}"


def bench_exit_gate_kernel(rows=256, vocab=50280):
    """Fused kernel (interpret mode on CPU -- correctness path; the derived
    column reports the single-pass HBM traffic the fusion achieves on TPU)."""
    from repro.kernels.ops import exit_gate

    z = jax.random.normal(jax.random.PRNGKey(0), (8, vocab))
    us = _time_call(lambda a: exit_gate(a, 1.7), z, iters=1, warmup=1)
    traffic = rows * vocab * 4  # one streaming pass
    return us, f"hbm_bytes_fused={traffic};traffic_cut=3.0x"


def bench_plan_gate(rows=512, c=10):
    """OffloadPlan.gate fast path: temperature states hand raw logits + T
    straight to apply_gate (kernel-routable) instead of materializing
    calibrated logits."""
    from repro.core.policy import OffloadPlan
    from repro.core.calibration import TemperatureScaling

    plan = OffloadPlan(
        p_tar=0.85, calibrators=[TemperatureScaling.from_temperature(1.7)]
    )
    z = jax.random.normal(jax.random.PRNGKey(0), (rows, c)) * 4
    f = jax.jit(lambda zz: plan.gate(zz).exit_mask)
    us = _time_call(f, z)
    return us, f"rows={rows};fastpath=temperature"


def bench_calibration_fit(n=10000, c=10):
    from repro.core.calibration import fit_temperature

    key = jax.random.PRNGKey(0)
    z = jax.random.normal(key, (n, c)) * 6
    y = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, c)
    f = jax.jit(lambda z, y: fit_temperature(z, y)[0])
    us = _time_call(f, z, y)
    return us, f"n={n}"


def bench_b_alexnet_step(batch=256):
    from repro.models import convnet
    from repro.models.convnet import B_ALEXNET
    from repro.training import optim
    from repro.training.loop import make_train_step

    params = convnet.init_params(jax.random.PRNGKey(0))
    step = jax.jit(
        make_train_step(B_ALEXNET, optim.AdamWConfig(total_steps=10), remat=False)
    )
    state = optim.init(params)
    b = {
        "images": jax.random.normal(jax.random.PRNGKey(1), (batch, 32, 32, 3)),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 10),
    }
    us = _time_call(
        lambda p, s, bb: step(p, s, bb)[2]["loss"], params, state, b, iters=3
    )
    return us, f"batch={batch}"


def bench_smoke_decode(arch="qwen3-8b"):
    from repro.configs import get_smoke
    from repro.launch.serve import make_serve_step
    from repro.models import registry

    cfg = get_smoke(arch)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    caches = registry.init_cache(cfg, 4, 128)
    step = jax.jit(make_serve_step(cfg))
    tok = jnp.ones((4, 1), jnp.int32)
    us = _time_call(
        lambda: step(params, tok, caches, jnp.int32(5))[0]["token"], iters=3
    )
    return us, f"arch={arch}-smoke"


def bench_serving_runtime(n_requests=2000, out_path="BENCH_serving.json"):
    """Event-driven serving runtime under a congested Markov link: static
    calibrated plan vs the online controller re-scoring the same
    calibrators. The scenario is repro.serving.scenarios.run_congested_markov
    -- the SAME one the acceptance test pins down -- so the benchmark and
    the test cannot drift apart. Writes BENCH_serving.json with the fully
    deterministic simulated metrics (p50/p95/p99, deadline-miss, offload,
    accuracy); the wall-clock sim throughput goes to the CSV row only."""
    from repro.core.calibration import TemperatureScaling
    from repro.core.policy import OffloadPlan
    from repro.serving.scenarios import (
        run_congested_markov,
        synthetic_cascade_logits,
    )

    n = 2048
    exits, final, y = synthetic_cascade_logits(n)
    plan = OffloadPlan(
        p_tar=0.8,
        calibrators=[TemperatureScaling.from_temperature(1.0),
                     TemperatureScaling.from_temperature(1.0)],
    )

    def scenario(with_controller, obs=None, controller_config=None):
        t0 = time.perf_counter()
        tel = run_congested_markov(
            plan, exits, final, y,
            n_requests=n_requests, with_controller=with_controller, obs=obs,
            controller_config=controller_config,
        )
        return tel.summary(), time.perf_counter() - t0

    static, wall_s = scenario(False)
    ctrl, wall_c = scenario(True)

    # instrumentation-overhead arm: the same static scenario with the
    # FULL observability bundle (trace + audit + metrics + calibration
    # sketch) attached, median-of-3 both ways against the obs-off run.
    # Two claims ride in the artifact and CI asserts both: the obs-on
    # summaries are BIT-IDENTICAL to obs-off (zero perturbation), and
    # the wall-clock ratio stays under a documented (generous -- shared
    # CI runners are noisy) bound.
    from repro.obs import full_observability

    off_walls, on_walls = [], []
    obs_summary = None
    for _ in range(3):
        _, w = scenario(False)
        off_walls.append(w)
        obs_summary, w = scenario(False, obs=full_observability())
        on_walls.append(w)
    off_med = sorted(off_walls)[1]
    on_med = sorted(on_walls)[1]
    obs_overhead = {
        "off_wall_s": off_med,
        "on_wall_s": on_med,
        "ratio": on_med / off_med,
        "bound": 3.0,  # CI assertion; documented in docs/observability.md
        "bit_exact": obs_summary == static,
    }

    # congested-uplink compression sweep (ISSUE 10): controller arms
    # differing ONLY in the codec axis -- bytes-blind (no axis: the
    # legacy candidate table), level-0-only (identity codec: MUST
    # reproduce the bytes-blind run bit-exactly), and compression-aware
    # (levels 0/1/2 priced per candidate). Each arm carries a metrics
    # registry so uplink bytes are the runtime's own post-codec
    # serving_uplink_bytes_total counter, not a model. With every axis
    # free the aware controller spends part of the byte win on routing
    # (compression makes offloading cheap, so the latency-optimal split
    # moves EARLIER -- bigger payloads, more offloads, much better p99),
    # so the >=4x byte claim is asserted on a split-pinned pair
    # (`branches` pins the deployed branch, p_tar held: the codec level
    # is the only knob) while the free-axes pair carries the p99 and
    # reliability-gap claims. All four assertions are CI gates.
    from repro.obs import MetricsRegistry, Observability
    from repro.serving.controller import ControllerConfig

    def _comp_arm(levels, pin_branch=False):
        cfg = ControllerConfig(
            interval_s=0.5, window_s=1.0, min_accuracy=0.9,
            compression_levels=levels,
            branches=(plan.exit_index + 1,) if pin_branch else None,
        )
        reg = MetricsRegistry()
        s, _ = scenario(True, obs=Observability(metrics=reg),
                        controller_config=cfg)
        return s, reg.counter_total("serving_uplink_bytes_total")

    blind, blind_bytes = _comp_arm(None)
    lvl0, lvl0_bytes = _comp_arm((0,))
    aware, aware_bytes = _comp_arm((0, 1, 2))
    pin_blind, pin_blind_bytes = _comp_arm(None, pin_branch=True)
    pin_aware, pin_aware_bytes = _comp_arm((0, 1, 2), pin_branch=True)
    byte_cut = pin_blind_bytes / max(pin_aware_bytes, 1.0)
    added_gap = aware["miscalibration_gap"] - blind["miscalibration_gap"]
    compression = {
        "levels": [0, 1, 2],
        "bytes_blind": blind,
        "level0_identity": lvl0,
        "compression_aware": aware,
        "uplink_bytes_blind": blind_bytes,
        "uplink_bytes_level0": lvl0_bytes,
        "uplink_bytes_aware": aware_bytes,
        "uplink_byte_cut_free_axes": blind_bytes / max(aware_bytes, 1.0),
        "pinned_split": {
            "branch": plan.exit_index + 1,
            "bytes_blind": pin_blind,
            "compression_aware": pin_aware,
            "uplink_bytes_blind": pin_blind_bytes,
            "uplink_bytes_aware": pin_aware_bytes,
            "uplink_byte_cut": byte_cut,
        },
        "added_reliability_gap": added_gap,
        "p99_blind_ms": blind["p99_ms"],
        "p99_aware_ms": aware["p99_ms"],
        "level0_bit_exact": lvl0 == blind and lvl0_bytes == blind_bytes,
    }
    if not compression["level0_bit_exact"]:
        raise AssertionError(
            "identity-codec (level 0) controller is not bit-exact with "
            "the bytes-blind controller")
    if byte_cut < 4.0:
        raise AssertionError(
            f"compression-aware controller cut uplink bytes only "
            f"{byte_cut:.2f}x (< 4x) at the pinned split")
    if added_gap > 0.01:
        raise AssertionError(
            f"compression added {added_gap:.4f} reliability gap (> 0.01)")
    if not aware["p99_ms"] < blind["p99_ms"]:
        raise AssertionError(
            f"compression-aware p99 {aware['p99_ms']:.1f}ms did not "
            f"strictly beat bytes-blind {blind['p99_ms']:.1f}ms")

    # metadata derived from the scenario module itself, never duplicated
    import inspect

    from repro.serving.scenarios import congested_markov_network

    sig = inspect.signature(run_congested_markov).parameters
    net = congested_markov_network()
    payload = {
        "scenario": {
            "arrival_rate_hz": sig["arrival_rate_hz"].default,
            "n_requests": n_requests,
            "network": (
                f"markov(good={net.good_bps / 1e6:g}Mbps,"
                f"bad={net.bad_bps / 1e6:g}Mbps)"
            ),
            "deadline_ms": sig["deadline_s"].default * 1e3,
            "profile": "paper_2020",
        },
        "static": static,
        "controller": ctrl,
        "obs_overhead": obs_overhead,
        "compression": compression,
        "p99_improvement": 1.0 - ctrl["p99_ms"] / static["p99_ms"],
        "miss_rate_improvement": static["deadline_miss_rate"]
        - ctrl["deadline_miss_rate"],
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    us = (wall_s + wall_c) / (2 * n_requests) * 1e6
    return us, (
        f"sim_rps={2 * n_requests / (wall_s + wall_c):.0f};"
        f"p99_static_ms={static['p99_ms']:.1f};"
        f"p99_ctrl_ms={ctrl['p99_ms']:.1f};"
        f"obs_overhead={obs_overhead['ratio']:.2f}x;"
        f"comp_bytes_cut={byte_cut:.1f}x;"
        f"comp_p99_ms={aware['p99_ms']:.1f};"
        f"artifact={out_path}"
    )


def bench_distortion_serving(n_requests=1500, out_path="BENCH_distortion.json"):
    """Offloading under drifting input distortion: uncalibrated plan vs the
    single global calibrated plan (fit on clean validation data, the
    paper's procedure) vs the expert PlanBank (one plan per distortion
    context + the cheap edge-side estimator picking the expert per
    sample). The scenario is repro.serving.scenarios.run_distortion_drift
    -- the SAME one tests/test_distortion.py pins down -- under a Markov
    severity schedule that visits all four regimes. Headline metric:
    on-device-weighted miscalibration gap |on-device accuracy - p_tar|
    per regime; CI asserts the bank beats the global plan. A second pair
    of arms serves the global plan WITH the online controller: once
    re-scoring on clean validation logits only (the original rule) and
    once context-AWARE (candidate tables weighted by the traffic mix the
    runtime's own telemetry observed; the fleet's rule ported back) --
    CI asserts the context-aware arm's gap is strictly smaller. Writes
    the fully deterministic BENCH_distortion.json."""
    from repro.serving.scenarios import (
        drift_contexts,
        drift_controller_config,
        fit_drift_plans,
        run_distortion_drift,
        severity_drift_schedule,
        synthetic_distorted_cascade,
    )

    val, test = synthetic_distorted_cascade()
    uncal, global_plan, bank = fit_drift_plans(val)
    sched = severity_drift_schedule()
    results, wall = {}, 0.0
    for name, plan in (
        ("uncalibrated", uncal),
        ("global_calibrated", global_plan),
        ("expert_bank", bank),
    ):
        t0 = time.perf_counter()
        tel = run_distortion_drift(plan, test, schedule=sched,
                                   n_requests=n_requests)
        wall += time.perf_counter() - t0
        results[name] = {
            "summary": tel.summary(),
            "per_context": tel.per_context_summary(),
        }
    g = results["global_calibrated"]["summary"]["miscalibration_gap"]
    b = results["expert_bank"]["summary"]["miscalibration_gap"]

    # controller arms (satellite of ISSUE 5): same global plan, same
    # reference controller config -- the only difference is the
    # INFORMATION the re-score prices (clean val logits vs the observed
    # traffic mix over all contexts' val logits)
    ctrl_results = {}
    for name, ca in (
        ("controller_clean_val", False),
        ("controller_context_aware", True),
    ):
        t0 = time.perf_counter()
        tel = run_distortion_drift(
            global_plan, test, schedule=severity_drift_schedule(),
            n_requests=n_requests, with_controller=True, val=val,
            context_aware=ca, controller_config=drift_controller_config(),
        )
        wall += time.perf_counter() - t0
        ctrl_results[name] = {
            "summary": tel.summary(),
            "per_context": tel.per_context_summary(),
        }
    gc = ctrl_results["controller_clean_val"]["summary"]["miscalibration_gap"]
    gx = ctrl_results["controller_context_aware"]["summary"]["miscalibration_gap"]

    # dwell-time vs controller-interval sweep (ROADMAP "bench breadth"):
    # how does the bank + online controller fare when regime drift is
    # faster or slower than the controller's re-score cadence? Each combo
    # serves the same workload under a fresh Markov schedule with the
    # given dwell; reported per combo: gap, p99, controller switches.
    sweep = []
    total_requests = 5 * n_requests  # three headline runs + two controller arms
    for dwell_s in (1.0, 3.0, 8.0):
        for interval_s in (0.5, 2.0):
            t0 = time.perf_counter()
            tel = run_distortion_drift(
                bank, test,
                schedule=severity_drift_schedule(dwell_s=dwell_s),
                n_requests=600, with_controller=True, val=val,
                controller_interval_s=interval_s,
            )
            wall += time.perf_counter() - t0
            total_requests += 600
            s = tel.summary()
            sweep.append({
                "dwell_s": dwell_s,
                "controller_interval_s": interval_s,
                "miscalibration_gap": s["miscalibration_gap"],
                "p99_ms": s["p99_ms"],
                "accuracy": s["accuracy"],
                "controller_switches": s["controller_switches"],
            })

    payload = {
        "scenario": {
            "contexts": [spec.key for spec in drift_contexts()],
            "schedule": f"markov(dwell={sched.dwell_s:g}s)",
            "n_requests": n_requests,
            "p_tar": bank.default_plan.p_tar,
            "profile": "paper_2020",
        },
        "plans": results,
        "controller_arms": ctrl_results,
        "gap_global": g,
        "gap_bank": b,
        "gap_improvement": g - b,
        "gap_controller_clean": gc,
        "gap_controller_context_aware": gx,
        "gap_context_aware_improvement": gc - gx,
        "dwell_interval_sweep": sweep,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    us = wall / total_requests * 1e6
    return us, (
        f"gap_uncal={results['uncalibrated']['summary']['miscalibration_gap']:.3f};"
        f"gap_global={g:.3f};gap_bank={b:.3f};"
        f"gap_ctrl_clean={gc:.3f};gap_ctrl_ctx={gx:.3f};artifact={out_path}"
    )


def bench_fleet(out_path="BENCH_fleet.json", scenario_names=None):
    """Fleet-scale vectorized serving: >=100k requests across >=64 cells
    (heterogeneous links, per-cell Markov severity drift, one shared
    cloud), simulated in seconds by `repro.fleet`. Compares the static
    UNCALIBRATED plan against the expert PlanBank driven by the
    context-aware fleet controller -- the scenario is
    repro.fleet.scenarios.reference_fleet, the SAME one
    tests/test_fleet.py pins down -- then sweeps the ADVERSARIAL
    orchestration matrix (`repro.orchestration.scenarios`: weather
    fronts, flash crowds, link outages, cloud brownouts, poisoned and
    good canary rollouts), each with its controller-vs-static (or
    rollout-vs-no-rollout) verdict. `scenario_names` filters the matrix
    (None = all registered; [] = skip). All simulated metrics are
    deterministic; the wall-clock throughput column is the speed claim
    the event-driven runtime cannot make. The ``fleet_compiled`` section
    records the fully compiled window pipeline (ISSUE 8): parity verdict
    vs host numpy plus honest CPU wall clocks at reference (64-cell) and
    scale (>=1M requests / >=256 cells) sizes."""
    from repro.fleet.scenarios import reference_fleet, run_fleet
    from repro.serving.scenarios import (
        fit_drift_plans,
        synthetic_distorted_cascade,
    )

    val, test = synthetic_distorted_cascade(
        directions={"gaussian_blur": "under"}
    )
    uncal, global_plan, bank = fit_drift_plans(val)
    scenario = reference_fleet(val=val, test=test)

    runs, wall = {}, {}
    for name, plan, ctrl in (
        ("static_uncalibrated", uncal, False),
        ("expert_bank_static", bank, False),
        ("expert_bank_controller", bank, True),
    ):
        t0 = time.perf_counter()
        tel = run_fleet(plan, scenario, with_controller=ctrl)
        wall[name] = time.perf_counter() - t0
        runs[name] = {
            "fleet": tel.fleet_summary(),
            "per_context": tel.per_context_summary(),
        }
    u = runs["static_uncalibrated"]["fleet"]
    c = runs["expert_bank_controller"]["fleet"]
    n_req = scenario.topology.n_requests
    total_wall = sum(wall.values())

    # gate-backend microbench (satellite of ISSUE 5): the same reference
    # gate table window-gated through the host numpy backend and the
    # jitted JAX backend, at the reference fleet's window sizes (one
    # 0.5 s window of the 64-cell fleet is ~640 arrivals) and the larger
    # windows a scaled-up fleet would push. Parity is asserted (identical
    # decisions, confidences to 1e-6); the speedup column is the
    # throughput claim and is machine-dependent.
    from repro.core.gatepath import GateTable

    tables = {
        name: GateTable(
            scenario.test["exit_logits"], scenario.test["final"], bank,
            labels=scenario.test["labels"],
            features_by_context=scenario.test["features"], backend=name,
        )
        for name in ("numpy", "jax")
    }
    rng = np.random.default_rng(0)
    n_cells = scenario.topology.n_cells
    gate_rows, parity = [], True
    for n_window in (640, 8192, 65536):
        ctx = rng.integers(0, len(tables["numpy"].ctx_keys), n_window)
        smp = rng.integers(0, tables["numpy"].n_samples, n_window)
        cells = rng.integers(0, n_cells, n_window)
        branch_by_cell = 1 + (np.arange(n_cells) % 2)
        p_tar_by_cell = np.where(np.arange(n_cells) % 3 == 0, 0.5, 0.8)
        out, us = {}, {}
        for name, table in tables.items():
            call = lambda: table.gate_window_cells(  # noqa: E731
                ctx, smp, cells, branch_by_cell, p_tar_by_cell, n_cells
            )
            call()  # warm the jit/trace cache outside the timing
            t0 = time.perf_counter()
            iters = 20
            for _ in range(iters):
                out[name] = call()
            us[name] = (time.perf_counter() - t0) / iters * 1e6
        ok = bool(
            np.array_equal(out["numpy"]["on_device"], out["jax"]["on_device"])
            and np.array_equal(out["numpy"]["prediction"], out["jax"]["prediction"])
            and np.allclose(out["numpy"]["confidence"], out["jax"]["confidence"],
                            rtol=1e-5, atol=1e-6)
        )
        parity = parity and ok
        gate_rows.append({
            "window": n_window,
            "numpy_us": us["numpy"],
            "jax_us": us["jax"],
            "speedup_jax_vs_numpy": us["numpy"] / us["jax"],
            "parity": ok,
        })
    # compiled fleet pipeline (ISSUE 8): the WHOLE window pipeline (gate
    # -> device FIFO queues -> uplink -> shared cloud) as jitted device
    # stages, max-plus associative_scan recurrences, shard_map over the
    # cell axis. Two sub-runs, both parity-checked against host numpy:
    # the 64-cell reference (same scenario as above) and a >=1M-request
    # / >=256-cell scale run -- the CI-runner floor; 10M+ requests
    # across 1000+ cells is the accelerator target the same program
    # reaches by sharding cells over real devices. Wall clocks are
    # honest CPU numbers: at reference scale the fixed compile/dispatch
    # cost still loses to numpy, at 1M+ the compiled path wins big.
    def _timed_run(plan, scn, backend=None):
        t0 = time.perf_counter()
        tel = run_fleet(plan, scn, backend=backend)
        return tel.fleet_summary(), time.perf_counter() - t0

    def _summaries_match(a, b):
        return bool(all(
            np.allclose(b[k], a[k], rtol=1e-9, atol=1e-12) for k in a
        ))

    ref_np = runs["expert_bank_static"]["fleet"]  # numpy arm, timed above
    ref_np_s = wall["expert_bank_static"]
    _, ref_c_cold_s = _timed_run(bank, scenario, backend="compiled")
    ref_c, ref_c_s = _timed_run(bank, scenario, backend="compiled")
    scale_scn = reference_fleet(n_cells=256, requests_per_cell=4096,
                                val=val, test=test)
    scale_np, scale_np_s = _timed_run(bank, scale_scn)
    scale_c, scale_c_s = _timed_run(bank, scale_scn, backend="compiled")
    n_scale = scale_scn.topology.n_requests
    compiled_parity = (_summaries_match(ref_np, ref_c)
                       and _summaries_match(scale_np, scale_c))
    fleet_compiled = {
        "parity": compiled_parity,
        "requests": n_scale,
        "cells": scale_scn.topology.n_cells,
        "devices": jax.device_count(),
        "mesh": "auto: 1-D shard_map mesh over local devices, axis "
                "'cells' (single-device on the CI runner)",
        "accelerator_target": {
            "requests": 10_000_000, "cells": 1000,
            "note": "same jitted program, cells sharded over real "
                    "devices; CI runner numbers below are CPU-bound",
        },
        "reference": {
            "requests": n_req,
            "cells": scenario.topology.n_cells,
            "numpy_s": ref_np_s,
            "compiled_cold_s": ref_c_cold_s,
            "compiled_warm_s": ref_c_s,
            "speedup_compiled_vs_numpy": ref_np_s / ref_c_s,
        },
        "scale": {
            "requests": n_scale,
            "cells": scale_scn.topology.n_cells,
            "numpy_s": scale_np_s,
            "compiled_s": scale_c_s,
            "numpy_rps": n_scale / scale_np_s,
            "compiled_rps": n_scale / scale_c_s,
            "speedup_compiled_vs_numpy": scale_np_s / scale_c_s,
        },
    }

    # fleet compression sweep (ISSUE 10): the same three-arm codec sweep
    # as BENCH_serving, on the 64-cell fleet. Bytes-blind re-uses the
    # reference controller config; level-0-only restricts the axis to
    # the identity codec and MUST reproduce the bytes-blind run (and the
    # obs-off expert_bank_controller arm above) bit-exactly; the
    # compression-aware arm prices levels 0/1/2 per (cell, candidate).
    # Uplink bytes come from the simulator's own per-cell
    # fleet_uplink_bytes_total counter (uplink + backhaul, post-codec).
    # The compiled stack's level-0 identity is the `fleet_compiled`
    # parity verdict above (static deployments run at level 0); a
    # level-2 static plan is additionally parity-checked host-vs-
    # compiled so the codec axis itself is pinned across backends.
    from repro.fleet.controller import FleetControllerConfig
    from repro.obs import MetricsRegistry, Observability

    def _comp_fleet_arm(levels, pin_branch=False):
        cfg = FleetControllerConfig(
            interval_s=1.0, window_s=2.0,
            p_tar_grid=None if pin_branch else (0.3, 0.5, 0.7, 0.8),
            branches=((bank.default_plan.exit_index + 1,)
                      if pin_branch else None),
            min_accuracy=0.8, cloud_rho_max=0.9,
            compression_levels=levels,
        )
        reg = MetricsRegistry()
        tel = run_fleet(bank, scenario, with_controller=True,
                        controller_config=cfg,
                        obs=Observability(metrics=reg))
        return (tel.fleet_summary(),
                reg.counter_total("fleet_uplink_bytes_total"))

    blind_f, blind_f_bytes = _comp_fleet_arm(None)
    lvl0_f, lvl0_f_bytes = _comp_fleet_arm((0,))
    aware_f, aware_f_bytes = _comp_fleet_arm((0, 1, 2))
    pin_blind_f, pin_blind_f_bytes = _comp_fleet_arm(None, pin_branch=True)
    pin_aware_f, pin_aware_f_bytes = _comp_fleet_arm((0, 1, 2),
                                                     pin_branch=True)
    plan_l2 = global_plan.with_compression(2)
    l2_np, _ = _timed_run(plan_l2, scenario)
    l2_c, _ = _timed_run(plan_l2, scenario, backend="compiled")
    byte_cut_f = pin_blind_f_bytes / max(pin_aware_f_bytes, 1.0)
    added_gap_f = (aware_f["miscalibration_gap"]
                   - blind_f["miscalibration_gap"])
    compression = {
        "levels": [0, 1, 2],
        "bytes_blind": blind_f,
        "level0_identity": lvl0_f,
        "compression_aware": aware_f,
        "uplink_bytes_blind": blind_f_bytes,
        "uplink_bytes_level0": lvl0_f_bytes,
        "uplink_bytes_aware": aware_f_bytes,
        "uplink_byte_cut_free_axes": blind_f_bytes / max(aware_f_bytes, 1.0),
        "pinned_split": {
            "branch": bank.default_plan.exit_index + 1,
            "bytes_blind": pin_blind_f,
            "compression_aware": pin_aware_f,
            "uplink_bytes_blind": pin_blind_f_bytes,
            "uplink_bytes_aware": pin_aware_f_bytes,
            "uplink_byte_cut": byte_cut_f,
        },
        "added_reliability_gap": added_gap_f,
        "p99_blind_ms": blind_f["p99_ms"],
        "p99_aware_ms": aware_f["p99_ms"],
        "level0_bit_exact": (lvl0_f == blind_f
                             and lvl0_f_bytes == blind_f_bytes
                             and lvl0_f == c),
        "compiled_level2_parity": _summaries_match(l2_np, l2_c),
    }
    if not compression["level0_bit_exact"]:
        raise AssertionError(
            "fleet identity-codec (level 0) controller is not bit-exact "
            "with the bytes-blind controller")
    if byte_cut_f < 4.0:
        raise AssertionError(
            f"fleet compression-aware controller cut uplink bytes only "
            f"{byte_cut_f:.2f}x (< 4x) at the pinned split")
    if added_gap_f > 0.01:
        raise AssertionError(
            f"fleet compression added {added_gap_f:.4f} reliability gap "
            f"(> 0.01)")
    if not aware_f["p99_ms"] < blind_f["p99_ms"]:
        raise AssertionError(
            f"fleet compression-aware p99 {aware_f['p99_ms']:.1f}ms did "
            f"not strictly beat bytes-blind {blind_f['p99_ms']:.1f}ms")
    if not compression["compiled_level2_parity"]:
        raise AssertionError(
            "compiled backend diverged from host numpy on the level-2 "
            "static plan")

    # adversarial orchestration matrix (churn, QoS, canary rollouts)
    from repro.orchestration import run_scenarios

    t0 = time.perf_counter()
    adversarial = run_scenarios(names=scenario_names)
    adversarial_wall = time.perf_counter() - t0

    payload = {
        "scenario": {
            "cells": scenario.topology.n_cells,
            "requests": n_req,
            "requests_per_cell": n_req // scenario.topology.n_cells,
            "cloud_servers": scenario.topology.cloud_servers,
            "contexts": scenario.contexts,
            "directions": {"gaussian_blur": "under"},
            "profile": "paper_2020",
        },
        "plans": runs,
        "p99_uncal_ms": u["p99_ms"],
        "p99_controller_ms": c["p99_ms"],
        "p99_improvement": 1.0 - c["p99_ms"] / u["p99_ms"],
        "gap_uncal": u["miscalibration_gap"],
        "gap_controller": c["miscalibration_gap"],
        "gap_improvement": u["miscalibration_gap"] - c["miscalibration_gap"],
        "gate_backend": {"parity": parity, "windows": gate_rows},
        "fleet_compiled": fleet_compiled,
        "compression": compression,
        "adversarial_scenarios": adversarial,
        "adversarial_wall_s": adversarial_wall,
        # wall-clock figures are machine-dependent and excluded from any
        # determinism assertion; they are the throughput claim
        "wall_clock": {
            "seconds_per_run": wall,
            "requests_per_second": {k: n_req / v for k, v in wall.items()},
        },
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    us = total_wall / (len(runs) * n_req) * 1e6
    n_pass = sum(1 for r in adversarial if r["pass"])
    fc = fleet_compiled["scale"]
    return us, (
        f"cells={scenario.topology.n_cells};requests={n_req};"
        f"sim_rps={len(runs) * n_req / total_wall:.0f};"
        f"p99_uncal={u['p99_ms']:.0f}ms;p99_ctrl={c['p99_ms']:.0f}ms;"
        f"gap_uncal={u['miscalibration_gap']:.3f};"
        f"gap_ctrl={c['miscalibration_gap']:.3f};"
        f"compiled_parity={compiled_parity};"
        f"comp_bytes_cut={byte_cut_f:.1f}x;"
        f"compiled_1M_rps={fc['compiled_rps']:.0f}"
        f"(numpy={fc['numpy_rps']:.0f});"
        f"scenarios={n_pass}/{len(adversarial)};artifact={out_path}"
    )


def bench_emit_obs(out_prefix="OBS"):
    """Re-run the reference serving and fleet scenarios with the full
    observability plane (`repro.obs`) attached and write the artifacts
    next to the BENCH files:

      {prefix}_serving_trace.jsonl   unsampled per-request trace
      {prefix}_serving_metrics.json  metrics registry (JSON export)
      {prefix}_serving_metrics.prom  same registry, Prometheus text
      {prefix}_serving_audit.jsonl   online-controller decision audit
      {prefix}_serving_calibration.json  reliability sketch of the run
      {prefix}_fleet_trace.jsonl     sampled trace of the >=100k fleet run
      {prefix}_fleet_metrics.json/.prom
      {prefix}_fleet_calibration.json
      {prefix}_fleet_audit.jsonl     guarded poisoned-canary rollout audit
                                     (holds the full trip->rollback chain,
                                     tripped by the CALIBRATION SLO)
      {prefix}_drift_calibration.json  sketch of a poisoned deployment
      {prefix}_bank.json             the poisoned candidate bank (its
                                     metadata still carries the honest
                                     fit-time val ECE, which is exactly
                                     what the drift report diffs against)

    Every artifact is cross-examined in-process with `repro.obs.check`
    before returning (CI re-runs the CLI against the files); a violated
    invariant fails the bench. The canary arm additionally asserts the
    EARLY-WARNING claim: the under-confident poison offloads its
    traffic, so the reliability-gap SLO (on-device label outcomes only)
    never reaches its evidence floor -- the windowed calibration gauges
    are the only stream that trips, and they must trip before any
    gap-family verdict."""
    from repro.core.calibration import TemperatureScaling
    from repro.core.policy import OffloadPlan
    from repro.fleet.scenarios import reference_fleet, run_fleet
    from repro.obs import (
        AuditLog,
        JsonlTraceSink,
        MetricsRegistry,
        Observability,
        ReliabilitySketch,
    )
    from repro.obs.check import (
        check_calibration,
        run_checks,
        verify_rollback_chain,
    )
    from repro.obs.trace import read_jsonl
    from repro.serving.scenarios import (
        fit_drift_plans,
        run_congested_markov,
        synthetic_cascade_logits,
        synthetic_distorted_cascade,
    )

    t_start = time.perf_counter()

    # serving: the BENCH_serving controller arm, traced unsampled
    exits, final, y = synthetic_cascade_logits(2048)
    plan = OffloadPlan(
        p_tar=0.8,
        calibrators=[TemperatureScaling.from_temperature(1.0),
                     TemperatureScaling.from_temperature(1.0)],
    )
    audit_s, metrics_s = AuditLog(), MetricsRegistry()
    obs_s = Observability(
        trace=JsonlTraceSink(f"{out_prefix}_serving_trace.jsonl"),
        audit=audit_s, metrics=metrics_s, calibration=ReliabilitySketch(),
    )
    run_congested_markov(plan, exits, final, y, n_requests=2000,
                         with_controller=True, obs=obs_s)
    obs_s.close()
    metrics_s.write_json(f"{out_prefix}_serving_metrics.json")
    metrics_s.write_prometheus(f"{out_prefix}_serving_metrics.prom")
    audit_s.to_jsonl(f"{out_prefix}_serving_audit.jsonl")
    obs_s.calibration.save(f"{out_prefix}_serving_calibration.json")
    errors = run_checks(
        read_jsonl(f"{out_prefix}_serving_trace.jsonl"),
        metrics_s, audit_s.records, calibration=obs_s.calibration,
    )

    # fleet: the full reference fleet (>=100k requests), sampled trace
    val, test = synthetic_distorted_cascade(
        directions={"gaussian_blur": "under"}
    )
    _, _, bank = fit_drift_plans(val)
    scn = reference_fleet(val=val, test=test)
    sample_every = 101
    metrics_f = MetricsRegistry()
    obs_f = Observability(
        trace=JsonlTraceSink(f"{out_prefix}_fleet_trace.jsonl"),
        metrics=metrics_f, trace_sample_every=sample_every,
        calibration=ReliabilitySketch(),
    )
    run_fleet(bank, scn, with_controller=True, obs=obs_f)
    obs_f.close()
    metrics_f.write_json(f"{out_prefix}_fleet_metrics.json")
    metrics_f.write_prometheus(f"{out_prefix}_fleet_metrics.prom")
    obs_f.calibration.save(f"{out_prefix}_fleet_calibration.json")
    errors += run_checks(
        read_jsonl(f"{out_prefix}_fleet_trace.jsonl"), metrics_f,
        calibration=obs_f.calibration,
    )

    # fleet audit: a guarded poisoned-canary rollout whose SLO watches
    # the streaming calibration gauges, so the artifact CI cross-examines
    # holds a complete CALIBRATION trip -> rollback causal chain. The
    # poison is UNDER-confidence (T x20): the canary offloads nearly
    # everything, the gap-family SLOs starve below their gate-sample
    # evidence floor, and only the calibration stream (which covers
    # offloaded requests too) can see the failure.
    from repro.orchestration.qos import CellSLO
    from repro.orchestration.scenarios import _rollout_pieces, poisoned_bank

    scn_small = reference_fleet(n_cells=8, requests_per_cell=300,
                                cloud_servers=2, val=val, test=test)
    # ece_cap sits between the incumbent's windowed per-cell ECE (~0.21
    # on these small windows) and the poisoned canary's (~0.45): the
    # incumbent never trips, the canary always does.
    cal_slo = CellSLO(reliability_shortfall=0.12, ece_cap=0.30,
                      min_requests=12, min_gate_samples=25)
    orch, monitor, _ = _rollout_pieces(
        scn_small, poisoned_bank(bank, temp_scale=20.0), slo=cal_slo)
    audit_f, metrics_a = AuditLog(), MetricsRegistry()
    cal_a = ReliabilitySketch()
    run_fleet(bank, scn_small, orchestrator=orch,
              obs=Observability(audit=audit_f, metrics=metrics_a,
                                calibration=cal_a))
    audit_f.to_jsonl(f"{out_prefix}_fleet_audit.jsonl")
    cal_a.save(f"{out_prefix}_fleet_audit_calibration.json")
    errors += check_calibration(cal_a, metrics=metrics_a)
    chain = verify_rollback_chain(audit_f.records)
    if not chain["ok"]:
        errors.append(f"rollback chain broken: {chain['why']}")
    trips = audit_f.filter(actor="qos_monitor", action="qos_trip")
    ece_trips = [r for r in trips if r["evidence"]["metric"] == "ece"]
    gap_trips = [r for r in trips if r["evidence"]["metric"]
                 in ("reliability_gap", "reliability_shortfall")]
    if not ece_trips:
        errors.append("calibration SLO never tripped on the poisoned canary")
    elif gap_trips and min(r["t_s"] for r in gap_trips) <= min(
            r["t_s"] for r in ece_trips):
        errors.append("gap-family SLO tripped before the calibration SLO")

    # drift-report artifacts: a poisoned bank deployed STATICALLY, plus
    # the bank file itself (whose metadata still carries the honest
    # fit-time val ECE) -- `repro.obs.calibration_report` must flag it
    from repro.obs.calibration_report import build_report

    bad = poisoned_bank(bank)
    cal_d = ReliabilitySketch()
    run_fleet(bad, scn_small, obs=Observability(calibration=cal_d))
    cal_d.save(f"{out_prefix}_drift_calibration.json")
    bad.save(f"{out_prefix}_bank.json")
    report = build_report(
        cal_d,
        bank_meta={**bad.metadata, "default_context": bad.default_context},
    )
    if not report["flagged"]:
        errors.append("drift report did not flag the poisoned deployment")
    if errors:
        raise AssertionError(
            "obs invariants violated: " + "; ".join(errors[:5])
        )

    n_total = 2000 + scn.topology.n_requests
    us = (time.perf_counter() - t_start) / n_total * 1e6
    return us, (
        f"fleet_requests={scn.topology.n_requests};"
        f"trace_sample_every={sample_every};"
        f"audit_records={len(audit_f)};rollback_chain=ok;"
        f"artifacts={out_prefix}_*"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip figure benchmarks")
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument(
        "--scenario",
        default=None,
        help="comma-separated adversarial scenario names for the fleet "
        "bench (default: all registered; 'none' skips the matrix)",
    )
    ap.add_argument(
        "--emit-obs",
        action="store_true",
        help="re-run the reference scenarios with the observability plane "
        "attached and write OBS_* trace/metrics/audit artifacts next to "
        "the BENCH files",
    )
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.scenario is None or args.scenario == "all":
        scenario_names = None
    elif args.scenario == "none":
        scenario_names = []
    else:
        scenario_names = [s for s in args.scenario.split(",") if s]

    print("name,us_per_call,derived")
    rows = [
        ("exit_gate_jnp", *bench_exit_gate_jnp()),
        ("exit_gate_kernel_interpret", *bench_exit_gate_kernel()),
        ("plan_gate_fastpath", *bench_plan_gate()),
        ("calibration_fit_temperature", *bench_calibration_fit()),
        ("b_alexnet_train_step", *bench_b_alexnet_step()),
        ("smoke_decode_step", *bench_smoke_decode()),
        ("serving_runtime_per_request", *bench_serving_runtime()),
        ("distortion_drift_per_request", *bench_distortion_serving()),
        ("fleet_simulator_per_request",
         *bench_fleet(scenario_names=scenario_names)),
    ]
    if args.emit_obs:
        rows.append(("observability_emit", *bench_emit_obs()))
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if not args.quick:
        t0 = time.perf_counter()
        from benchmarks.paper_figures import run_all

        res = run_all(epochs=args.epochs)
        us = (time.perf_counter() - t0) * 1e6
        t = res["temps"]
        # headline claim numbers (Fig. 4 at the outage knee): conv vs cal
        f4 = {r[0]: (r[1], r[2]) for r in res["fig4"]}
        knee = next((p for p in sorted(f4) if f4[p][0] > 0), max(f4))
        convk, calk = f4[knee]
        print(
            f"paper_figures_all,{us:.1f},"
            f"T1={t[0]:.2f};outage@{knee} conv={convk:.3f} cal={calk:.3f}"
        )

        # roofline summary (from cached dry-run artifacts if present)
        try:
            from benchmarks.roofline import table

            rl = table(mesh="16x16")
            n_dom = {}
            for r in rl:
                n_dom[r["dominant"]] = n_dom.get(r["dominant"], 0) + 1
            print(f"roofline_pairs,{len(rl)},dominant_counts={n_dom}")
        except Exception as e:  # dry-run artifacts absent
            print(f"roofline_pairs,0,unavailable:{e}")


if __name__ == "__main__":
    main()
