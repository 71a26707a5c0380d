"""Event-driven serving runtime, telemetry, and online controller tests.

The anchor test pins the runtime to the paper's static numbers: one device,
the fixed 18.8 Mbps link, arrivals slow enough that queues stay empty --
then every per-request latency equals the closed-form edge/comm/cloud sums
to 1e-9 and the offload rate matches the offline batch simulator on the
same logits. The congestion tests then exercise what the static math
cannot express: queueing, microbatching, time-varying links, and the
Edgent-style controller.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.calibration import TemperatureScaling
from repro.core.policy import OffloadPlan, rescore_plan
from repro.models.convnet import payload_bytes
from repro.offload import latency as L
from repro.offload.simulator import simulate_batches
from repro.serving import (
    ControllerConfig,
    FixedRateNetwork,
    LogitsCore,
    MarkovNetwork,
    OnlineController,
    RuntimeConfig,
    ServingRuntime,
    Telemetry,
    constant_workload,
    poisson_workload,
    trace_workload,
)


def _synthetic_logits(n=512, c=10, seed=0):
    """Branch 1 moderately confident, branch 2 strictly more confident,
    cloud main head always right -- the shared reference cascade."""
    from repro.serving.scenarios import synthetic_cascade_logits

    exits, final, y = synthetic_cascade_logits(n, c, seed)
    return exits[1], exits[2], final, y


@pytest.fixture(scope="module")
def setup():
    z1, z2, final, y = _synthetic_logits()
    plan = OffloadPlan(
        p_tar=0.8,
        calibrators=[
            TemperatureScaling.from_temperature(1.0),
            TemperatureScaling.from_temperature(1.0),
        ],
    )
    profile = L.paper_2020()
    core = LogitsCore({1: z1, 2: z2}, final, plan, labels=y)
    return z1, z2, final, y, plan, profile, core


# --------------------------------------------------- static special case
def test_runtime_reproduces_static_numbers(setup):
    """Empty queues + fixed link => the runtime IS the paper's closed-form
    model, request by request, and agrees with simulate_batches."""
    z1, z2, final, y, plan, profile, core = setup
    n = len(y)
    reqs = constant_workload(10.0, n, n)  # 100 ms spacing >> ~30 ms service
    rt = ServingRuntime(
        core, profile, plan, reqs,
        network=FixedRateNetwork(profile.uplink_bps),
        config=RuntimeConfig(max_batch=1),
    )
    tel = rt.run()
    assert len(tel.records) == n

    t_edge = L.edge_time(profile, 1)
    t_cloud = t_edge + L.comm_time(profile, 1) + L.cloud_time(profile, 1)
    for r in tel.records:
        expected = t_edge if r.on_device else t_cloud
        assert abs(r.latency_s - expected) < 1e-9

    # offload rate and accuracy match the offline simulator on these logits
    outs = simulate_batches(
        [z1], final, y, profile=profile, plan=plan, batch_size=n, branches=(1,)
    )
    assert len(outs) == 1
    assert tel.offload_rate == pytest.approx(1.0 - outs[0].on_device_frac, abs=0)
    assert tel.accuracy == pytest.approx(outs[0].accuracy, abs=0)
    # and per-request mean equals the simulator's mean batch time
    assert tel.latencies().mean() == pytest.approx(outs[0].time_s, rel=1e-9)


def test_runtime_deterministic(setup):
    z1, z2, final, y, plan, profile, core = setup
    def run():
        reqs = poisson_workload(50.0, 300, len(y), seed=4)
        net = MarkovNetwork(seed=3)
        rt = ServingRuntime(core, profile, plan, reqs, network=net,
                            config=RuntimeConfig(max_batch=4, batch_window_s=0.01))
        return rt.run().latencies()
    np.testing.assert_array_equal(run(), run())


# ----------------------------------------------------- queueing dynamics
def test_queueing_inflates_latency(setup):
    """Arrivals near the service rate queue up; the closed-form model
    cannot see this, the event simulator must."""
    z1, z2, final, y, plan, profile, core = setup
    t_edge = L.edge_time(profile, 1)
    slow = constant_workload(0.1 / t_edge, 200, len(y))
    fast = constant_workload(2.0 / t_edge, 200, len(y))  # 2x over capacity
    def p95(reqs):
        rt = ServingRuntime(core, profile, plan, reqs,
                            config=RuntimeConfig(max_batch=1))
        return rt.run().p95_s
    assert p95(fast) > 2 * p95(slow)


def test_multi_device_spreads_load(setup):
    z1, z2, final, y, plan, profile, core = setup
    t_edge = L.edge_time(profile, 1)
    reqs = constant_workload(3.0 / t_edge, 300, len(y), n_devices=4)
    def p95(n_dev):
        rt = ServingRuntime(core, profile, plan, reqs,
                            config=RuntimeConfig(n_devices=n_dev, max_batch=1))
        return rt.run().p95_s
    assert p95(4) < p95(1)


def test_microbatcher_coalesces(setup):
    """max_batch > 1 means fewer uplink transfers than offloaded samples."""
    z1, z2, final, y, plan, profile, core = setup
    reqs = poisson_workload(500.0, 400, len(y), seed=1)
    rt = ServingRuntime(core, profile, plan, reqs,
                        config=RuntimeConfig(max_batch=8, batch_window_s=0.05))
    tel = rt.run()
    offloaded = sum(not r.on_device for r in tel.records)
    assert offloaded > 0
    n_transfers = len(tel.bandwidth_samples)
    assert n_transfers < offloaded  # coalesced
    assert len(tel.records) == 400  # nobody lost in the batcher


def test_batch_window_flushes_partial_batch(setup):
    """A lone refused sample must not wait forever for batch-mates."""
    z1, z2, final, y, plan, profile, core = setup
    reqs = constant_workload(5.0, 40, len(y))
    rt = ServingRuntime(core, profile, plan, reqs,
                        config=RuntimeConfig(max_batch=64, batch_window_s=0.03))
    tel = rt.run()
    assert len(tel.records) == 40
    for r in tel.records:
        if not r.on_device:
            # waited at most the window + transfer + cloud service
            assert r.latency_s < 0.03 + 0.2


# -------------------------------------------------------------- workload
def test_workload_generators():
    reqs = poisson_workload(100.0, 50, 20, n_devices=3, deadline_s=0.1, seed=0)
    assert len(reqs) == 50
    arr = [r.arrival_s for r in reqs]
    assert arr == sorted(arr)
    assert [r.sample for r in reqs[:20]] == list(range(20))  # sequential pass
    assert {r.device for r in reqs} == {0, 1, 2}
    assert all(r.deadline_s == 0.1 for r in reqs)
    # same seed, same arrivals
    again = poisson_workload(100.0, 50, 20, n_devices=3, deadline_s=0.1, seed=0)
    assert [r.arrival_s for r in again] == arr

    tr = trace_workload([0.0, 0.5, 0.5, 1.0], 4)
    assert [r.arrival_s for r in tr] == [0.0, 0.5, 0.5, 1.0]
    with pytest.raises(ValueError):
        trace_workload([1.0, 0.5], 4)

    const = constant_workload(10.0, 5, 100, sample_order="random", seed=3)
    assert all(0 <= r.sample < 100 for r in const)


# ------------------------------------------------------------- telemetry
def test_telemetry_summary_json_safe(setup):
    z1, z2, final, y, plan, profile, core = setup
    reqs = poisson_workload(100.0, 128, len(y), deadline_s=0.05, seed=2)
    rt = ServingRuntime(core, profile, plan, reqs)
    tel = rt.run()
    s = tel.summary()
    json.dumps(s)  # must be serializable
    assert s["requests"] == 128
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    assert 0.0 <= s["offload_rate"] <= 1.0
    assert 0.0 <= s["deadline_miss_rate"] <= 1.0


def test_telemetry_windowed_estimates():
    tel = Telemetry()
    assert tel.bandwidth_estimate(1.0, now=10.0) is None
    tel.observe_bandwidth(9.5, 4e6)
    tel.observe_bandwidth(5.0, 20e6)  # outside the window
    assert tel.bandwidth_estimate(1.0, now=10.0) == pytest.approx(4e6)
    assert tel.bandwidth_estimate() == pytest.approx(12e6)  # all samples
    # empty window with older observations: most recent stale sample wins
    assert tel.bandwidth_estimate(1.0, now=20.0) == pytest.approx(4e6)
    assert tel.arrival_rate_estimate(1.0, now=10.0) is None
    for t in (9.2, 9.4, 9.6, 9.8, 4.0):
        tel.observe_arrival(t)
    assert tel.arrival_rate_estimate(1.0, now=10.0) == pytest.approx(4.0)


def test_windowed_estimators_future_only_fallback():
    """Regression (ISSUE 7 satellite): the stale-sample fallback must also
    cover observations that all post-date `now` -- a congested cell's
    in-flight transfers are priced at their FUTURE ready times, so a
    controller tick early in the run can find nothing at or before now.
    The documented contract is None only when nothing was ever observed."""
    from repro.core.control import windowed_mean

    # single future record, empty trailing window
    assert windowed_mean([7.0], [3e6], 0.5, now=1.0) == pytest.approx(3e6)
    # all future: the EARLIEST upcoming observation wins (nearest to now)
    assert windowed_mean([5.0, 9.0], [4e6, 2e6], 1.0, now=1.0) == (
        pytest.approx(4e6)
    )
    # mixed: the most recent PAST sample still beats any future one
    assert windowed_mean([0.5, 9.0], [5e6, 2e6], 1.0, now=2.0) == (
        pytest.approx(5e6)
    )
    # nothing ever observed stays None; queue contract keeps strict windows
    assert windowed_mean([], [], 1.0, now=1.0) is None
    assert windowed_mean([7.0], [3e6], 0.5, now=1.0,
                         stale_fallback=False) is None

    # the same guarantees through Telemetry's estimator surface
    tel = Telemetry()
    tel.observe_bandwidth(9.5, 4e6)  # future relative to now=1.0
    assert tel.bandwidth_estimate(1.0, now=1.0) == pytest.approx(4e6)
    # single-record window: that one sample IS the estimate
    assert tel.bandwidth_estimate(1.0, now=9.6) == pytest.approx(4e6)


# ------------------------------------------------------- plan re-scoring
def test_rescore_plan_switches_under_bad_link(setup):
    """Under a starved uplink the small-payload, rarely-offloading deep
    exit must win; under the nominal link the shallow exit is fine."""
    z1, z2, final, y, plan, profile, core = setup
    kw = dict(
        edge_times_s=[L.edge_time(profile, 1), L.edge_time(profile, 2)],
        cloud_times_s=[L.cloud_time(profile, 1), L.cloud_time(profile, 2)],
        payload_bytes=[payload_bytes(1), payload_bytes(2)],
        labels=y,
        final_logits=final,
        min_accuracy=0.9,
    )
    fast, _ = rescore_plan(plan, [z1, z2], uplink_bps=1e9, **kw)
    slow, table = rescore_plan(plan, [z1, z2], uplink_bps=1e5, **kw)
    assert fast.exit_index == 0  # cheap shallow exit when comm is free
    assert slow.exit_index == 1  # small payload when comm dominates
    assert all(
        r["accuracy"] is not None and 0 <= r["accuracy"] <= 1 for r in table
    )
    # calibrators are re-used, never re-fit
    assert slow.calibrators is not plan.calibrators
    assert slow.temperatures == plan.temperatures


def test_rescore_plan_accuracy_floor(setup):
    """Infeasible floor: fall back to the most accurate candidate rather
    than the fastest."""
    z1, z2, final, y, plan, profile, core = setup
    best, _ = rescore_plan(
        plan, [z1, z2],
        edge_times_s=[1e-3, 2e-3],
        cloud_times_s=[5e-3, 4e-3],
        payload_bytes=[payload_bytes(1), payload_bytes(2)],
        uplink_bps=1e9,
        labels=y,
        final_logits=final,
        p_tar_grid=[0.0, 0.8],  # p_tar=0 exits everything on-device (fast)
        min_accuracy=1.1,  # impossible
    )
    # most accurate candidate keeps the strict gate, not the p_tar=0 one
    assert best.p_tar == 0.8


def test_plan_with_p_tar_keeps_calibration(setup):
    z1, z2, final, y, plan, profile, core = setup
    moved = plan.with_p_tar(0.6)
    assert moved.p_tar == 0.6
    assert moved.temperatures == plan.temperatures
    assert moved.exit_index == plan.exit_index
    rt = OffloadPlan.from_json(moved.to_json())
    assert rt.p_tar == 0.6


def test_rescore_plan_argument_validation(setup):
    z1, z2, final, y, plan, profile, core = setup
    kw = dict(
        edge_times_s=[1e-3, 2e-3], cloud_times_s=[5e-3, 4e-3],
        payload_bytes=[payload_bytes(1), payload_bytes(2)], uplink_bps=1e7,
    )
    with pytest.raises(ValueError):  # accuracy floor needs the data to score it
        rescore_plan(plan, [z1, z2], min_accuracy=0.9, **kw)
    entropy_plan = OffloadPlan(
        p_tar=0.8, calibrators=list(plan.calibrators),
        criterion="entropy", entropy_threshold=0.5,
    )
    with pytest.raises(ValueError):  # p_tar re-scoring is confidence-only
        rescore_plan(entropy_plan, [z1, z2], **kw)


def test_rescore_plan_partition_layer_not_stale(setup):
    """Switching exits without exit_layer_indices must clear the recorded
    partition layer rather than keep the old exit's."""
    z1, z2, final, y, plan, profile, core = setup
    src = plan.with_partition(0, 7)
    moved, _ = rescore_plan(
        src, [z1, z2],
        edge_times_s=[L.edge_time(profile, 1), L.edge_time(profile, 2)],
        cloud_times_s=[L.cloud_time(profile, 1), L.cloud_time(profile, 2)],
        payload_bytes=[payload_bytes(1), payload_bytes(2)],
        uplink_bps=1e5,  # starved link: exit 1 wins (smaller payload)
    )
    assert moved.exit_index == 1
    assert moved.partition_layer is None
    kept, _ = rescore_plan(
        src, [z1, z2],
        edge_times_s=[L.edge_time(profile, 1), L.edge_time(profile, 2)],
        cloud_times_s=[L.cloud_time(profile, 1), L.cloud_time(profile, 2)],
        payload_bytes=[payload_bytes(1), payload_bytes(2)],
        uplink_bps=1e5,
        exit_layer_indices=[0, 1],
    )
    assert kept.partition_layer == 1


def test_logits_core_entropy_criterion():
    """LogitsCore honors the plan's entropy criterion (BranchyNet rule)."""
    z1, z2, final, y = _synthetic_logits(n=256)
    plan = OffloadPlan(
        p_tar=0.8,
        calibrators=[TemperatureScaling.from_temperature(1.0)],
        criterion="entropy",
        entropy_threshold=0.5,
    )
    core = LogitsCore({1: z1}, final, plan, labels=y)
    from repro.core.exits import apply_gate

    expected = np.asarray(
        apply_gate(jnp.asarray(z1), 0.8, criterion="entropy",
                   entropy_threshold=0.5).exit_mask
    )
    got = np.array([core.gate(i, 1, 0.8)[0] for i in range(len(y))])
    np.testing.assert_array_equal(got, expected)
    with pytest.raises(ValueError):  # threshold is mandatory for entropy
        LogitsCore({1: z1}, final,
                   OffloadPlan(p_tar=0.8, calibrators=list(plan.calibrators),
                               criterion="entropy"))


def test_runtime_rejects_controller_core_mismatch(setup):
    """A controller that may deploy a branch the core cannot serve must be
    rejected at construction, not silently desynchronize later."""
    z1, z2, final, y, plan, profile, _ = setup
    one_branch_core = LogitsCore({1: z1}, final, plan, labels=y)
    controller = OnlineController(
        plan, profile, {1: z1, 2: z2}, final_logits=final, labels=y,
    )
    reqs = constant_workload(10.0, 10, len(y))
    with pytest.raises(ValueError):
        ServingRuntime(one_branch_core, profile, plan, reqs,
                       controller=controller)


# ---------------------------------------------- controller under congestion
def _congestion_scenario(setup, with_controller):
    """The ISSUE 2 acceptance scenario -- shared verbatim with the
    CI-asserted benchmark via repro.serving.scenarios."""
    from repro.serving.scenarios import run_congested_markov

    z1, z2, final, y, plan, profile, core = setup
    return run_congested_markov(
        plan, {1: z1, 2: z2}, final, y,
        with_controller=with_controller, profile=profile,
    )


def test_controller_beats_static_under_congestion(setup):
    """The acceptance scenario: on a congested Markov link the online
    controller (re-scoring the SAME calibrators) must cut tail latency
    without giving up accuracy."""
    static = _congestion_scenario(setup, with_controller=False)
    ctrl = _congestion_scenario(setup, with_controller=True)
    assert len(ctrl.controller_events) > 0  # it actually acted
    assert ctrl.p99_s < 0.8 * static.p99_s
    assert ctrl.deadline_miss_rate <= static.deadline_miss_rate
    assert ctrl.accuracy >= static.accuracy - 0.01


def test_controller_settles_on_fixed_link(setup):
    """On a constant link the controller must converge: at most one initial
    re-selection, then hysteresis holds the configuration (controller
    events only fire on change, so settling == at most one event)."""
    z1, z2, final, y, plan, profile, core = setup
    reqs = constant_workload(10.0, 200, len(y))
    controller = OnlineController(
        plan, profile, {1: z1, 2: z2}, final_logits=final, labels=y,
        config=ControllerConfig(interval_s=1.0, window_s=2.0, min_accuracy=0.9),
    )
    rt = ServingRuntime(core, profile, plan, reqs,
                        network=FixedRateNetwork(profile.uplink_bps),
                        config=RuntimeConfig(max_batch=1),
                        controller=controller)
    tel = rt.run()
    assert len(tel.controller_events) <= 1


# ------------------------------------------- serve steps consume the plan
def test_serve_steps_accept_plan():
    """launch/serve.py gates with the plan's calibrators; the legacy
    temperatures kwarg remains as a shim and must agree for scalar-T
    plans."""
    from repro.configs import get_smoke
    from repro.launch.serve import make_prefill_step, make_serve_step
    from repro.models import registry

    cfg = get_smoke("qwen3-8b")
    n_exits = len(cfg.exit_layers)
    plan = OffloadPlan(
        p_tar=0.5,
        calibrators=[TemperatureScaling.from_temperature(1.7)] * n_exits,
    )
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}

    out_plan = make_prefill_step(cfg, plan=plan)(params, batch)
    out_temp = make_prefill_step(cfg, temperatures=[1.7] * n_exits)(params, batch)
    np.testing.assert_array_equal(
        np.asarray(out_plan["exit_confidence"]),
        np.asarray(out_temp["exit_confidence"]),
    )
    np.testing.assert_array_equal(
        np.asarray(out_plan["exit_prediction"]),
        np.asarray(out_temp["exit_prediction"]),
    )

    caches = registry.init_cache(cfg, 2, 32)
    step = make_serve_step(cfg, plan=plan)
    tok = jnp.ones((2, 1), jnp.int32)
    out, _ = step(params, tok, caches, jnp.int32(1))
    assert out["exit_confidence"].shape[0] == n_exits

    with pytest.raises(ValueError):
        make_prefill_step(cfg, plan=plan, temperatures=[1.0] * n_exits)
    bad = OffloadPlan(
        p_tar=0.5,
        calibrators=[TemperatureScaling.from_temperature(1.0)] * (n_exits + 1),
    )
    with pytest.raises(ValueError):
        make_serve_step(cfg, plan=bad)


# --------------------------------------------------- engine-backed core
def test_engine_core_matches_logits_core(setup):
    """The runtime driving real jitted partitions (EngineCore) must agree
    with the precomputed-logits core on decisions and predictions."""
    from repro.offload.engine import convnet_engine
    from repro.models import convnet
    from repro.serving.runtime import EngineCore

    n = 32
    rng = np.random.default_rng(0)
    images = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 10, n)
    params = convnet.init_params(jax.random.PRNGKey(0))
    plan = OffloadPlan(
        p_tar=0.5, calibrators=[TemperatureScaling.from_temperature(1.0)]
    )
    profile = L.paper_2020()

    engine = convnet_engine(params, plan, branch=1)
    ecore = EngineCore({1: engine}, {"images": jnp.asarray(images)}, labels=labels)

    logits, _ = convnet.edge_forward(params, jnp.asarray(images), branch=1)
    final = convnet.forward(params, jnp.asarray(images))["logits"]
    lcore = LogitsCore({1: np.asarray(logits)}, np.asarray(final), plan,
                       labels=labels)

    reqs = constant_workload(10.0, n, n)
    t_e = ServingRuntime(ecore, profile, plan, reqs,
                         config=RuntimeConfig(max_batch=1)).run()
    t_l = ServingRuntime(lcore, profile, plan, reqs,
                         config=RuntimeConfig(max_batch=1)).run()
    by_id = lambda tel: {r.req_id: r for r in tel.records}
    e, l = by_id(t_e), by_id(t_l)
    assert set(e) == set(l)
    for rid in e:
        assert e[rid].on_device == l[rid].on_device
        assert e[rid].correct == l[rid].correct
        assert e[rid].latency_s == pytest.approx(l[rid].latency_s, rel=1e-12)
    # the engine's counters saw every edge call
    assert engine.stats.edge_calls == n
    assert engine.stats.edge_time_s > 0
    assert 0 < engine.stats.edge_dispatch_s <= engine.stats.edge_time_s
