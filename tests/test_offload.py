"""Offload engine + simulator + partition optimizer integration tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import OffloadPolicy, make_policy
from repro.data.synthetic import cifar_like
from repro.models import convnet
from repro.offload import latency as L
from repro.offload.engine import convnet_engine
from repro.offload.simulator import (
    missed_deadline_curve,
    missed_deadline_probability,
    simulate_batches,
)


@pytest.fixture(scope="module")
def setup():
    data = cifar_like(n_train=64, n_val=512, n_test=1024, seed=3)
    params = convnet.init_params(jax.random.PRNGKey(0))
    return data, params


def test_engine_routes_by_confidence(setup):
    data, params = setup
    policy = OffloadPolicy(p_tar=0.5, temperatures=[1.0])
    engine = convnet_engine(params, policy, branch=1)
    out = engine.infer({"images": jnp.asarray(data.test_x[:256])})
    assert out["prediction"].shape == (256,)
    assert engine.stats.requests == 256
    assert engine.stats.on_device + engine.stats.offloaded == 256
    # engine prediction must agree with running the branches manually
    logits, hidden = convnet.edge_forward(params, jnp.asarray(data.test_x[:256]), 1)
    conf = np.asarray(jax.nn.softmax(logits, -1).max(-1))
    np.testing.assert_array_equal(np.asarray(out["on_device"]), conf >= 0.5)


def test_engine_cloud_equals_full_model(setup):
    """Offloaded samples must get EXACTLY the full model's prediction
    (partitioned execution is numerically the unpartitioned model)."""
    data, params = setup
    x = jnp.asarray(data.test_x[:128])
    policy = OffloadPolicy(p_tar=1.1, temperatures=[1.0])  # force offload all
    engine = convnet_engine(params, policy, branch=1)
    out = engine.infer({"images": x})
    assert engine.stats.offloaded == 128
    full = convnet.forward(params, x)
    np.testing.assert_array_equal(
        out["prediction"], np.asarray(jnp.argmax(full["logits"], -1))
    )


def test_engine_all_on_device(setup):
    data, params = setup
    policy = OffloadPolicy(p_tar=0.0, temperatures=[1.0])
    engine = convnet_engine(params, policy, branch=1)
    out = engine.infer({"images": jnp.asarray(data.test_x[:64])})
    assert engine.stats.offloaded == 0
    assert engine.stats.payload_bytes == 0


def test_simulator_latency_accounting():
    """Hand-built logits: half supremely confident, half uniform."""
    n, c = 1024, 10
    z_conf = np.zeros((n, c), np.float32)
    z_conf[: n // 2, 0] = 100.0  # first half exits on device
    final = np.zeros((n, c), np.float32)
    final[:, 1] = 100.0
    labels = np.concatenate(
        [np.zeros(n // 2, np.int64), np.ones(n // 2, np.int64)]
    )
    prof = L.paper_2020()
    outs = simulate_batches([z_conf], final, labels, 0.9, [1.0], prof, batch_size=256)
    t_dev = L.edge_time(prof, 1)
    t_cloud = t_dev + L.comm_time(prof, 1) + L.cloud_time(prof, 1)
    for o in outs:
        assert o.accuracy == 1.0  # device half correct cls 0, cloud half cls 1
        assert t_dev <= o.time_s <= t_cloud
    # batches are ordered: first two all-device, last two all-cloud
    np.testing.assert_allclose(outs[0].time_s, t_dev, rtol=1e-6)
    np.testing.assert_allclose(outs[-1].time_s, t_cloud, rtol=1e-6)


def test_simulator_includes_tail_batch():
    """n not divisible by batch_size: the final partial batch must be
    simulated (the old code silently dropped it); drop_last=True restores
    the truncating behavior."""
    n, c = 1000, 10  # 1000 = 3*256 + 232
    z = np.zeros((n, c), np.float32)
    z[:, 0] = 100.0  # everyone exits on device
    final = np.zeros((n, c), np.float32)
    labels = np.zeros(n, np.int64)
    prof = L.paper_2020()
    outs = simulate_batches([z], final, labels, 0.9, [1.0], prof, batch_size=256)
    assert len(outs) == 4  # 3 full + 1 tail of 232
    assert all(o.accuracy == 1.0 and o.on_device_frac == 1.0 for o in outs)
    trunc = simulate_batches(
        [z], final, labels, 0.9, [1.0], prof, batch_size=256, drop_last=True
    )
    assert len(trunc) == 3
    assert [o.time_s for o in trunc] == [o.time_s for o in outs[:3]]


def test_simulator_network_repricing():
    """A time-varying network changes ONLY the comm component, per batch."""
    from repro.serving.network import FixedRateNetwork, TraceNetwork

    n, c = 512, 10
    z = np.zeros((n, c), np.float32)  # uniform logits: everyone offloads
    final = np.zeros((n, c), np.float32)
    final[:, 0] = 100.0
    labels = np.zeros(n, np.int64)
    prof = L.paper_2020()
    base = simulate_batches([z], final, labels, 0.9, [1.0], prof, batch_size=256)
    fixed = simulate_batches(
        [z], final, labels, 0.9, [1.0], prof, batch_size=256,
        network=FixedRateNetwork(prof.uplink_bps),
    )
    assert [o.time_s for o in fixed] == [o.time_s for o in base]
    halved = TraceNetwork([0.0, 1.0], [prof.uplink_bps, prof.uplink_bps / 2])
    slow = simulate_batches(
        [z], final, labels, 0.9, [1.0], prof, batch_size=256,
        network=halved, batch_times_s=[0.0, 2.0],
    )
    assert slow[0].time_s == pytest.approx(base[0].time_s)
    assert slow[1].time_s == pytest.approx(
        base[1].time_s + L.comm_time(prof, 1)
    )
    with pytest.raises(ValueError):  # one timestamp per simulated batch
        simulate_batches(
            [z], final, labels, 0.9, [1.0], prof, batch_size=256,
            network=halved, batch_times_s=[0.0],
        )


def _stub_engine(exit_logits, level=0):
    """An OffloadEngine over host stubs: 4 rows, 256-wide payloads."""
    from repro.core.calibration import TemperatureScaling
    from repro.core.policy import OffloadPlan
    from repro.offload.engine import OffloadEngine

    plan = OffloadPlan(p_tar=0.5,
                       calibrators=[TemperatureScaling.from_temperature(1.0)])
    return OffloadEngine(
        edge_fn=lambda b: {"exit_logits": exit_logits,
                           "payload": np.ones((4, 256), np.float32)},
        cloud_fn=lambda p: {"logits": np.ones((p.shape[0], 10), np.float32)},
        plan=plan.with_compression(level),
    )


#: the EngineStats fields that tile an `infer` call
TILING = ("edge_time_s", "cloud_time_s", "gate_time_s", "gate_sync_time_s",
          "gather_time_s", "codec_time_s", "fetch_time_s")


@pytest.mark.parametrize("level", [0, 1])
def test_engine_timing_hooks(monkeypatch, level):
    """The phase counters tile `infer`: with the engine's clock stubbed to
    a fixed sequence, the seven tiling fields sum exactly to the time from
    the call's first stamp to its last, each phase gets its own interval,
    and a batch with no refused row adds nothing to the offload phases."""
    import types

    from repro.offload import engine as engine_mod

    stamps = []

    def clock():  # 0, 1, 3, 6, 10, ...: phase k lasts k + 1
        stamps.append(stamps[-1] + len(stamps) if stamps else 0.0)
        return stamps[-1]

    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(perf_counter=clock))
    # uniform logits: every row is refused
    engine = _stub_engine(np.zeros((4, 10), np.float32), level)
    out = engine.infer({"x": None})
    st = engine.stats
    assert out["prediction"].shape == (4,)
    assert st.edge_calls == 1 and st.cloud_calls == 1 and st.offloaded == 4
    assert sum(getattr(st, f) for f in TILING) == stamps[-1] - stamps[0]
    # edge: dispatch 1 + wait 2; gate 3; gate sync 4; gather 5; then
    # encode 6 + decode 7 where the plan has a codec; cloud 2 phases; fetch
    codec = 6 + 7 if level else 0
    cloud = 8 + 9 if level else 6 + 7
    assert (st.edge_dispatch_s, st.edge_time_s) == (1, 3)
    assert (st.gate_time_s, st.gate_sync_time_s, st.gather_time_s) == (3, 4, 5)
    assert st.codec_time_s == codec
    assert st.cloud_time_s == cloud
    assert st.fetch_time_s == (10 if level else 8)

    # confident logits: every row exits on the edge
    stamps.clear()
    confident = np.zeros((4, 10), np.float32)
    confident[:, 3] = 20.0
    engine = _stub_engine(confident, level)
    engine.infer({"x": None})
    st = engine.stats
    assert st.cloud_calls == 0 and st.offloaded == 0
    assert st.gather_time_s == st.codec_time_s == st.fetch_time_s == 0.0
    assert sum(getattr(st, f) for f in TILING) == stamps[-1] - stamps[0]
    assert st.edge_dispatch_s <= st.edge_time_s


def test_engine_spans_land_in_a_profiler_trace(tmp_path):
    """Under a profiler session with the host tracer on, every phase of
    `infer` is an `offload.*` span of the host plane, inside `offload.infer`,
    which carries the batch's sequence number, rows and refused rows."""
    from jax.profiler import ProfileData, ProfileOptions

    engine = _stub_engine(np.zeros((4, 10), np.float32), level=1)
    engine.infer({"x": None})  # compile outside the trace
    opts = ProfileOptions()
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        engine.infer({"x": None})
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith("offload.")]
    (parent,) = [e for e in events if e[0] == "offload.infer"]
    assert parent[3] == {"batch": 1, "rows": 4, "refused": 4}
    names = {"offload.edge", "offload.edge_wait", "offload.gate", "offload.gate_sync",
             "offload.gather", "offload.encode", "offload.decode", "offload.cloud",
             "offload.cloud_wait", "offload.fetch"}
    children = [e for e in events if e[0] != "offload.infer"]
    assert {e[0] for e in children} == names
    assert all(parent[1] <= e[1] <= e[2] <= parent[2] for e in children)
    inside = {"offload.edge_wait": "offload.edge", "offload.cloud_wait": "offload.cloud"}
    for child, outer in inside.items():
        (c,), (o,) = ([e for e in children if e[0] == n] for n in (child, outer))
        assert o[1] <= c[1] <= c[2] <= o[2]


def _plan(kind, criterion="confidence", p_tar=0.5, entropy_threshold=None):
    """A two-exit plan whose branch 1 carries a `kind` calibrator."""
    from repro.core.calibration import CalibratorState, TemperatureScaling
    from repro.core.policy import OffloadPlan

    deployed = {
        "temperature": TemperatureScaling.from_temperature(1.7),
        "vector": CalibratorState("vector", {
            "w": jnp.linspace(0.5, 1.5, 10, dtype=jnp.float32),
            "b": jnp.linspace(-0.3, 0.3, 10, dtype=jnp.float32)}),
        "identity": CalibratorState("identity", {}),
    }[kind]
    return OffloadPlan(p_tar=p_tar, criterion=criterion,
                       entropy_threshold=entropy_threshold,
                       calibrators=[TemperatureScaling.from_temperature(9.0), deployed])


def _eager_gate(plan, logits, use_kernel):
    """`OffloadPlan.gate` of branch 1 called eagerly, as host arrays."""
    g = plan.gate(jnp.asarray(logits), branch=1, use_kernel=use_kernel)
    return tuple(np.asarray(a) for a in (g.exit_mask, g.prediction, g.confidence,
                                         g.entropy))


def _recording_engine(plan, logits, payload, use_kernel):
    """An engine over branch 1 whose cloud partition keeps what it is sent."""
    from repro.offload.engine import OffloadEngine

    sent = []

    def cloud(p):
        sent.append(p)
        return {"logits": jnp.zeros((jax.tree.leaves(p)[0].shape[0], 10))}

    edge_out = {"exit_logits": jnp.asarray(logits),
                "payload": jax.tree.map(jnp.asarray, payload)}
    engine = OffloadEngine(lambda b: edge_out, cloud, plan, branch=1,
                           use_kernel=use_kernel)
    return engine, sent


def _logits(rows=64, seed=7):
    return (np.random.default_rng(seed).normal(size=(rows, 10)) * 2.5).astype(np.float32)


#: (calibrator, criterion, use_kernel, payload) of each parity case
PARITY = {
    "kernel-temperature-confidence": ("temperature", "confidence", True, "image"),
    "kernel-temperature-entropy": ("temperature", "entropy", True, "image"),
    "kernel-vector-confidence": ("vector", "confidence", True, "image"),
    "kernel-identity-confidence": ("identity", "confidence", True, "lm"),
    "jnp-temperature-confidence": ("temperature", "confidence", False, "image"),
    "jnp-vector-entropy": ("vector", "entropy", False, "image"),
    "jnp-temperature-lm": ("temperature", "confidence", False, "lm"),
}


@pytest.mark.parametrize("case", list(PARITY))
def test_engine_gate_and_gather_match_the_eager_path(case):
    """`infer` answers as eager `plan.gate` followed by an eager `x[idx]`:
    the same mask, predictions and edge confidences, and the cloud is sent
    exactly the eagerly gathered rows of every payload leaf. Confidences
    are bit for bit where the kernel reads the raw logits (a temperature or
    identity calibrator); where XLA computes them, or the calibrated logits
    the kernel reads, one program may round differently from op-by-op
    dispatch (a fused multiply-add), within 2 ulp."""
    kind, criterion, use_kernel, shape = PARITY[case]
    logits = _logits()
    rows = len(logits)
    rng = np.random.default_rng(3)
    payload = ({"h": rng.normal(size=(rows, 4, 4, 8)).astype(np.float32)}
               if shape == "image" else
               {"hidden": rng.normal(size=(rows, 6, 16)).astype(np.float32),
                "pos": np.tile(np.arange(6, dtype=np.int32), (rows, 1))})
    _, _, conf, ent = _eager_gate(_plan(kind), logits, use_kernel)
    plan = _plan(kind, criterion, p_tar=float(np.quantile(conf, 0.4)),
                 entropy_threshold=float(np.quantile(ent, 0.6)))
    mask, pred, conf, _ = _eager_gate(plan, logits, use_kernel)
    assert 0 < mask.sum() < rows

    engine, sent = _recording_engine(plan, logits, payload, use_kernel)
    out = engine.infer({})
    np.testing.assert_array_equal(out["on_device"], mask)
    np.testing.assert_array_equal(out["prediction"][mask], pred[mask])
    if use_kernel and kind != "vector":
        np.testing.assert_array_equal(out["confidence"][mask].view(np.uint32),
                                      conf[mask].view(np.uint32))
    else:
        np.testing.assert_array_max_ulp(out["confidence"][mask], conf[mask], maxulp=2)
    idx = np.nonzero(~mask)[0]
    (got,) = sent
    want = jax.tree.map(lambda x: np.asarray(jnp.asarray(x)[idx]), payload)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g).view(np.uint32), w.view(np.uint32))


@pytest.mark.parametrize("step", [0, 1, -1])
def test_engine_gate_compares_in_float32_at_a_rows_confidence(step):
    """With `p_tar` at a row's float32 confidence, or at either float64
    neighbour of it, the compiled gate decides as the eager one: the
    threshold is compared in float32, so the row exits in all three."""
    logits = _logits()
    _, _, conf, _ = _eager_gate(_plan("temperature"), logits, use_kernel=True)
    row = int(np.argsort(conf)[len(conf) // 2])
    at = float(conf[row])
    p_tar = at if step == 0 else float(np.nextafter(at, step * np.inf))
    assert (p_tar == at) is (step == 0)
    plan = _plan("temperature", p_tar=p_tar)
    mask, pred, conf, _ = _eager_gate(plan, logits, use_kernel=True)
    engine, _ = _recording_engine(plan, logits, np.zeros((len(logits), 8), np.float32),
                                  use_kernel=True)
    out = engine.infer({})
    assert mask[row] and out["on_device"][row]
    np.testing.assert_array_equal(out["on_device"], mask)
    np.testing.assert_array_equal(out["prediction"][mask], pred[mask])
    np.testing.assert_array_equal(out["confidence"][mask].view(np.uint32),
                                  conf[mask].view(np.uint32))


def test_engine_counts_host_fetches():
    """One fetch brings the gate's three outputs to the host; a batch with
    refused rows makes one more, for the cloud's logits."""
    refused = _stub_engine(np.zeros((4, 10), np.float32))
    confident = np.zeros((4, 10), np.float32)
    confident[:, 3] = 20.0
    on_device = _stub_engine(confident)
    for calls in (1, 2):
        refused.infer({})
        on_device.infer({})
        assert refused.stats.host_fetches == 2 * calls
        assert on_device.stats.host_fetches == calls


class _Compiles:
    """The names of the programs compiled inside the block (JAX's
    monitoring events, as the chip benchmark's CompileCounter counts)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax.monitoring

        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self.names

    def _on(self, event, duration, fun_name="", **_):
        if event == self.EVENT:
            self.names.append(fun_name)

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


def test_engine_compiles_nothing_again_for_a_count_or_a_threshold():
    """A second call at the same refused count compiles no program, and a
    new threshold (`with_p_tar`) moves the gate without a new gate program."""
    logits = _logits(rows=16, seed=11)
    plan = _plan("temperature")
    _, _, conf, _ = _eager_gate(plan, logits, use_kernel=True)
    plan = plan.with_p_tar(float(np.quantile(conf, 0.3)))
    engine, _ = _recording_engine(plan, logits, np.ones((16, 8), np.float32),
                                  use_kernel=True)
    first = engine.infer({})["on_device"]
    with _Compiles() as names:
        again = engine.infer({})["on_device"]
    assert names == []
    np.testing.assert_array_equal(again, first)
    engine.plan = engine.plan.with_p_tar(float(np.quantile(conf, 0.7)))
    with _Compiles() as names:
        moved = engine.infer({})["on_device"]
    assert moved.sum() < first.sum()
    assert not [n for n in names if "exit_gate" in n], names


def test_engine_gate_program_is_the_one_the_gate_roofline_reads():
    """The chip benchmark times the gate by the program name its
    `exit_gate_roofline` reader matches and the codec by `codec_roofline`'s:
    the engine's gate program matches the first alone, and its row gather
    neither."""
    import re
    from pathlib import Path

    from repro.offload.engine import _exit_gate_step, _take_rows

    metrics = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "metrics"

    def pattern(metric):
        text = (metrics / f"{metric}.py").read_text()
        return re.search(r'^PROGRAM = r"(.+)"$', text, re.M).group(1)

    def module(lowered):
        return re.match(r"HloModule (\S+?),", lowered.compile().as_text()).group(1)

    gate_rx, codec_rx = pattern("exit_gate_roofline"), pattern("codec_roofline")
    plan = _plan("temperature")
    gate = module(_exit_gate_step.lower(
        jnp.zeros((256, 10)), plan.calibrators[1], plan.p_tar, None,
        criterion="confidence", use_kernel=True))
    take = module(_take_rows.lower(jnp.zeros((256, 16, 16, 64)),
                                   np.arange(26, dtype=np.int32)))
    assert re.search(gate_rx, gate) and not re.search(codec_rx, gate), gate
    assert not re.search(gate_rx, take) and not re.search(codec_rx, take), take


def test_missed_deadline_monotone_in_t_tar():
    n, c = 2048, 10
    rng = np.random.default_rng(0)
    z = rng.normal(size=(n, c)).astype(np.float32) * 3
    final = rng.normal(size=(n, c)).astype(np.float32) * 3
    labels = rng.integers(0, c, n)
    prof = L.paper_2020()
    outs = simulate_batches([z], final, labels, 0.5, [1.0], prof)
    ts = [1e-4, 1e-3, 1e-2, 1e-1]
    curve = missed_deadline_curve(outs, ts, 0.0)  # p_tar=0: latency-only
    assert all(a >= b for a, b in zip(curve, curve[1:]))  # non-increasing
    assert curve[-1] == 0.0  # huge deadline always met (accuracy ignored)


def test_partition_optimizer_prefers_cheap_exit():
    from repro.core.partition import choose_partition

    rng = np.random.default_rng(1)
    # exit0 confident (cheap, rarely offloads); exit1 unconfident
    z0 = np.zeros((512, 10), np.float32)
    z0[:, 0] = 20.0
    z1 = rng.normal(size=(512, 10)).astype(np.float32) * 0.01
    cands = choose_partition(
        [z0, z1],
        temperatures=[1.0, 1.0],
        p_tar=0.8,
        edge_times_s=[1e-3, 2e-3],
        cloud_times_s=[5e-3, 4e-3],
        payload_bytes=[65536, 24576],
        exit_layer_indices=[0, 1],
        uplink_bps=18.8e6,
    )
    assert cands[0].exit_index == 0
    assert cands[0].offload_prob < 0.01
    assert cands[1].offload_prob > 0.9
