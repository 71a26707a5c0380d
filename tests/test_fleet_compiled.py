"""Compiled fleet pipeline parity tests (ISSUE 8 tentpole).

`repro.fleet.compiled.CompiledFleetSimulator` runs the whole window
pipeline -- gate -> per-device FIFO edge queues -> per-cell uplink ->
shared cloud tier -- as jitted JAX stages (max-plus
`associative_scan` recurrences, `shard_map` over the cell axis). The
host numpy `FleetSimulator` is the spec: these tests pin per-request
parity to float round-off on `reference_fleet`, identical churn
shed/backhaul accounting and orchestration events, and the declared
scope limits (static deployments only: no controller, no rollouts).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.offload import latency as L
from repro.orchestration import ChurnSchedule, Orchestrator
from repro.orchestration.qos import CellSLO, QoSConfig, QoSMonitor
from repro.serving.scenarios import fit_drift_plans, synthetic_distorted_cascade
from repro.fleet.scenarios import fleet_gate_table, reference_fleet, run_fleet

LAT_TOL = dict(rtol=1e-9, atol=1e-12)


@pytest.fixture(scope="module")
def drift_data():
    val, test = synthetic_distorted_cascade(
        directions={"gaussian_blur": "under"}
    )
    return val, test, fit_drift_plans(val)


@pytest.fixture(scope="module")
def scenario(drift_data):
    val, test, _ = drift_data
    return reference_fleet(n_cells=6, requests_per_cell=200, seed=0,
                           val=val, test=test, cloud_servers=2)


def assert_per_request_parity(a, b):
    """Every per-cell telemetry column matches: int/bool columns exactly,
    latencies to float round-off (tree-scan vs sequential rounding)."""
    assert a.n_cells == b.n_cells
    for c in range(a.n_cells):
        ca, cb = a._cells[c], b._cells[c]
        assert len(ca) == len(cb)
        for f in ca.FIELDS:
            va, vb = ca.column(f), cb.column(f)
            if f == "latency_s":
                np.testing.assert_allclose(vb, va, **LAT_TOL)
            else:
                np.testing.assert_array_equal(vb, va)


def assert_summary_parity(a, b):
    sa, sb = a.fleet_summary(), b.fleet_summary()
    assert set(sa) == set(sb)
    for k in sa:
        np.testing.assert_allclose(sb[k], sa[k], **LAT_TOL)


# ------------------------------------------------------------ plain parity
def test_compiled_per_request_parity(drift_data, scenario):
    val, test, (uncal, global_plan, bank) = drift_data
    a = run_fleet(bank, scenario)
    b = run_fleet(bank, scenario, backend="compiled")
    assert_per_request_parity(a, b)
    assert_summary_parity(a, b)


def test_compiled_parity_plain_plan(drift_data, scenario):
    """The non-bank path (single plan, static context) also matches."""
    val, test, (uncal, global_plan, bank) = drift_data
    a = run_fleet(global_plan, scenario)
    b = run_fleet(global_plan, scenario, backend="compiled")
    assert_per_request_parity(a, b)
    assert_summary_parity(a, b)


# ------------------------------------------------------------ churn parity
def test_compiled_churn_shed_parity(drift_data, scenario):
    """Outage with live neighbors: shed arrivals land on the same serving
    cells with identical latencies and orchestration events."""
    val, test, (uncal, global_plan, bank) = drift_data
    churn = ChurnSchedule.outage([0, 2], start_s=2.0, duration_s=2.0)
    a = run_fleet(bank, scenario, orchestrator=Orchestrator(churn=churn))
    b = run_fleet(bank, scenario, orchestrator=Orchestrator(churn=churn),
                  backend="compiled")
    assert a.orchestration_events == b.orchestration_events
    assert_per_request_parity(a, b)
    assert_summary_parity(a, b)


def test_compiled_backhaul_parity(drift_data, scenario):
    """Whole-fleet outage: every arrival rides the backhaul to the cloud
    on both backends, request conservation included."""
    val, test, (uncal, global_plan, bank) = drift_data
    cells = list(range(scenario.topology.n_cells))
    churn = ChurnSchedule.outage(cells, start_s=1.0, duration_s=2.0)
    a = run_fleet(bank, scenario, orchestrator=Orchestrator(churn=churn))
    b = run_fleet(bank, scenario, orchestrator=Orchestrator(churn=churn),
                  backend="compiled")
    assert a.orchestration_events == b.orchestration_events
    assert a.fleet_summary()["requests"] == b.fleet_summary()["requests"]
    assert_per_request_parity(a, b)
    assert_summary_parity(a, b)


def test_compiled_qos_monitor_parity(drift_data, scenario):
    """The compiled run drives the QoS monitor through the same live
    telemetry views: identical trip/clear events."""
    val, test, (uncal, global_plan, bank) = drift_data

    def orch():
        return Orchestrator(monitor=QoSMonitor(
            CellSLO(p99_ms=1e-3, min_requests=1),
            QoSConfig(window_s=2.0, trip_after=1, clear_after=1000),
        ))

    a = run_fleet(bank, scenario, orchestrator=orch())
    b = run_fleet(bank, scenario, orchestrator=orch(), backend="compiled")
    trips = [k for _, k, _ in a.orchestration_events]
    assert "qos_trip" in trips  # the SLO is designed to trip
    assert a.orchestration_events == b.orchestration_events
    assert_per_request_parity(a, b)


# ------------------------------------------------------------- scope limits
def test_compiled_rejects_controller(drift_data, scenario):
    val, test, (uncal, global_plan, bank) = drift_data
    with pytest.raises(ValueError, match="host backend"):
        run_fleet(bank, scenario, with_controller=True, backend="compiled")


def test_compiled_rejects_rollout(drift_data, scenario):
    from repro.orchestration import RolloutManager

    val, test, (uncal, global_plan, bank) = drift_data
    ro = RolloutManager(bank.bumped(), lambda b: b, canary_cells=(0,))
    with pytest.raises(ValueError, match="rollout"):
        run_fleet(bank, scenario, orchestrator=Orchestrator(rollout=ro),
                  backend="compiled")


# ------------------------------------------------------------ mesh sharding
def test_compiled_explicit_mesh_parity(drift_data, scenario):
    """Forcing the `shard_map` path on the 1-device CPU mesh must change
    nothing: the sharded program is the same program."""
    from repro.sharding import fleet_mesh
    from repro.fleet.compiled import CompiledFleetSimulator
    from repro.fleet.simulator import FleetConfig, FleetSimulator

    val, test, (uncal, global_plan, bank) = drift_data
    table = fleet_gate_table(bank, scenario, backend="compiled")
    profile = L.paper_2020()
    cfg = FleetConfig(window_s=0.5)
    a = FleetSimulator(table, scenario.topology, profile, config=cfg).run()
    b = CompiledFleetSimulator(table, scenario.topology, profile,
                               config=cfg, mesh=fleet_mesh()).run()
    assert_per_request_parity(a, b)
    assert_summary_parity(a, b)


def test_compiled_mesh_must_divide_cells(drift_data, scenario):
    from repro.fleet.compiled import CompiledFleetSimulator
    from repro.fleet.simulator import FleetConfig

    class FakeMesh:  # 4 devices over 6 cells: not an even split
        size = 4

    val, test, (uncal, global_plan, bank) = drift_data
    table = fleet_gate_table(bank, scenario, backend="compiled")
    sim = CompiledFleetSimulator(table, scenario.topology, L.paper_2020(),
                                 config=FleetConfig(window_s=0.5),
                                 mesh=FakeMesh())
    with pytest.raises(ValueError, match="shard evenly"):
        sim._resolve_mesh(scenario.topology.n_cells)


def test_compiled_auto_mesh_never_falls_back_to_one_device(
        drift_data, scenario, monkeypatch):
    """mesh="auto" with several devices that do not divide the cell count
    raises instead of quietly running on one device."""
    import jax
    import repro.sharding
    from repro.fleet.compiled import CompiledFleetSimulator
    from repro.fleet.simulator import FleetConfig

    class FakeMesh:  # 4 devices over 6 cells
        size = 4

    monkeypatch.setattr(jax, "device_count", lambda: 4)
    monkeypatch.setattr(repro.sharding, "fleet_mesh", lambda: FakeMesh())
    val, test, (uncal, global_plan, bank) = drift_data
    table = fleet_gate_table(bank, scenario, backend="compiled")
    sim = CompiledFleetSimulator(table, scenario.topology, L.paper_2020(),
                                 config=FleetConfig(window_s=0.5))
    assert sim.mesh == "auto"
    with pytest.raises(ValueError, match="shard evenly"):
        sim._resolve_mesh(scenario.topology.n_cells)


@pytest.mark.nightly
def test_compiled_multi_device_shard_map():
    """Real multi-device sharding: 4 forced host devices, cells sharded
    2-per-device through `shard_map`, parity against host numpy. Runs in
    a subprocess because XLA device count is fixed at backend init."""
    code = textwrap.dedent("""
        import numpy as np
        from repro.serving.scenarios import (
            fit_drift_plans, synthetic_distorted_cascade)
        from repro.fleet.scenarios import reference_fleet, run_fleet
        import jax
        assert jax.device_count() == 4, jax.device_count()
        val, test = synthetic_distorted_cascade(
            directions={"gaussian_blur": "under"})
        _, _, bank = fit_drift_plans(val)
        scn = reference_fleet(n_cells=8, requests_per_cell=150, seed=0,
                              val=val, test=test)
        a = run_fleet(bank, scn).fleet_summary()
        b = run_fleet(bank, scn, backend="compiled").fleet_summary()
        for k in a:
            np.testing.assert_allclose(b[k], a[k], rtol=1e-9, atol=1e-12)
        print("MULTI_DEVICE_PARITY_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "MULTI_DEVICE_PARITY_OK" in out.stdout
