"""Compiled fleet pipeline: the whole window loop as jitted device stages.

`FleetSimulator.run` steps the fleet in host numpy: a Python loop over
(window, cell) batches, each doing a handful of small vectorized solves.
This module moves the full pipeline -- per-device FIFO edge queues ->
context lookup -> gate -> per-cell uplink (with Markov/trace link
repricing) -> the shared K-server cloud tier -- into three jitted JAX
stages (edge + gate, uplink, cloud), `vmap`ped (and optionally
`shard_map`ped over a "cells" mesh axis, see `repro.sharding.fleet_mesh`)
over serving cells. The two orderings between the stages (offloads per
uplink batch, the cloud's FIFO order) are exact stable sorts on the host
(see `_uplink_order`):

* every FIFO recurrence becomes a masked `lax.associative_scan` over the
  max-plus semiring (`repro.fleet.maxplus`, property-tested against a
  per-request Python oracle);
* windows do not need a host loop at all: window boundaries only decide
  BATCH MEMBERSHIP (which uplink batch a request joins) and the per-batch
  link repricing order, so the host precomputes the (window, origin) ->
  serving-cell batch layout (including churn shed routing, which is pure
  time-based) and the device program runs the per-cell batch sequence
  under `lax.scan` -- that scan IS the window loop, fused;
* the `GateTable` conf block and the materialized context/network tables
  live device-resident for the whole run.

Parity contract (pinned by tests/test_gatepath.py, test_fleet.py,
test_fleet_properties.py, test_obs.py): against the host simulator on the
same scenario, every integer/bool column (gate decision, context id,
estimator verdict, correctness, shed routing, churn accounting) matches
EXACTLY -- the gate compares the same float64 table values against the
same threshold -- while latency columns match to float round-off (the
scan evaluates the same max-plus algebra with a different, tree-shaped
rounding order than the host's sequential cumsum).

Scope: the compiled path serves a STATIC deployment (no mid-run
controller rescoring, no canary rollout -- both mutate per-window state
the fused program has already consumed; use backend="numpy"/"jax" for
those). Churn shed/backhaul, cloud brownouts, the QoS monitor, and obs
trace/audit/metrics emission are fully supported: the device program
returns the per-request columns and the host replays the boundary
bookkeeping (orchestrator hooks, live QoS view, sampled traces) from
them, operation-for-operation in the host simulator's order.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

from repro.core.gatepath import GateTable, NumpyGateBackend, _next_pow2
from repro.fleet.simulator import FleetConfig, FleetSimulator, _LiveCloud
from repro.fleet.telemetry import FleetTelemetry
from repro.fleet.topology import FleetTopology
from repro.offload import latency as L
from repro.serving.drift import MarkovContextSchedule, PiecewiseSchedule
from repro.serving.network import FixedRateNetwork, MarkovNetwork, TraceNetwork

__all__ = ["CompiledGateBackend", "CompiledFleetSimulator"]

_BIG_DWELL = 1e18  # one-slot "slotted" table: floor(t / BIG) == 0 for any t


class CompiledGateBackend(NumpyGateBackend):
    """Backend marker that routes `run_fleet` to the compiled simulator.

    Table precompute and host-side window gates are the exact float64
    numpy path (this class IS `NumpyGateBackend` plus a name), so gate
    decisions on the compiled path are bit-identical to the host
    simulator's; what changes is WHERE the fleet pipeline runs -- see
    `CompiledFleetSimulator`.
    """

    name = "compiled"


@dataclass
class _Batch:
    """One (window, origin-cell) arrival batch and where it serves."""

    w: int
    origin: int
    serve: int  # serving cell, or -1 = whole-fleet-outage cloud backhaul
    lo: int
    hi: int
    shed: bool
    row0: int = 0  # start row in the serving cell's lane (or backhaul lane)
    blocal: int = 0  # batch index within the serving cell's lane


class CompiledFleetSimulator(FleetSimulator):
    """Drop-in `FleetSimulator` whose `run` executes device-side.

    mesh: None = single-device `vmap`; a `jax.sharding.Mesh` with axis
    "cells" = `shard_map` over cells (cell count must divide the mesh
    size evenly); "auto" = `repro.sharding.fleet_mesh()` when more than
    one device is visible, and an error when the cell count does not
    divide the device count -- it never drops to one device in silence.
    `output_devices` holds the ids of the devices that held the last
    window program's outputs.
    """

    def __init__(
        self,
        table: GateTable,
        topology: FleetTopology,
        profile: L.LatencyProfile,
        config: Optional[FleetConfig] = None,
        controller=None,
        payload_nbytes: Optional[Callable[[int], int]] = None,
        orchestrator=None,
        obs=None,
        mesh="auto",
    ):
        if controller is not None:
            raise ValueError(
                "the compiled fleet pipeline serves a static deployment; "
                "run the controller on the host backend "
                "(backend='numpy' or 'jax')"
            )
        if orchestrator is not None and getattr(orchestrator, "rollout", None) is not None:
            raise ValueError(
                "the compiled fleet pipeline does not support canary "
                "rollouts (per-window table swaps); use the host backend"
            )
        super().__init__(
            table, topology, profile, config=config, controller=None,
            payload_nbytes=payload_nbytes, orchestrator=orchestrator, obs=obs,
        )
        self.mesh = mesh
        self.output_devices: frozenset = frozenset()
        self._programs: dict = {}

    # ------------------------------------------------------------- helpers
    def _resolve_mesh(self, n_cells: int):
        if self.mesh is None:
            return None
        if self.mesh == "auto":
            import jax

            if jax.device_count() == 1:
                return None
            from repro.sharding import fleet_mesh

            mesh = fleet_mesh()
        else:
            mesh = self.mesh
        if n_cells % mesh.size != 0:
            raise ValueError(
                f"{n_cells} cells do not shard evenly over a "
                f"{mesh.size}-device mesh; pass mesh=None to run on one "
                f"device, or a mesh whose size divides the cell count"
            )
        return mesh

    def _min_rate(self, net) -> float:
        if isinstance(net, MarkovNetwork):
            return min(net.good_bps, net.bad_bps)
        if isinstance(net, TraceNetwork):
            return float(np.min(net.trace_rates_bps))
        if isinstance(net, FixedRateNetwork):
            return float(net.bps)
        raise ValueError(
            f"compiled fleet pipeline supports Fixed/Markov/Trace networks, "
            f"not {type(net).__name__}; use the host backend"
        )

    def _net_tables(self, t_bound: float):
        """Materialize every cell's link-rate lookup device-side.

        Slotted mode replicates `MarkovNetwork.rates_bps` exactly
        (floor-division into sequentially materialized dwell slots; a
        fixed link is a one-slot table); knot mode replicates
        `TraceNetwork.rates_bps` (searchsorted over knot times, modulo the
        replay period). Same lookup, same floats -- only the memory lives
        on device for the run.
        """
        topo = self.topology
        C = topo.n_cells
        mode = np.zeros(C, np.int64)
        dwell = np.full(C, _BIG_DWELL)
        period = np.zeros(C)
        slot_rates: List[np.ndarray] = []
        knot_ts: List[np.ndarray] = []
        knot_rates: List[np.ndarray] = []
        for cell in topo.cells:
            net = cell.network
            if isinstance(net, MarkovNetwork):
                n_slots = int(max(t_bound, 0.0) // net.dwell_s) + 2
                rates = net.rates_bps(
                    (np.arange(n_slots) + 0.5) * net.dwell_s
                )
                dwell[len(slot_rates)] = net.dwell_s
                slot_rates.append(np.asarray(rates, np.float64))
                knot_ts.append(np.zeros(1))
                knot_rates.append(np.zeros(1))
            elif isinstance(net, TraceNetwork):
                mode[len(slot_rates)] = 1
                period[len(slot_rates)] = (
                    0.0 if net.period_s is None else float(net.period_s)
                )
                slot_rates.append(np.asarray([1.0]))
                knot_ts.append(np.asarray(net.times_s, np.float64))
                knot_rates.append(np.asarray(net.trace_rates_bps, np.float64))
            elif isinstance(net, FixedRateNetwork):
                slot_rates.append(np.asarray([net.bps], np.float64))
                knot_ts.append(np.zeros(1))
                knot_rates.append(np.zeros(1))
            else:  # pragma: no cover - guarded by _min_rate earlier
                raise ValueError(f"unsupported network {type(net).__name__}")
        S_net = max(len(r) for r in slot_rates)
        Kn = max(len(k) for k in knot_ts)
        slots = np.empty((C, S_net))
        kts = np.full((C, Kn), np.inf)
        krs = np.empty((C, Kn))
        for c in range(C):
            r = slot_rates[c]
            slots[c, : len(r)] = r
            slots[c, len(r):] = r[-1]
            kt, kr = knot_ts[c], knot_rates[c]
            kts[c, : len(kt)] = kt
            krs[c, : len(kr)] = kr
            krs[c, len(kr):] = kr[-1]
        return dict(
            net_mode=mode, net_dwell=dwell, net_period=period,
            net_slots=slots, net_knots=kts, net_rates=krs,
        ), bool((mode == 1).any())

    def _ctx_tables(self, t_bound: float):
        """Materialize every cell's context-regime lookup device-side,
        already mapped through the schedule-context -> table-context ids
        (`_sched_map`), mirroring `FleetSimulator._ctx_ids` exactly."""
        topo = self.topology
        C = topo.n_cells
        mode = np.zeros(C, np.int64)
        dwell = np.full(C, _BIG_DWELL)
        slot_ids: List[np.ndarray] = []
        knot_ts: List[np.ndarray] = []
        knot_ids: List[np.ndarray] = []
        for c, cell in enumerate(topo.cells):
            sched = cell.schedule
            if sched is None:
                slot_ids.append(np.asarray([self._static_ctx[c]], np.int64))
                knot_ts.append(np.zeros(1))
                knot_ids.append(np.zeros(1, np.int64))
            elif isinstance(sched, MarkovContextSchedule):
                n_slots = int(max(t_bound, 0.0) // sched.dwell_s) + 2
                mids = (np.arange(n_slots) + 0.5) * sched.dwell_s
                ids = self._sched_map[c][sched.context_ids_at(mids)]
                dwell[c] = sched.dwell_s
                slot_ids.append(np.asarray(ids, np.int64))
                knot_ts.append(np.zeros(1))
                knot_ids.append(np.zeros(1, np.int64))
            elif isinstance(sched, PiecewiseSchedule):
                mode[c] = 1
                slot_ids.append(np.zeros(1, np.int64))
                knot_ts.append(np.asarray(sched.starts, np.float64))
                seg_ids = self._sched_map[c][
                    sched.context_ids_at(sched.starts)
                ]
                knot_ids.append(np.asarray(seg_ids, np.int64))
            else:
                raise ValueError(
                    f"compiled fleet pipeline supports Markov/Piecewise "
                    f"context schedules, not {type(sched).__name__}; use "
                    f"the host backend"
                )
        S_ctx = max(len(s) for s in slot_ids)
        Kc = max(len(k) for k in knot_ts)
        slots = np.empty((C, S_ctx), np.int64)
        kts = np.full((C, Kc), np.inf)
        kids = np.zeros((C, Kc), np.int64)
        for c in range(C):
            s = slot_ids[c]
            slots[c, : len(s)] = s
            slots[c, len(s):] = s[-1]
            kt, ki = knot_ts[c], knot_ids[c]
            kts[c, : len(kt)] = kt
            kids[c, : len(ki)] = ki
            kids[c, len(ki):] = ki[-1]
        return dict(
            ctx_mode=mode, ctx_dwell=dwell,
            ctx_slots=slots, ctx_knots=kts, ctx_kctx=kids,
        ), bool((mode == 1).any())

    # ------------------------------------------------------- device program
    def _program(self, S):
        if S in self._programs:
            return self._programs[S]
        import jax
        import jax.numpy as jnp
        from jax import lax

        from repro.fleet.maxplus import maxplus_fifo

        (C, R, B, Rb, RB, D, K, N_pad, S_ctx, Kc, S_net, Kn,
         slowdowns, ctx_knots, net_knots, mesh_axes, n_ctx, cal_bins) = S
        mesh = self._mesh_obj  # resolved by run(); part of the cache key

        def scale_at(t):
            sc = jnp.ones_like(t)
            for a, b, f in slowdowns:
                sc = sc * jnp.where((t >= a) & (t < b), f, 1.0)
            return sc

        def ctx_at(tbl, org, t):
            tpos = jnp.maximum(t, 0.0)
            slot = jnp.clip(
                (tpos // tbl["ctx_dwell"][org]).astype(jnp.int32),
                0, S_ctx - 1,
            )
            out = tbl["ctx_slots"][org, slot]
            if ctx_knots:
                seg = jax.vmap(
                    lambda kn, x: jnp.searchsorted(kn, x, side="right")
                )(tbl["ctx_knots"][org], tpos) - 1
                seg = jnp.clip(seg, 0, Kc - 1)
                out = jnp.where(
                    tbl["ctx_mode"][org] == 1, tbl["ctx_kctx"][org, seg], out
                )
            return out

        def rate_at(tbl, c, t):
            tpos = jnp.maximum(t, 0.0)
            slot = jnp.clip(
                (tpos // tbl["net_dwell"][c]).astype(jnp.int32),
                0, S_net - 1,
            )
            out = tbl["net_slots"][c, slot]
            if net_knots:
                per = tbl["net_period"][c]
                tt = jnp.where(per > 0, jnp.mod(t, per), t)
                seg = jnp.maximum(
                    jnp.searchsorted(tbl["net_knots"][c], tt, side="right")
                    - 1,
                    0,
                )
                out = jnp.where(
                    tbl["net_mode"][c] == 1, tbl["net_rates"][c, seg], out
                )
            return out

        def gate_fn(arr, smp, dev, org, valid, tbl):
            # --- edge tier: one masked max-plus chain per device lane.
            # Rows arrive in (window, origin) batch order, which is
            # exactly the host's carried-dev_free chain order.
            srv = jnp.full(R, tbl["s_edge"])
            edge_done = jnp.zeros(R)
            for d in range(D):
                m = valid & (dev == d)
                done = maxplus_fifo(arr, srv, m, 0.0)
                edge_done = jnp.where(m, done, edge_done)
            # --- context + gate (same float64 conf vs p_tar as the host)
            ctx = jnp.where(valid, ctx_at(tbl, org, edge_done), 0)
            conf = tbl["conf"][ctx, smp]
            on = conf >= tbl["p_tar"]
            return edge_done, ctx, conf, on

        def uplink_fn(cell_id, edge_done, offl, bl, order, tbl):
            # --- uplink: offloads first in (batch, ready-time) order (the
            # permutation `order` comes from the host, see `_uplink_order`),
            # then price each batch with the host's two-pass link
            # repricing under a lax.scan carrying the uplink-free time.
            # That scan is the window loop, fused.
            t_s = edge_done[order]
            o_s = offl[order]
            counts = jax.ops.segment_sum(
                o_s.astype(jnp.int32), bl[order], num_segments=B
            )
            starts = jnp.concatenate(
                [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]]
            )
            sub = jnp.arange(Rb)
            idx = jnp.clip(starts[:, None] + sub[None, :], 0, R - 1)
            sv = sub[None, :] < counts[:, None]  # (B, Rb) in-batch validity
            t_b = t_s[idx]
            nbytes8 = tbl["nbytes8"]

            def step(free, xs):
                t_row, m_row = xs
                r1 = rate_at(tbl, cell_id, t_row)
                c1 = nbytes8 / r1
                d1 = maxplus_fifo(t_row, c1, m_row, free)
                # reprice at the actual transfer start (host's fixed-point
                # pass: rates at done - comm1)
                c2 = nbytes8 / rate_at(tbl, cell_id, d1 - c1)
                d2 = maxplus_fifo(t_row, c2, m_row, free)
                free2 = jnp.where(
                    m_row.any(),
                    jnp.max(jnp.where(m_row, d2, -jnp.inf)),
                    free,
                )
                return free2, (d2, c2)

            _, (d_b, c_b) = lax.scan(step, jnp.asarray(0.0), (t_b, sv))
            flat_i = idx.reshape(-1)
            flat_v = sv.reshape(-1)
            safe = jnp.where(flat_v, order[flat_i], R)
            up_done = jnp.full(R + 1, jnp.nan).at[safe].set(
                d_b.reshape(-1)
            )[:R]
            up_comm = jnp.full(R + 1, jnp.nan).at[safe].set(
                c_b.reshape(-1)
            )[:R]
            return up_done, up_comm

        def bh_fn(cell_id, arr, smp, valid, tbl):
            # whole-fleet outage: nominal-rate cloud backhaul per origin
            done = maxplus_fifo(
                arr, jnp.full(RB, tbl["comm_bh"]), valid, 0.0
            )
            org = jnp.full(RB, cell_id)
            ctx = jnp.where(valid, ctx_at(tbl, org, arr), 0)
            return ctx, done

        def gate_cells(cell_ids, lane, bh, tbl):
            outA = jax.vmap(gate_fn, in_axes=(0, 0, 0, 0, 0, None))(
                lane["arr"], lane["smp"], lane["dev"], lane["org"],
                lane["valid"], tbl,
            )
            outB = jax.vmap(bh_fn, in_axes=(0, 0, 0, 0, None))(
                cell_ids, bh["arr"], bh["smp"], bh["valid"], tbl
            )
            return outA, outB

        def uplink_cells(cell_ids, up, tbl):
            return jax.vmap(uplink_fn, in_axes=(0, 0, 0, 0, 0, None))(
                cell_ids, up["edge_done"], up["offl"], up["bl"],
                up["order"], tbl,
            )

        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            sh = P("cells", None)
            rep = jax.tree_util.tree_map(lambda _: P(), self._tbl_struct)
            gate_cells = jax.shard_map(
                gate_cells,
                mesh=mesh,
                in_specs=(
                    P("cells"),
                    {k: sh for k in ("arr", "smp", "dev", "org", "valid")},
                    {k: sh for k in ("arr", "smp", "valid")},
                    rep,
                ),
                out_specs=((sh,) * 4, (sh,) * 2),
                check_vma=False,
            )
            uplink_cells = jax.shard_map(
                uplink_cells,
                mesh=mesh,
                in_specs=(
                    P("cells"),
                    {k: sh for k in ("edge_done", "offl", "bl", "order")},
                    rep,
                ),
                out_specs=(sh, sh),
                check_vma=False,
            )

        def gate_program(cell_ids, lane, bh, tbl):
            lane_in = {k: lane[k] for k in
                       ("arr", "smp", "dev", "org", "valid")}
            bh_in = {k: bh[k] for k in ("arr", "smp", "valid")}
            (edge_done, ctx, conf, on), (ctx_bh, bh_done) = gate_cells(
                cell_ids, lane_in, bh_in, tbl
            )
            res = dict(
                edge_done=edge_done, ctx=ctx, conf=conf, on=on,
                ctx_bh=ctx_bh, bh_done=bh_done,
                s_eff_bh=tbl["s_cloud"] * scale_at(bh_done),
            )
            if cal_bins:
                # --- reliability-bin sketch, accumulated IN the fused
                # program: the same float64 edges the host sketch bins
                # with (passed in via tbl, not recomputed on device, so
                # `searchsorted` assigns bit-identical bins), summed by
                # (origin cell, context, bin) segment ids. Backhaul lanes
                # carry no gate decision and are excluded -- the host
                # counts them via `note_ungated`.
                nb1 = cal_bins + 1
                vf = lane["valid"].reshape(-1).astype(conf.dtype)
                ctx_f = ctx.reshape(-1)
                org_f = lane["org"].reshape(-1)
                conf_f = conf.reshape(-1)
                ec = tbl["ecorrect"][ctx_f, lane["smp"].reshape(-1)]
                onf = on.reshape(-1).astype(conf.dtype)
                bin_ = jnp.searchsorted(tbl["cal_edges"], conf_f,
                                        side="left") - 1
                bin_ = jnp.where(bin_ < 0, cal_bins, bin_)
                seg = (org_f * n_ctx + ctx_f) * nb1 + bin_
                rows = jnp.stack([
                    vf, ec * vf, conf_f * vf, conf_f * conf_f * vf,
                    conf_f * ec * vf, onf * vf, onf * ec * vf,
                ])
                calsum = jax.vmap(
                    lambda r: jax.ops.segment_sum(
                        r, seg, num_segments=C * n_ctx * nb1
                    )
                )(rows)
                res["cal"] = calsum.reshape(7, C, n_ctx, nb1)
            return res

        def uplink_program(cell_ids, up, tbl):
            up_done, up_comm = uplink_cells(cell_ids, up, tbl)
            # service times at the shared cloud tier; the tier itself is
            # solved by `cloud_fn` once the host has ordered its jobs
            return dict(up_done=up_done, up_comm=up_comm,
                        s_eff=tbl["s_cloud"] * scale_at(up_done))

        def cloud_fn(mat_t, mat_s):
            # the shared cloud tier: K residue-class max-plus chains as
            # the columns of the row-major (M, K) matrix of jobs in FIFO
            # order (sorted on the host, see `_cloud_done`)
            def combine(x, y):
                a1, b1 = x
                a2, b2 = y
                return a1 + a2, jnp.maximum(b1 + a2, b2)

            a_s, b_s = lax.associative_scan(
                combine, (mat_s, mat_t + mat_s), axis=0
            )
            return jnp.maximum(b_s, a_s).reshape(-1)

        prog = (jax.jit(gate_program), jax.jit(uplink_program),
                jax.jit(cloud_fn))
        self._programs[S] = prog
        return prog

    # The orderings between the device stages are computed here, on the
    # host, with numpy's exact stable lexsort: the TPU compiler's time for
    # a sort grows with the number of elements sorted (about 50 s for a
    # 2^16-element float32 sort, over 6 min for the per-cell float64
    # lexsort at the reference fleet's 64 x 2048), while every stage
    # without a sort compiles in seconds. The orders are permutations,
    # so the device stages compute exactly what one program would.
    @staticmethod
    def _uplink_order(edge_done, offl, bl):
        """Per cell, the row permutation that puts offloads first, in
        (uplink batch, edge completion, row) order: (C, R) row indices."""
        C, R = edge_done.shape
        o = np.lexsort((
            np.tile(np.arange(R), C), edge_done.ravel(), bl.ravel(),
            ~offl.ravel(), np.repeat(np.arange(C), R),
        ))
        return o.reshape(C, R) - (np.arange(C) * R)[:, None]

    @staticmethod
    def _cloud_done(out, lane, bh, K: int, N_pad: int, cloud_fn):
        """Shared cloud tier completion times, (C, R) and (C, RB).

        Jobs are stably ordered by transfer completion, generation order
        breaking ties, exactly as the host simulator queues them; the K
        residue-class chains run on the device (`cloud_fn`).
        """
        C, R = out["edge_done"].shape
        okA = (lane["valid"] & ~out["on"]).reshape(-1)
        okB = bh["valid"].reshape(-1)
        ok = np.concatenate([okA, okB])
        t = np.concatenate([out["up_done"].reshape(-1), out["bh_done"].reshape(-1)])
        sv = np.concatenate([out["s_eff"].reshape(-1), out["s_eff_bh"].reshape(-1)])
        gid = np.concatenate([lane["gid"].reshape(-1), bh["gid"].reshape(-1)])
        ready = np.concatenate(
            [out["edge_done"].reshape(-1), bh["arr"].reshape(-1)]
        )
        n = t.shape[0]
        fi = np.arange(n)
        gorder = np.lexsort((fi, ready, gid, ~ok))
        grank = np.empty(n, fi.dtype)
        grank[gorder] = fi
        key_t = np.where(ok, t, np.inf)
        order2 = np.lexsort((grank, key_t))
        t_sorted = np.full(N_pad, np.inf)
        s_sorted = np.zeros(N_pad)
        t_sorted[:n] = key_t[order2]
        s_sorted[:n] = np.where(ok, sv, 0.0)[order2]
        done_sorted = np.asarray(
            cloud_fn(t_sorted.reshape(-1, K), s_sorted.reshape(-1, K))
        )[:n]
        cloud = np.empty(n)
        cloud[order2] = done_sorted
        return cloud[: C * R].reshape(C, R), cloud[C * R:].reshape(C, -1)

    # ----------------------------------------------------------------- run
    def run(self) -> FleetTelemetry:
        topo, cfg, table = self.topology, self.config, self.table
        tel = FleetTelemetry(
            topo.n_cells,
            context_keys=table.ctx_keys,
            bank_keys=table.bank_keys or None,
        )
        for c, cell in enumerate(topo.cells):
            tel.set_arrivals(c, cell.workload.arrival_s)

        self._state = [self._initial_state for _ in topo.cells]
        self._active = topo.initial_active_mask()
        self._cell_tables = [None] * topo.n_cells
        self._backhaul_free = np.zeros(topo.n_cells)
        self.shed_counts = np.zeros(topo.n_cells, np.int64)
        orch = self.orchestrator
        self._live = _LiveCloud(topo.cloud_servers) if orch is not None else None

        ws = cfg.window_s
        C = topo.n_cells
        n_windows = int(math.ceil(max(topo.horizon_s, 0.0) / ws)) + 1
        branch, p_tar, clevel = self._initial_state
        s_edge = L.edge_time(self.profile, branch)
        s_cloud = L.cloud_time(self.profile, branch)
        # the static deployment fixes (branch, level), so the device-resident
        # (branch, level) -> bytes table collapses to one scalar; level 0
        # reuses the raw tensor bytes unchanged (bit-exact legacy pricing)
        nbytes = float(self._payload_nbytes_for(branch, clevel))
        comm_bh = nbytes * 8.0 / self.profile.uplink_bps

        # ---- churn pre-pass: activation is pure time-based, so the
        # (window, origin) -> serving cell routing is known up front.
        active_w = np.empty((n_windows, C), bool)
        active = topo.initial_active_mask()
        churn = None if orch is None else orch.churn
        cursor = 0
        if churn is not None:
            from repro.orchestration.churn import JOIN
        for w in range(n_windows):
            if churn is not None:
                due, cursor = churn.due(cursor, w * ws)
                for ev in due:
                    active[ev.cell] = ev.kind == JOIN
            active_w[w] = active

        # ---- batch layout in host (window, origin) order
        shed_orders: dict = {}
        batches: List[_Batch] = []
        by_window: List[List[_Batch]] = [[] for _ in range(n_windows)]
        ptr = np.zeros(C, np.int64)
        for w in range(n_windows):
            t1 = (w + 1) * ws
            act = active_w[w]
            for c, cell in enumerate(topo.cells):
                arr = cell.workload.arrival_s
                hi = int(np.searchsorted(arr, t1, side="left"))
                lo = int(ptr[c])
                ptr[c] = hi
                if hi == lo:
                    continue
                if act[c]:
                    serve, shed = c, False
                else:
                    shed = True
                    serve = -1
                    if c not in shed_orders:
                        shed_orders[c] = topo.shed_order(c)
                    for s in shed_orders[c]:
                        if act[s]:
                            serve = int(s)
                            break
                b = _Batch(w, c, serve, lo, hi, shed)
                batches.append(b)
                by_window[w].append(b)

        rowsA = np.zeros(C, np.int64)
        rowsB = np.zeros(C, np.int64)
        nbatchA = np.zeros(C, np.int64)
        max_batch = 1
        for b in batches:
            n = b.hi - b.lo
            max_batch = max(max_batch, n)
            if b.serve >= 0:
                b.row0 = int(rowsA[b.serve])
                b.blocal = int(nbatchA[b.serve])
                rowsA[b.serve] += n
                nbatchA[b.serve] += 1
            else:
                b.row0 = int(rowsB[b.origin])
                rowsB[b.origin] += n
        R = _next_pow2(max(1, int(rowsA.max())))
        RB = _next_pow2(max(1, int(rowsB.max())))
        B = max(1, int(nbatchA.max()))
        Rb = _next_pow2(max_batch)
        D = max(cell.n_devices for cell in topo.cells)

        lane = dict(
            arr=np.zeros((C, R)), smp=np.zeros((C, R), np.int64),
            dev=np.zeros((C, R), np.int64), org=np.zeros((C, R), np.int64),
            bl=np.zeros((C, R), np.int64), gid=np.zeros((C, R), np.int64),
            valid=np.zeros((C, R), bool),
        )
        bh = dict(
            arr=np.zeros((C, RB)), smp=np.zeros((C, RB), np.int64),
            gid=np.zeros((C, RB), np.int64), valid=np.zeros((C, RB), bool),
        )
        for b in batches:
            n = b.hi - b.lo
            wl = topo.cells[b.origin].workload
            gid = b.w * C + b.origin
            if b.serve >= 0:
                sl = (b.serve, slice(b.row0, b.row0 + n))
                lane["arr"][sl] = wl.arrival_s[b.lo:b.hi]
                lane["smp"][sl] = wl.sample[b.lo:b.hi]
                dev = wl.device[b.lo:b.hi]
                if b.shed:
                    dev = dev % topo.cells[b.serve].n_devices
                lane["dev"][sl] = dev
                lane["org"][sl] = b.origin
                lane["bl"][sl] = b.blocal
                lane["gid"][sl] = gid
                lane["valid"][sl] = True
            else:
                sl = (b.origin, slice(b.row0, b.row0 + n))
                bh["arr"][sl] = wl.arrival_s[b.lo:b.hi]
                bh["smp"][sl] = wl.sample[b.lo:b.hi]
                bh["gid"][sl] = gid
                bh["valid"][sl] = True

        # ---- materialized lookup tables (bounded by the worst completion
        # time any lookup can be queried at)
        t_edge_bound = topo.horizon_s + ws + (R + 1) * s_edge + 1.0
        max_comm = max(
            (nbytes * 8.0 / self._min_rate(cell.network)
             for cell in topo.cells),
            default=0.0,
        )
        t_net_bound = t_edge_bound + (R + 1) * max(max_comm, comm_bh) + 1.0
        net_tbl, any_net_knots = self._net_tables(t_net_bound)
        ctx_tbl, any_ctx_knots = self._ctx_tables(t_edge_bound)
        bi = table.branch_idx(branch)
        tbl = dict(
            conf=np.asarray(table.conf[:, bi, :], np.float64),
            s_edge=np.float64(s_edge), s_cloud=np.float64(s_cloud),
            nbytes8=np.float64(nbytes * 8.0),
            comm_bh=np.float64(comm_bh), p_tar=np.float64(p_tar),
            **net_tbl, **ctx_tbl,
        )
        cal_on = self._cal is not None and table.labels is not None
        if cal_on:
            from repro.obs.calibration import bin_edges

            # host-computed float64 edges + per-(ctx, sample) EDGE
            # correctness table, so the device program's binning and
            # correctness match the host sketch bit-for-bit
            tbl["cal_edges"] = bin_edges(self._cal.n_bins)
            tbl["ecorrect"] = (
                table.pred[:, bi, :] == table.labels[None, :]
            ).astype(np.float64)
        self._tbl_struct = tbl

        K = topo.cloud_servers
        n_jobs = C * R + C * RB
        N_pad = int(math.ceil(n_jobs / K)) * K
        self._mesh_obj = self._resolve_mesh(C)
        S = (
            C, R, B, Rb, RB, D, K, N_pad,
            ctx_tbl["ctx_slots"].shape[1], ctx_tbl["ctx_knots"].shape[1],
            net_tbl["net_slots"].shape[1], net_tbl["net_knots"].shape[1],
            tuple(cfg.cloud_slowdowns), any_ctx_knots, any_net_knots,
            None if self._mesh_obj is None else tuple(self._mesh_obj.shape.items()),
            int(table.conf.shape[0]),
            0 if not cal_on else int(self._cal.n_bins),
        )
        prog = self._program(S)

        import jax

        gate_prog, uplink_prog, cloud_prog = prog
        cell_ids = np.arange(C, dtype=np.int64)
        with jax.enable_x64(True):
            gate_out = gate_prog(cell_ids, lane, bh, tbl)
            out = {k: np.asarray(v) for k, v in gate_out.items()}
            offl = lane["valid"] & ~out["on"]
            up_out = uplink_prog(cell_ids, dict(
                edge_done=gate_out["edge_done"], offl=offl, bl=lane["bl"],
                order=self._uplink_order(out["edge_done"], offl, lane["bl"]),
            ), tbl)
            self.output_devices = frozenset(
                d.id for v in (*gate_out.values(), *up_out.values())
                for d in v.sharding.device_set
            )
            out.update({k: np.asarray(v) for k, v in up_out.items()})
            out["cloud"], out["cloud_bh"] = self._cloud_done(
                out, lane, bh, K, N_pad, cloud_prog
            )

        # ---- host recovery: per-request verdict columns (exact numpy
        # table math, same as the host simulator's gate aftermath)
        est = table.est_ids(out["ctx"].ravel(), lane["smp"].ravel())
        estA = (
            np.full((C, R), -2, np.int64) if est is None
            else est.reshape(C, R)
        )
        pred = table.pred[:, bi, :][out["ctx"], lane["smp"]]
        cpredA = table.cloud_pred(out["ctx"].ravel(),
                                  lane["smp"].ravel(),
                                  level=clevel).reshape(C, R)
        ce = table.correct(lane["smp"].ravel(), pred.ravel())
        cc = table.correct(lane["smp"].ravel(), cpredA.ravel())
        # EDGE-branch correctness, kept separately from the cloud-patched
        # column: the calibration stream audits the gate's own verdict
        self._ecA = None if ce is None else ce.reshape(C, R).astype(np.int8)
        if ce is None:
            correctA = np.full((C, R), -1, np.int8)
        else:
            correctA = np.where(
                out["on"], ce.reshape(C, R), cc.reshape(C, R)
            ).astype(np.int8)
        completeA = np.where(out["on"], out["edge_done"], out["cloud"])
        cpredB = table.cloud_pred(out["ctx_bh"].ravel(),
                                  bh["smp"].ravel(),
                                  level=clevel).reshape(C, RB)
        ccB = table.correct(bh["smp"].ravel(), cpredB.ravel())
        correctB = (
            np.full((C, RB), -1, np.int8) if ccB is None
            else ccB.reshape(C, RB).astype(np.int8)
        )

        deadlines = [cell.deadline_s for cell in topo.cells]
        has_shed = any(b.shed for b in batches)
        obs_on = self.obs is not None and self.obs.enabled

        if orch is None and not obs_on and not has_shed:
            self._flush_fast(tel, lane, out, estA, correctA, completeA,
                             rowsA, deadlines, branch, p_tar, clevel, nbytes)
        else:
            self._replay(tel, lane, bh, out, estA, correctA, completeA,
                         correctB, by_window, n_windows, ws, deadlines,
                         branch, p_tar, clevel, nbytes, orch)
        if orch is not None:
            orch.finish(self, tel, n_windows * ws)
        return tel

    # ------------------------------------------------- host-side recovery
    def _est_mapped(self, est, ctx):
        return np.where(
            est >= 0, self._bank_to_table[np.maximum(est, 0)],
            np.where(est == -2, ctx, -1),
        )

    def _flush_fast(self, tel, lane, out, estA, correctA, completeA,
                    rowsA, deadlines, branch, p_tar, clevel, nbytes):
        """No churn, no orchestrator, no obs: flush whole per-cell columns.

        Chunking telemetry per cell instead of per (window, cell) batch is
        invisible to every reader (`_CellColumns` concatenates chunks and
        the observation streams are windowed by value), and the row order
        is the host's batch order, so the streams are element-identical.
        """
        C = self.topology.n_cells
        for c in range(C):
            n = int(rowsA[c])
            if n == 0:
                continue
            sl = (c, slice(0, n))
            arr = lane["arr"][sl]
            edge_done = out["edge_done"][sl]
            on = out["on"][sl]
            ctx = out["ctx"][sl]
            est = estA[sl]
            complete = completeA[sl]
            lat = complete - arr
            ded = deadlines[c]
            missed = (
                np.full(n, -1, np.int8) if ded is None
                else (lat > ded).astype(np.int8)
            )
            tel.observe_contexts(c, edge_done, self._est_mapped(est, ctx))
            off = ~on
            if off.any():
                order = np.lexsort((
                    np.arange(n)[off], edge_done[off], lane["bl"][sl][off],
                ))
                t_ready = edge_done[off][order]
                rates = nbytes * 8.0 / out["up_comm"][sl][off][order]
                tel.observe_bandwidth(c, t_ready, rates)
            tel.add_window(
                c, latency_s=lat, on_device=on, correct=correctA[sl],
                p_tar=np.full(n, p_tar), branch=np.full(n, branch, np.int64),
                ctx_id=ctx, est_id=est, missed=missed,
                energy_j=self._energy_col(
                    L.edge_time(self.profile, branch), on, branch, clevel
                ),
            )

    def _batch_cols(self, b, lane, bh, out, estA, correctA, completeA,
                    correctB, deadlines, branch, p_tar, clevel):
        n = b.hi - b.lo
        if b.serve >= 0:
            sl = (b.serve, slice(b.row0, b.row0 + n))
            cols = {
                "arrival": lane["arr"][sl],
                "samples": lane["smp"][sl],
                "edge_done": out["edge_done"][sl],
                "complete": completeA[sl],
                "on_device": out["on"][sl],
                "ctx_id": out["ctx"][sl],
                "est_id": estA[sl],
                "correct": correctA[sl],
                "branch": np.full(n, branch, np.int64),
                "p_tar": np.full(n, p_tar),
                "clevel": np.full(n, int(clevel), np.int64),
                "energy_j": self._energy_col(
                    L.edge_time(self.profile, branch), out["on"][sl],
                    branch, int(clevel),
                ),
                "deadline": deadlines[b.origin],
            }
            # cols["correct"] above is already cloud-patched; the live
            # calibration stream and gate trace records need the gate's
            # own verdict, so the edge column always rides along
            cols["edge_correct"] = (
                np.full(n, -1, np.int8) if self._ecA is None
                else self._ecA[sl]
            )
            if self._tracing:
                cols["conf"] = out["conf"][sl]
                cols["uplink_done"] = out["up_done"][sl]
                cols["uplink_start"] = out["up_done"][sl] - out["up_comm"][sl]
                cols["cloud_service"] = np.where(
                    cols["on_device"], np.nan, out["s_eff"][sl]
                )
                cols["serve_cell"] = b.serve
            elif self._live is not None:
                cols["conf"] = out["conf"][sl]
            return cols, out["up_comm"][sl], out["s_eff"][sl]
        sl = (b.origin, slice(b.row0, b.row0 + n))
        arr = bh["arr"][sl]
        cols = {
            "arrival": arr,
            "samples": bh["smp"][sl],
            "edge_done": arr.copy(),
            "complete": out["cloud_bh"][sl],
            "on_device": np.zeros(n, bool),
            "ctx_id": out["ctx_bh"][sl],
            "est_id": np.full(n, -2, np.int64),
            "correct": correctB[sl],
            "branch": np.full(n, branch, np.int64),
            "p_tar": np.full(n, p_tar),
            "clevel": np.full(n, int(clevel), np.int64),
            "energy_j": self._energy_col(0.0, np.zeros(n, bool), branch,
                                         int(clevel)),
            "deadline": deadlines[b.origin],
        }
        cols["edge_correct"] = np.full(n, -1, np.int8)
        comm = np.full(n, float(self._tbl_struct["comm_bh"]))
        if self._tracing:
            cols["conf"] = np.full(n, np.nan)
            cols["uplink_done"] = out["bh_done"][sl]
            cols["uplink_start"] = out["bh_done"][sl] - comm
            cols["cloud_service"] = out["s_eff_bh"][sl]
            cols["serve_cell"] = -1
        elif self._live is not None:
            cols["conf"] = np.full(n, np.nan)
        return cols, comm, out["s_eff_bh"][sl]

    def _replay(self, tel, lane, bh, out, estA, correctA, completeA,
                correctB, by_window, n_windows, ws, deadlines, branch,
                p_tar, clevel, nbytes, orch):
        """Replay the host simulator's boundary bookkeeping from the
        device-solved columns, operation-for-operation in its order:
        live-cloud pops, orchestrator hooks (churn audit + QoS monitor),
        shed accounting, telemetry/metrics/audit per batch, then the
        shared flush + obs emission."""
        window_cols: List[Tuple[int, dict]] = []
        if orch is not None:
            orch.attach(self, tel, audit=self._audit)
        for w in range(n_windows):
            t0 = w * ws
            if orch is not None:
                if w > 0:
                    self._pop_live(t0, tel)
                orch.on_window(self, tel, w, t0)
            for b in by_window[w]:
                n = b.hi - b.lo
                cols, comm, s_eff = self._batch_cols(
                    b, lane, bh, out, estA, correctA, completeA, correctB,
                    deadlines, branch, p_tar, clevel,
                )
                if bool(self._active[b.origin]) == b.shed:
                    # pragma: no cover - internal consistency
                    raise RuntimeError(
                        "churn replay diverged from the precomputed "
                        "activation schedule"
                    )
                if b.shed:
                    self.shed_counts[b.origin] += n
                    if b.serve < 0 and self._cal is not None:
                        # backhauled without a gate decision: no
                        # calibration signal, but the sketch totals must
                        # still conserve fleet_requests_total
                        self._cal.note_ungated(b.origin, n)
                    if self._metrics is not None:
                        self._metrics.inc(
                            "fleet_shed_total", n, cell=b.origin
                        )
                    arr = cols["arrival"]
                    if b.serve >= 0:
                        tel.observe_shed_arrivals(b.serve, arr)
                        if self._audit is not None:
                            self._audit.record(
                                float(arr[0]), "simulator", "shed_route",
                                cell=b.origin, host_cell=b.serve,
                                backhaul=False, requests=int(n))
                    elif self._audit is not None:
                        self._audit.record(
                            float(arr[0]), "simulator", "shed_route",
                            cell=b.origin, host_cell=None,
                            backhaul=True, requests=int(n))
                est = cols["est_id"]
                tel.observe_contexts(
                    b.serve if b.serve >= 0 else b.origin,
                    cols["edge_done"],
                    self._est_mapped(est, cols["ctx_id"]),
                )
                off = ~cols["on_device"]
                if self._metrics is not None:
                    self._metrics.inc("fleet_requests_total", n,
                                      cell=b.origin)
                    n_off = int(off.sum())
                    if n_off:
                        self._metrics.inc("fleet_offloaded_total", n_off,
                                          cell=b.origin)
                if off.any():
                    pos = np.flatnonzero(off)[
                        np.argsort(cols["edge_done"][off], kind="stable")
                    ]
                    t_ready = cols["edge_done"][pos]
                    if self._metrics is not None:
                        # uplink AND backhaul payloads count, attributed
                        # to the origin cell (host simulator's rule)
                        self._metrics.inc("fleet_uplink_bytes_total",
                                          nbytes * len(pos), cell=b.origin)
                    if b.serve >= 0:
                        tel.observe_bandwidth(
                            b.serve, t_ready, nbytes * 8.0 / comm[pos]
                        )
                        done = (out["up_done"][b.serve,
                                              b.row0:b.row0 + n][pos])
                    else:
                        done = out["bh_done"][b.origin,
                                              b.row0:b.row0 + n][pos]
                    if self._live is not None:
                        self._live.add(
                            done, s_eff[pos], b.origin,
                            cols["arrival"][pos], cols["deadline"],
                        )
                if self._live is not None:
                    self._observe_edge_live(b.origin, cols, tel)
                window_cols.append((b.origin, cols))
        if self._cal is not None and "cal" in out:
            self._ingest_cal(out["cal"], branch)
        self._flush(window_cols, tel)
        if self.obs is not None and self.obs.enabled:
            self._finish_obs(window_cols, tel)

    def _ingest_cal(self, cal: np.ndarray, branch: int) -> None:
        """Fold the device-binned `(7, C, n_ctx, n_bins+1)` reliability
        blocks into the sketch. Zero-count (cell, context) blocks are
        skipped so the sketch's key set matches the host simulator's
        (which only creates keys for contexts it actually served)."""
        keys = self.table.ctx_keys
        for c in range(cal.shape[1]):
            for k in range(cal.shape[2]):
                blk = cal[:, c, k, :]
                if blk[0].sum() <= 0:
                    continue
                self._cal.update_binned(c, keys[k], branch, blk)
