"""Edge-cloud partitioned serving engine.

A small serving runtime around the two jitted partitions of a model:

    edge partition  = blocks [0..exit_k] + exit head   (the device)
    cloud partition = blocks [exit_k..L] + main head   (the pod)

Per request batch: the edge partition runs first; the calibrated gate of
the deployed OffloadPlan marks which samples exit on-device; only the
refused samples' partition activations are shipped to the cloud partition
(the payload the paper prices at 18.8 Mbps). The engine gates with the
CalibratorState of the branch that is PHYSICALLY deployed on the edge --
not the plan's default exit -- so a plan calibrated for several exits
always pairs branch-k logits with branch-k calibration. The engine keeps
running statistics (offload rate, per-tier latency estimates) and works
for the convnet (per-image classification, the paper's case) and for the
LM families (per-sequence classification at prefill).

Every engine call is cut into phases (edge, gate, gate sync, gather,
codec, cloud, fetch) by one host-clock stamp at each boundary, so the
phases tile the call: each phase's time accumulates in an EngineStats
field, and the phase runs inside a profiler span `offload.<phase>`
(docs/observability.md, "Tracing the served engine"). The gate and the
gather are one compiled program each (`_exit_gate_step`, `_take_rows`),
and the gate's three outputs reach the host in one overlapped fetch.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.policy import OffloadPlan


@dataclass
class EngineStats:
    requests: int = 0
    on_device: int = 0
    offloaded: int = 0
    payload_bytes: int = 0
    edge_calls: int = 0
    cloud_calls: int = 0
    edge_time_s: float = 0.0  # wall-clock in edge_fn (blocked on device)
    cloud_time_s: float = 0.0  # wall-clock in cloud_fn
    # the part of edge_time_s until edge_fn returns: the host's dispatch
    edge_dispatch_s: float = 0.0
    # infer's host phases; with edge_time_s and cloud_time_s they tile it
    gate_time_s: float = 0.0  # plan.gate: exit-gate kernel and mask ops
    gate_sync_time_s: float = 0.0  # mask, prediction, confidence to the host
    gather_time_s: float = 0.0  # refused rows and their payload gather
    codec_time_s: float = 0.0  # compress.encode + decode, (un)flatten
    fetch_time_s: float = 0.0  # cloud logits to the host, softmax, scatter
    # device-to-host fetches: the gate's outputs, then any cloud logits
    host_fetches: int = 0

    @property
    def offload_rate(self):
        return self.offloaded / max(self.requests, 1)


#: each phase of an engine call: the EngineStats fields its time adds to,
#: and the phase whose span encloses its own
_PHASES = {
    "edge": (("edge_time_s", "edge_dispatch_s"), None),
    "edge_wait": (("edge_time_s",), "edge"),
    "gate": (("gate_time_s",), None),
    "gate_sync": (("gate_sync_time_s",), None),
    "gather": (("gather_time_s",), None),
    "encode": (("codec_time_s",), None),
    "decode": (("codec_time_s",), None),
    "cloud": (("cloud_time_s",), None),
    "cloud_wait": (("cloud_time_s",), "cloud"),
    "fetch": (("fetch_time_s",), None),
}


@functools.partial(jax.jit, static_argnames=("criterion", "use_kernel"))
def _exit_gate_step(exit_logits, state, p_tar, entropy_threshold, criterion,
                    use_kernel):
    """`OffloadPlan.gate` of one branch as one program: (exit_mask,
    prediction, confidence). `p_tar`, `entropy_threshold` and the state's
    arrays are traced, so a new threshold or refit compiles nothing; one
    program per logits shape, calibrator kind and criterion."""
    plan = OffloadPlan(p_tar=p_tar, calibrators=[state], criterion=criterion,
                       entropy_threshold=entropy_threshold)
    gate = plan.gate(exit_logits, branch=0, use_kernel=use_kernel)
    return gate.exit_mask, gate.prediction, gate.confidence


@jax.jit
def _take_rows(payload, idx):
    """Rows `idx` of every payload leaf (axis 0), one program per row count."""
    return jax.tree.map(lambda x: x[idx], payload)


class _Phases:
    """The phase clock of one engine call, a context manager.

    `to(phase)` takes one `perf_counter` stamp: the running phase ends and
    `phase` begins there, so consecutive phases tile the call from the
    first stamp to the last, which leaving the context takes. A phase's
    time is added to its EngineStats fields, and it runs inside the
    profiler span `offload.<phase>`, which records nothing without a
    profiler session.
    """

    def __init__(self, stats: EngineStats):
        self.stats = stats
        self.phase: Optional[str] = None
        self.t = 0.0
        self.spans: list = []  # (phase, open annotation), innermost last

    def to(self, phase: Optional[str]) -> None:
        t = time.perf_counter()
        if self.phase is not None:
            for f in _PHASES[self.phase][0]:
                setattr(self.stats, f, getattr(self.stats, f) + (t - self.t))
        parent = _PHASES[phase][1] if phase is not None else None
        while self.spans and self.spans[-1][0] != parent:
            self.spans.pop()[1].__exit__(None, None, None)
        if phase is not None:
            span = TraceAnnotation(f"offload.{phase}")
            span.__enter__()
            self.spans.append((phase, span))
        self.t, self.phase = t, phase

    def __enter__(self) -> "_Phases":
        return self

    def __exit__(self, *exc) -> None:
        self.to(None)


class OffloadEngine:
    """Generic two-tier engine over (edge_fn, cloud_fn) callables.

    edge_fn(batch)  -> {"exit_logits": (b, C), "payload": pytree}
    cloud_fn(payload_subset) -> {"logits": (m, C)}

    `branch` is the index (into plan.calibrators) of the exit the edge
    partition actually computes; defaults to plan.exit_index. use_kernel
    routes gating through the fused Pallas exit-gate kernel when the
    branch's calibration is pure temperature scaling.

    The engine is the per-batch compute core of the serving layer: the
    event-driven runtime (repro.serving.runtime) calls `edge_step` and
    `cloud_step` separately so queueing and transfer sit between them on
    the simulated clock. Both steps block until the device is done and
    accumulate wall-clock in EngineStats.
    """

    def __init__(
        self,
        edge_fn: Callable,
        cloud_fn: Callable,
        plan: OffloadPlan,
        payload_nbytes: Optional[Callable[[Any], int]] = None,
        branch: Optional[int] = None,
        use_kernel: bool = False,
    ):
        self.edge_fn = edge_fn
        self.cloud_fn = cloud_fn
        self.plan = plan
        self.branch = plan.exit_index if branch is None else branch
        if not 0 <= self.branch < plan.num_exits:
            raise ValueError(
                f"deployed branch index {self.branch} has no calibrator state "
                f"(plan covers {plan.num_exits} exit(s))"
            )
        self.use_kernel = use_kernel
        self.payload_nbytes = payload_nbytes or (
            lambda p: sum(x.nbytes for x in jax.tree.leaves(p))
        )
        self.stats = EngineStats()

    @property
    def policy(self) -> OffloadPlan:  # legacy name
        return self.plan

    # ------------------------------------------------------- timed steps
    def edge_step(self, batch) -> Dict[str, Any]:
        """Run the edge partition on one request batch (timed, blocking)."""
        with _Phases(self.stats) as clock:
            return self._edge(batch, clock)

    def cloud_step(self, payload) -> Dict[str, Any]:
        """Run the cloud partition on a refused-sample payload (timed)."""
        with _Phases(self.stats) as clock:
            return self._cloud(payload, clock)

    def _edge(self, batch, clock: _Phases) -> Dict[str, Any]:
        clock.to("edge")
        out = self.edge_fn(batch)
        clock.to("edge_wait")
        out = jax.block_until_ready(out)
        self.stats.edge_calls += 1
        return out

    def _cloud(self, payload, clock: _Phases) -> Dict[str, Any]:
        clock.to("cloud")
        out = self.cloud_fn(payload)
        clock.to("cloud_wait")
        out = jax.block_until_ready(out)
        self.stats.cloud_calls += 1
        return out

    def _gate(self, exit_logits):
        """(exit_mask, prediction, confidence) of the deployed branch: one
        program where the plan gates as `OffloadPlan.gate` does, else the
        plan's own gate (a subclass's or an instance's), called as it is."""
        plan = self.plan
        if getattr(plan.gate, "__func__", None) is not OffloadPlan.gate:
            g = plan.gate(exit_logits, branch=self.branch, use_kernel=self.use_kernel)
            return g.exit_mask, g.prediction, g.confidence
        return _exit_gate_step(exit_logits, plan.calibrators[self.branch], plan.p_tar,
                               plan.entropy_threshold, criterion=plan.criterion,
                               use_kernel=self.use_kernel)

    def infer(self, batch) -> Dict[str, np.ndarray]:
        with TraceAnnotation("offload.infer", batch=self.stats.edge_calls) as span, \
                _Phases(self.stats) as clock:
            edge_out = self._edge(batch, clock)
            clock.to("gate")
            gate = self._gate(edge_out["exit_logits"])
            clock.to("gate_sync")
            mask, pred, conf = jax.device_get(gate)
            self.stats.host_fetches += 1
            pred, conf = pred.copy(), conf.copy()
            b = mask.shape[0]
            on = int(mask.sum())
            self.stats.requests += b
            self.stats.on_device += on
            span.set_metadata(rows=b, refused=b - on)

            if on < b:
                clock.to("gather")
                idx = np.flatnonzero(~mask).astype(np.int32)
                payload = _take_rows(edge_out["payload"], idx)
                self.stats.offloaded += len(idx)
                level = int(getattr(self.plan, "compression_level", 0))
                if level != 0:
                    # the plan priced this deployment at the codec's wire
                    # bytes; ship the ACTUAL encoded payload (Pallas kernel,
                    # interpret mode off-TPU) and charge its analytic size
                    from repro.kernels import compress

                    clock.to("encode")
                    leaves, treedef = jax.tree.flatten(payload)
                    encs = [compress.encode(x, level) for x in leaves]
                    self.stats.payload_bytes += sum(e.nbytes for e in encs)
                    clock.to("decode")
                    payload = jax.tree.unflatten(
                        treedef, [compress.decode(e) for e in encs]
                    )
                else:
                    self.stats.payload_bytes += self.payload_nbytes(payload)
                cloud_out = self._cloud(payload, clock)
                clock.to("fetch")
                cloud_logits = np.asarray(cloud_out["logits"])
                self.stats.host_fetches += 1
                pred[idx] = np.argmax(cloud_logits, axis=-1)
                z = cloud_logits - cloud_logits.max(-1, keepdims=True)
                p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
                conf[idx] = p.max(-1)
        return {
            "prediction": pred,
            "confidence": conf,
            "on_device": mask,
        }


# ------------------------------------------------------- concrete bindings
def convnet_engine(params, plan: OffloadPlan, branch: int = 1,
                   use_kernel: bool = False) -> OffloadEngine:
    """The paper's system: B-AlexNet split at side branch `branch`.

    Physical branch k (1-based) gates with plan.calibrators[k-1] -- a plan
    calibrated per exit deploys any branch without re-fitting.
    """
    from repro.models import convnet

    @jax.jit
    def edge(batch):
        logits, hidden = convnet.edge_forward(params, batch["images"], branch=branch)
        return {"exit_logits": logits, "payload": hidden}

    @jax.jit
    def cloud(hidden):
        return {"logits": convnet.cloud_forward(params, hidden, from_branch=branch)}

    return OffloadEngine(edge, cloud, plan, branch=branch - 1, use_kernel=use_kernel)


@functools.partial(jax.jit, static_argnames=("cfg", "exit_index"))
def _lm_edge(params, batch, cfg, exit_index):
    """The LM's edge partition, weights as an argument: the exit's
    last-position logits and the (b, s, d) hidden state it ships."""
    from repro.models import transformer

    out = transformer.edge_forward(params, cfg, batch, exit_index=exit_index)
    return {"exit_logits": out["exit_logits"][:, 0, :], "payload": out["hidden"]}


@functools.partial(jax.jit, static_argnames=("cfg", "exit_index"))
def _lm_cloud(params, hidden, cfg, exit_index):
    """The LM's cloud partition, weights as an argument: last-position logits."""
    from repro.models import transformer

    out = transformer.cloud_forward(params, cfg, hidden, exit_index=exit_index)
    return {"logits": out["logits"][:, 0, :]}


def lm_engine(params, cfg, plan: OffloadPlan, exit_index: int = 0,
              use_kernel: bool = False) -> OffloadEngine:
    """LM variant: classify-at-prefill; edge = blocks up to the exit.

    Both partitions take the weights as an argument (bound here with
    `functools.partial`), so no program embeds them as constants and one
    compiled program serves any weights of the same configuration."""
    edge = functools.partial(_lm_edge, params, cfg=cfg, exit_index=exit_index)
    cloud = functools.partial(_lm_cloud, params, cfg=cfg, exit_index=exit_index)
    return OffloadEngine(edge, cloud, plan, branch=exit_index, use_kernel=use_kernel)
