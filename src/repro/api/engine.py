"""``repro.api.fit`` — one entry point for every distributed trainer.

    fit(strategy, data, transport=..., wire=..., executor=..., schedule=...)

runs any (strategy × transport × wire) combination on a chosen executor
(`local` stacked scan / `mesh` shard_map placement / `multipod`
hierarchical pod placement with per-hop ledger pricing / `sweep` vmapped
scenario batch / composed `mesh+sweep` & `multipod+sweep` scenario vmaps
nested inside the shard placement — see ``repro.api.executor`` and
``docs/EXECUTORS.md``) inside one jit/scan-able engine and returns a
uniform ``FitResult``.  The engine owns what every
historical entry point used to reimplement by hand: the scan loop (via
the transport + executor), message encoding (via the wire), and
``CommLedger`` byte accounting (materialized here from the per-round
byte counts the transport/wire emitted).

``FitResult`` fields:

* ``theta``       — the final parameter (or model pytree, for strategies
  whose ``finalize`` builds one);
* ``trajectory``  — per-round trace: the handed-back θ for server
  transports, the strategy's ``round_metric`` for update transports, the
  residual history for admm_consensus;
* ``ledger``      — byte-accurate ``CommLedger`` under the paper's strict
  client-server cost model (a LIST of per-scenario ledgers under the
  sweep executor);
* ``metrics``     — the strategy's summary dict, plus engine extras:
  ``uplink_bytes_per_round`` / ``downlink_bytes_per_round`` (numpy),
  transport extras (e.g. the full ``ADMMResult``), and ``carry`` — an
  opaque resume token accepted by a later ``fit(..., carry=...)``.

Under the sweep executor every result field gains a leading S (scenario)
axis: ``theta`` is (S, …), ``trajectory`` is (S, T), the per-round byte
arrays are (S, T), and ``ledger`` is a list of S ``CommLedger``s.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import numpy as np

from repro.core.allreduce import CommLedger
from repro.api.executor import Executor, make_executor
from repro.api.faults import FaultPlan, make_fault_plan
from repro.api.strategy import Strategy
from repro.api.transport import Transport, make_transport
from repro.api.wire import Wire, make_wire
from repro.telemetry import trace as _trace

PyTree = Any


def _jsonable(v, _size_cap: int = 100_000):
    """Best-effort JSON conversion: primitives pass, arrays become lists
    (or a shape/dtype placeholder past ``_size_cap`` elements), anything
    else becomes ``"<TypeName>"``."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if hasattr(v, "dtype") and hasattr(v, "shape"):  # numpy / jax array
        arr = np.asarray(v)
        if arr.ndim == 0:
            return arr.item()
        if arr.size > _size_cap:
            return f"<ndarray shape={arr.shape} dtype={str(arr.dtype)}>"
        return arr.tolist()
    return f"<{type(v).__name__}>"


class FitResult(NamedTuple):
    theta: PyTree
    trajectory: PyTree
    ledger: CommLedger | list
    metrics: dict

    def metrics_json(self) -> dict:
        """``metrics`` as a JSON-serializable dict: drops the opaque
        ``"carry"`` resume token, converts arrays to lists, and strings
        anything non-serializable (e.g. ``"serve_engine"`` →
        ``"<ServeEngine>"``).  This is what ``benchmarks/`` writers and
        ``RunReport`` persist."""
        return {
            k: _jsonable(v) for k, v in self.metrics.items() if k != "carry"
        }


def _total(a: np.ndarray) -> int:
    """Exact byte total: int64 accumulation for integer counts, f64 for
    the (small) value-dependent traced counts."""
    if np.issubdtype(a.dtype, np.integer):
        return int(a.sum(dtype=np.int64))
    return int(round(float(a.sum(dtype=np.float64))))


def fit(
    strategy: Strategy,
    data: PyTree = None,
    *,
    transport: str | Transport = "sequential_server",
    wire: str | Wire = "dense",
    executor: str | Executor = "local",
    sweep: dict | None = None,
    schedule=None,
    steps: int | None = None,
    stream: PyTree = None,
    theta0: PyTree = None,
    carry=None,
    faults: FaultPlan | None = None,
    tag: str = "fit",
    tracer=None,
    trace: str | None = None,
    **transport_options,
) -> FitResult:
    """Train ``strategy`` on ``data`` under a transport, a wire and an
    executor.

    Args:
      strategy: the per-node learner F^(k) (see ``repro.api.strategy``).
      data: fixed sharded data (leading node axis), or None for stream- or
        closure-based strategies.
      transport: one of ``sequential_server`` / ``stale_server`` /
        ``delay_line`` / ``allreduce`` / ``admm_consensus``, or a
        ``Transport`` instance.
      wire: ``"dense"``, ``"topk:<f>[+ef]"``, ``"thresh:<τ>[+ef]"``,
        ``"int8[+ef]"``, the privacy wires ``"dp:<clip>,<sigma>"`` /
        ``"secagg"``, a ``">"``-chain of those
        (``"dp:1.0,0.5>topk:0.1+ef"``), or a ``Wire``.
      executor: ``"local"`` (stacked scan), ``"mesh"`` / ``"multipod"``
        (shard_map node placement; or a configured ``MeshExecutor(mesh)``
        / ``MultiPodExecutor(mesh, ...)``), an
        ``api.SweepExecutor({...}, inner=...)`` scenario batch, or the
        composed spec strings ``"sweep"`` / ``"mesh+sweep"`` /
        ``"multipod+sweep"`` whose scenario values arrive via ``sweep=``.
        See ``docs/EXECUTORS.md`` for the compatibility matrix.
      sweep: scenario parameters for the string sweep specs, e.g.
        ``fit(..., executor="mesh+sweep", sweep={"lr": jnp.asarray(
        [0.02, 0.1])})`` — same keys ``api.SweepExecutor`` accepts.
      schedule: int32 contact schedule (server transports; see
        ``repro.core.schedules``).
      steps: number of rounds (update/consensus transports).
      stream: optional pytree with a leading time axis scanned as the
        per-round batch (update transports).
      theta0: initial parameter; defaults to ``strategy.init_theta(data)``.
      carry: resume token from a previous ``FitResult.metrics["carry"]``.
      faults: optional ``repro.api.faults.FaultPlan`` — seeded per-round
        node dropout / straggler lag / quorum model threaded through the
        transport as masked participation (see ``docs/FAULTS.md``).
        ``sweep={"dropout_p": ...}`` sweeps the plan's threshold against
        its shared draws.
      tracer: optional ``repro.telemetry.trace.Tracer``.  Installed as
        the ambient tracer for the whole run, so the engine's loop /
        ledger spans, the executors' dispatch + program-cache spans, and
        (under ``executor="serve"``) the serving engine's spans all land
        on one timeline.  All spans are host-side: a traced fit runs the
        same compiled program and returns bit-identical results
        (``tests/test_trace.py``).  Without one, the ambient tracer
        (``trace.activated``), if any, records the run; with neither,
        zero overhead.  The whole call is a ``fit/call`` span, the
        parent of ``fit/loop``, ``fit/ledger`` and ``fit/metrics``.
      trace: ``"phases"`` (requires ``tracer``) additionally recovers
        per-phase DEVICE timings — local-step, wire encode, per-hop
        collective, stats completion — by replaying standalone
        ``jax.block_until_ready``-fenced probe programs at the run's
        real shapes AFTER the fit completes.  The fit program itself is
        untouched, so ``trace="phases"`` is bit-exact by construction.
      transport_options: transport-specific (``staleness=...`` for
        delay_line; ``rho``/``g``/``g_lam`` for admm_consensus).
    """
    if trace not in (None, "phases"):
        raise ValueError(f"trace must be None or 'phases', got {trace!r}")
    if trace == "phases" and tracer is None:
        raise ValueError("trace='phases' requires a tracer=Tracer()")
    with _trace.activated(tracer), _trace.span("fit/call", tag=tag):
        return _fit_traced(
            strategy, data, wire=wire, transport=transport,
            executor=executor, sweep=sweep, schedule=schedule, steps=steps,
            stream=stream, theta0=theta0, carry=carry, faults=faults,
            tag=tag, tracer=tracer, trace=trace,
            transport_options=transport_options,
        )


def _fit_traced(
    strategy, data, *, wire, transport, executor, sweep, schedule, steps,
    stream, theta0, carry, faults, tag, tracer, trace, transport_options,
) -> FitResult:
    w = make_wire(wire)
    tr = make_transport(transport, **transport_options)
    ex = make_executor(executor, sweep_params=sweep)
    plan = make_fault_plan(faults)
    with _trace.span(
        "fit/loop", transport=tr.name, wire=w.name, executor=ex.name, tag=tag
    ):
        raw = tr.run(
            strategy, data,
            wire=w, schedule=schedule, steps=steps, stream=stream,
            theta0=theta0, carry=carry, executor=ex, faults=plan,
        )
        if _trace.current_tracer() is not None:
            # fence so the loop span covers device completion, not just
            # async dispatch — a pure wait, results unchanged
            jax.block_until_ready(raw.theta)

    if trace == "phases":
        from repro.telemetry import phases as _phases  # lazy: jax-heavy

        _phases.profile_phases(
            tracer, strategy, data,
            wire=w, transport=tr, executor=ex,
            schedule=schedule, steps=steps, stream=stream, theta0=theta0,
        )

    ups = np.asarray(raw.uplink)
    downs = np.asarray(raw.downlink)
    # topology-aware executors decompose the flat totals by reduction
    # tier (intra-pod vs inter-pod), priced per hop — same totals, now
    # attributed to the link each byte crossed
    hop_split = ex.ledger_hops(strategy, data)

    def materialize(u: np.ndarray, d: np.ndarray, suffix: str = "") -> CommLedger:
        led = CommLedger()
        if strategy.init_rounds and carry is None:
            K = strategy.num_nodes(data)
            theta_like = (
                ex.scenario_template(raw.theta) if theta0 is None else theta0
            )
            for _ in range(strategy.init_rounds):
                led.record_allreduce(theta_like, K, tag=f"{tag}/init")
        T = int(u.shape[0])
        up_tot, down_tot = _total(u), _total(d)
        led.uplink_bytes += up_tot
        led.downlink_bytes += down_tot
        led.rounds += raw.rounds_per_step * T
        led.events.append(
            (raw.event_kind, f"{tag}{suffix}[0:{T}]", up_tot + down_tot)
        )
        if hop_split:
            led.attribute_hops(hop_split)
        return led

    S = ex.num_scenarios
    with _trace.span("fit/ledger", scenarios=S):
        if S is None:
            ledger = materialize(ups, downs)
        else:
            ledger = [
                materialize(ups[s], downs[s], f"/s{s}") for s in range(S)
            ]
    with _trace.span("fit/metrics"):
        if S is None:
            metrics = dict(strategy.summary(raw.theta, data))
        else:
            try:
                batched = jax.vmap(lambda th: strategy.summary(th, data))(
                    raw.theta
                )
                metrics = {k: np.asarray(v) for k, v in batched.items()}
            except Exception:  # summaries need not be vmappable — skip
                metrics = {}
    metrics.update(raw.extras)
    metrics["uplink_bytes_per_round"] = ups
    metrics["downlink_bytes_per_round"] = downs
    metrics["transport"] = tr.name
    metrics["wire"] = w.name
    metrics["executor"] = ex.name
    metrics["carry"] = raw.carry
    if hasattr(w, "kernel_report"):
        # which leaves the wire's Pallas kernels actually covered vs the
        # <256/non-f32 fallback — no more silent fallbacks
        metrics["wire_kernel_hits"] = w.kernel_report(
            ex.scenario_template(raw.theta)
        )
    metrics.update(ex.extra_metrics())  # e.g. ServingExecutor's live engine
    return FitResult(
        theta=raw.theta, trajectory=raw.trajectory, ledger=ledger, metrics=metrics
    )
