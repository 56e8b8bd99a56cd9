"""``ServeEngine`` — the inference half of the train→serve executor swap.

A fit produces ``FitResult.theta``; the same ``Strategy`` that trained it
knows how to answer requests with it (``Strategy.predict``).  The engine
owns everything WHERE-shaped about serving, mirroring what the training
executors own for fitting:

* **placement** — given a mesh, parameters are sharded on the model axis
  via ``sharding/rules.partition_params`` (the ROADMAP's serving-executor
  note) and request batches on the data axes; without one, everything
  stays local and replicated;
* **compilation** — jittable predicts are compiled once per request
  shape with the request buffer donated (the response reuses it);
  strategies that drive their own decode loop (``predict_jit = False``,
  e.g. LM prefill+decode) are called eagerly;
* **hot-swap** — ``swap(theta)`` atomically replaces the served
  parameters (same placement, no recompile when shapes are unchanged),
  which is what the registry's publish→activate path calls into;
* **accounting** — every answered batch is metered through
  ``ServeMetrics``/``CommLedger`` as a priced ``inference`` message
  (request features up, predictions down), extending the paper's
  client-server cost model from training to deployment traffic.
"""

from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import batch_axes, data_axis_size
from repro.serve.metrics import ServeMetrics
from repro.sharding.rules import auto_axes, place_params
from repro.telemetry import trace as _trace

PyTree = Any


class ServeEngine:
    """Serve a finalized model through its strategy's ``predict``.

    Args:
      strategy: the Strategy that produced (or can interpret) ``theta``.
      theta: finalized parameters — ``FitResult.theta`` or a registry load.
      mesh: optional ``jax.sharding.Mesh``; parameters go on
        ``model_axis`` (+ optional ``fsdp_axis``) per the name-based
        partition rules, request batches on the mesh's data axes.
      donate: donate the request buffer to the compiled predict so XLA
        can reuse it for the response (jittable strategies only).
      metrics: a shared ``ServeMetrics`` (one per deployment); fresh by
        default.
      tag: ledger event tag for this engine's inference traffic.
      tracer: optional ``repro.telemetry.trace.Tracer`` recording
        ``serve/predict`` and ``serve/swap`` spans; defaults to the
        ambient tracer at construction (so ``fit(..., executor="serve",
        tracer=...)`` traces its engine automatically).  None → no
        tracing, zero overhead.
    """

    def __init__(
        self,
        strategy,
        theta: PyTree,
        *,
        mesh: Mesh | None = None,
        model_axis: str = "model",
        fsdp_axis: str | None = None,
        donate: bool = True,
        metrics: ServeMetrics | None = None,
        tag: str = "serve",
        tracer=None,
    ):
        self.strategy = strategy
        self.mesh = auto_axes(mesh) if mesh is not None else None
        self.model_axis = model_axis
        self.fsdp_axis = fsdp_axis
        self.tag = tag
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.tracer = tracer if tracer is not None else _trace.current_tracer()
        self._lock = threading.Lock()
        self._batch_axes = batch_axes(mesh) if mesh is not None else ()
        self._batch_mul = data_axis_size(mesh) if mesh is not None else 1
        if strategy.predict_jit:
            # CPU never reuses donated buffers and warns per compile
            donate = donate and jax.default_backend() != "cpu"
            donate_args = (1,) if donate else ()
            self._fn = jax.jit(
                lambda th, X: strategy.predict(th, X),
                donate_argnums=donate_args,
            )
            self._donate = donate
        else:
            self._fn = strategy.predict
            self._donate = False
        self.theta = None
        self.swap(theta)

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_fit(cls, result, strategy, **kw) -> "ServeEngine":
        """Stand a finished ``api.fit`` up for inference (its ``theta`` is
        already finalized)."""
        return cls(strategy, result.theta, **kw)

    @classmethod
    def from_registry(
        cls, registry, name: str, strategy, *, version: int | None = None,
        like: PyTree = None, **kw,
    ) -> "ServeEngine":
        """Serve a published model; ``like`` restores non-dict pytrees
        (NamedTuple thetas) into their original structure."""
        return cls(strategy, registry.load(name, version, like=like), **kw)

    # -- placement -----------------------------------------------------------

    def _place(self, theta: PyTree) -> PyTree:
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, theta)
        return place_params(
            self.mesh, theta,
            model_axis=self.model_axis, fsdp_axis=self.fsdp_axis,
        )

    def _place_request(self, X: jnp.ndarray) -> jnp.ndarray:
        if self.mesh is None or not self._batch_axes:
            return X
        axes = (
            self._batch_axes
            if len(self._batch_axes) > 1
            else self._batch_axes[0]
        )
        return jax.device_put(X, NamedSharding(self.mesh, P(axes)))

    # -- serving -------------------------------------------------------------

    def swap(self, theta: PyTree) -> None:
        """Atomically replace the served parameters (registry hot-swap).
        Same pytree structure required; same shapes reuse the compiled
        predict, changed shapes recompile on the next request."""
        if self.theta is not None:
            old = jax.tree_util.tree_structure(self.theta)
            new = jax.tree_util.tree_structure(theta)
            if old != new:
                raise ValueError(
                    f"swap() needs the served pytree structure {old}, got {new}"
                )
        tr = self.tracer
        with tr.span("serve/swap") if tr is not None else nullcontext():
            placed = self._place(theta)
            with self._lock:
                self.theta = placed

    def predict(self, X, *, valid: int | None = None) -> jnp.ndarray:
        """Answer one request batch.

        ``X`` rows are independent requests; ``valid`` marks how many
        leading rows are real (the batcher's bucket padding) — only those
        are returned and metered.  The engine may pad the batch further to
        a device multiple under a mesh; that padding never leaves it.
        """
        caller_owns = isinstance(X, jax.Array)
        X = jnp.asarray(X)
        n = X.shape[0] if valid is None else valid
        # metering needs only shapes — a struct stays valid after the
        # request buffer is donated
        req_ref = jax.ShapeDtypeStruct((n,) + X.shape[1:], X.dtype)
        Xp = X
        pad = (-Xp.shape[0]) % self._batch_mul
        if pad:
            Xp = jnp.concatenate(
                [Xp, jnp.broadcast_to(Xp[-1:], (pad,) + Xp.shape[1:])]
            )
        elif self._donate and caller_owns:
            # host inputs (the batcher path) already produced a fresh
            # device buffer via asarray; only a caller's live jax array
            # must be copied before donation invalidates it
            Xp = jnp.array(X)
        Xp = self._place_request(Xp)
        with self._lock:
            theta = self.theta
        tr = self.tracer
        t0 = time.perf_counter()
        with (
            tr.span("serve/predict", batch=int(Xp.shape[0]), valid=int(n))
            if tr is not None else nullcontext()
        ):
            Y = self._fn(theta, Xp)
            Y = jax.block_until_ready(Y)
        dt = time.perf_counter() - t0
        if tr is not None:
            tr.count("serve/requests", n)
            tr.count("serve/padded_slots", int(Xp.shape[0]) - int(n))
        Y = jax.tree.map(lambda y: y[:n], Y)
        self.metrics.record_batch(
            n, Xp.shape[0], dt, req_ref, Y, tag=self.tag
        )
        return Y

    @property
    def ledger(self):
        return self.metrics.ledger

    def stats(self) -> dict:
        return self.metrics.summary()
