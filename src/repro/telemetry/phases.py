"""Per-phase DEVICE timings for ``fit(..., trace="phases")``.

A host-side span around an async-dispatched jax program measures
submission, not execution — and fencing *inside* the fit's scan would
split the compiled program (different fusion, different numerics risk).
So ``trace="phases"`` never touches the fit program at all: after the
(bit-exact, untouched) fit completes, :func:`profile_phases` replays the
round's constituent phases as **standalone** jitted probe programs at
the run's real shapes, each compiled+warmed first and then timed once
under a ``jax.block_until_ready`` fence inside its span:

* ``phase/local_step``      — per-node grads + stack reduce + apply: the
  compute floor every executor shares;
* ``phase/encode``          — the wire's stacked encode (top-k select /
  quantize / EF residual) on the run's own first-round messages;
* ``hop/<name>``            — one span per reduction hop of the mesh /
  multipod topology (``intra_pod``, ``inter_pod``, ``flat``): a
  shard_map'd scan reducing the message shape over just that hop via
  ``hierarchical_allreduce`` — what placement itself adds, per link;
* ``phase/stats_completion`` — the deferred ``metric_mean`` completion
  (a trajectory-shaped pmean over the node axis).

This mirrors the probe methodology of ``benchmarks/bench_fit_executors``
(phase decomposition) and ``benchmarks/bench_multipod`` (per-hop loops),
promoted into the library so every traced fit can carry its own
attribution.  Each probe scans ``steps`` rounds, so span durations are
directly comparable to the ``fit/loop`` span.

Probes are best-effort: a strategy/executor combination a probe doesn't
apply to (non-stacked messages, closure data, indivisible placement)
skips that probe, bumps the ``phases/skipped`` counter, and leaves the
rest of the report intact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.allreduce import hierarchical_allreduce, mesh_allreduce

__all__ = ["profile_phases"]


def _fenced(tracer, name, prog, *args, **tags):
    """Compile+warm ``prog`` outside the span, then time one fenced call
    inside it.  Any failure marks the probe skipped instead of failing
    the fit."""
    try:
        jax.block_until_ready(prog(*args))
        with tracer.span(name, **tags):
            jax.block_until_ready(prog(*args))
        return True
    except Exception as e:  # probe inapplicable — record why, move on
        tracer.count("phases/skipped")
        tracer.gauge(f"{name}/skipped", type(e).__name__)
        return False


def _tree_reduce_stack(msgs, op: str):
    red = jnp.mean if op == "mean" else jnp.sum
    return jax.tree.map(lambda m: red(m, axis=0), msgs)


def _consume(tree):
    """Scalar folding every leaf, so scanned probe outputs defeat DCE."""
    return sum(jnp.sum(leaf) for leaf in jax.tree.leaves(tree))


def profile_phases(
    tracer, strategy, data, *,
    wire, transport, executor,
    schedule=None, steps=None, stream=None, theta0=None,
) -> None:
    """Record the per-phase probe spans for one fit configuration (see
    module docstring).  Called by ``api.fit`` when ``trace="phases"``."""
    from repro.api.executor import MeshExecutor, SweepExecutor

    if isinstance(executor, SweepExecutor):
        executor = executor.inner  # probe one scenario's placement
    if steps is not None:
        T = int(steps)
    elif schedule is not None:
        T = int(jnp.shape(jnp.asarray(schedule))[0])
    else:
        T = 1
    tname = getattr(transport, "name", str(transport))

    theta = theta0 if theta0 is not None else strategy.init_theta(data)
    try:
        state = strategy.init_state(theta, data)
    except Exception:
        state = ()
    batch = None if stream is None else jax.tree.map(lambda s: s[0], stream)
    op = strategy.aggregate_op

    # -- phase/local_step: grads + stack reduce + apply, no wire, no mesh
    msgs = None
    if strategy.stacked_msgs:
        try:
            msgs, _ = strategy.local_updates(theta, state, data, batch)
        except Exception:
            tracer.count("phases/skipped")
            tracer.gauge("phase/local_step/skipped", "local_updates")
        if msgs is not None:

            def local_prog(th, st, d):
                def step(c, _):
                    th1, st1 = c
                    m, st2 = strategy.local_updates(th1, st1, d, batch)
                    th2, st3 = strategy.apply_update(
                        th1, _tree_reduce_stack(m, op), st2, d
                    )
                    return (th2, st3), ()

                return _consume(
                    jax.lax.scan(step, (th, st), None, length=T)[0]
                )

            _fenced(
                tracer, "phase/local_step", jax.jit(local_prog),
                theta, state, data, steps=T, transport=tname,
            )

    # -- phase/encode: the wire's stacked encode at the real message shape
    if msgs is not None:
        try:
            K = strategy.num_nodes(data)
            wstate = wire.init_state(theta, K, stacked=True)
        except Exception:
            wstate = None
            tracer.count("phases/skipped")
            tracer.gauge("phase/encode/skipped", "init_state")
        if wstate is not None:

            def encode_prog(w0, m):
                def step(c, _):
                    ws, acc = c
                    ws, m_hat, _up = wire.encode_updates(ws, m, stacked=True)  # reprolint: disable=ledger-completeness -- timing probe; the traced fit already accounted these bytes
                    return (ws, acc + _consume(m_hat)), ()

                return jax.lax.scan(
                    step, (w0, jnp.zeros(())), None, length=T
                )[0]

            _fenced(
                tracer, "phase/encode", jax.jit(encode_prog),
                wstate, msgs, steps=T, wire=wire.name,
            )

    # -- hop/<name> + phase/stats_completion: mesh placements only
    if not isinstance(executor, MeshExecutor) or msgs is None:
        return
    try:
        r = executor.resolve()
    except Exception:
        tracer.count("phases/skipped")
        tracer.gauge("hop/skipped", "resolve")
        return

    def hop_loop(hop):
        def body(v):
            one = jax.tree.map(lambda x: x[0], v)

            def step(c, _):
                red = hierarchical_allreduce(one, [hop], op="sum")
                return jax.tree.map(jnp.add, c, red), ()

            z = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype), v)
            return jax.lax.scan(step, z, None, length=T)[0]

        return jax.jit(shard_map(
            body, mesh=r.mesh, in_specs=P(r.axis), out_specs=P(),
            check_vma=False,
        ))

    for hop in r.topology.hops:
        _fenced(
            tracer, f"hop/{hop.name}", hop_loop(hop), msgs,
            axes="+".join(hop.axes), steps=T,
        )

    # the deferred metric_mean completion: a (T,)-per-node pmean
    def stats_body(v):
        return mesh_allreduce(jnp.sum(v, axis=0), r.axis, op="mean")

    stats_prog = jax.jit(shard_map(
        stats_body, mesh=r.mesh, in_specs=P(r.axis), out_specs=P(),
        check_vma=False,
    ))
    K = strategy.num_nodes(data)
    _fenced(
        tracer, "phase/stats_completion", stats_prog,
        jnp.ones((K, T)), steps=T,
    )
