"""Transport layer — WHO talks to whom, and when.

Each transport is one client-server topology from the paper, wrapping the
corresponding ``repro.core`` primitive:

* ``sequential_server`` — the §5 central information server with the
  sequential handoff (round-robin ≡ mini-batch GD equivalence); wraps
  ``core.server``.
* ``stale_server``      — the literal §5 protocol text: the pusher
  receives θ_{t-1}; wraps ``core.server``.
* ``allreduce``         — the two-phase central-server Allreduce of §3.1
  ([47]/[5]); wraps ``core.allreduce``.
* ``delay_line``        — the §5 algorithm mapped to SPMD: the aggregated
  update is applied D steps late; wraps ``core.staleness``.
* ``admm_consensus``    — global-variable-consensus ADMM (three-stage
  Douglas-Rachford, two Allreduces per iteration); wraps ``core.admm``.

A transport's ``run`` builds the per-round step; it calls back into the
strategy for local computation, into the wire for message encoding and
byte metering, and into the executor-provided primitive set
(``repro.api.executor``: ``aggregate`` / ``broadcast`` / ``metric_mean`` /
``sum_bytes`` — and, for the server family, ``local_node`` /
``from_owner`` / ``commit_owner``) for everything that depends on WHERE
the nodes live — the executor owns the loop placement (stacked scan,
``shard_map``'d scan, vmapped scenario sweep, or shard_map(vmap(scan))
for the composed ``mesh+sweep``) and returns what the transport wraps
into a ``RawRun`` for the engine.  See ``docs/EXECUTORS.md`` for the
Transport × Executor compatibility matrix.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import executor as _exec
from repro.api.faults import FaultCarry
from repro.api.strategy import Strategy
from repro.core.admm import consensus_admm
from repro.core.server import contact, init_server
from repro.core.staleness import (
    DelayLine,
    delay_init,
    delay_push_pop,
    delay_push_read,
)

PyTree = Any


class RawRun(NamedTuple):
    theta: PyTree
    state: Any
    trajectory: PyTree
    uplink: jnp.ndarray  # (T,) per-round uplink bytes
    downlink: jnp.ndarray  # (T,) per-round downlink bytes
    rounds_per_step: int  # ledger rounds charged per loop step
    event_kind: str  # ledger event tag ("contact" / "allreduce" / ...)
    extras: dict
    carry: Any  # opaque resume state


class Transport:
    name = "transport"

    def run(
        self, strategy, data, *, wire, schedule, steps, stream, theta0, carry,
        executor, faults=None,
    ) -> RawRun:
        raise NotImplementedError


def _resolve_theta0(strategy, data, theta0):
    return strategy.init_theta(data) if theta0 is None else theta0


def _unwrap_fault_carry(carry, faults, name):
    """Split a resume carry into (inner carry, plan round offset) —
    faulted fits wrap their carry in a :class:`FaultCarry` so the draw
    stream resumes where it stopped; mixing faulted and fault-free
    carries is a usage error, not something to guess through."""
    if faults is None:
        if isinstance(carry, FaultCarry):
            raise ValueError(
                f"transport {name!r}: carry= comes from a faults= fit — "
                "pass the same FaultPlan to resume it"
            )
        return carry, 0
    if carry is None:
        return None, 0
    if not isinstance(carry, FaultCarry):
        raise ValueError(
            f"transport {name!r}: resuming under faults= needs the carry "
            "of a faulted fit (a FaultCarry); this one is from a "
            "fault-free fit"
        )
    return carry.inner, int(carry.next_round)


class ServerTransport(Transport):
    """The §5 central information server under a contact schedule.

    The per-contact step is written against the executor primitive set,
    so the same program places anywhere ``run_server`` can put it: on
    the local executor ``local_node``/``from_owner``/``commit_owner``
    are identities and the walk is the historical sequential scan
    (bit-exact with ``core.server.run_protocol``); on a mesh executor
    the schedule stays sequential but each contact's ``local_step``
    runs on the shard OWNING the contacted node — every shard traces
    the step masked, the owner's push is replicated by one ``psum``,
    and per-node wire state commits owner-only::

        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (Xs, ys),
                      transport="sequential_server",
                      schedule=schedules.round_robin(K, rounds),
                      executor="mesh")     # local ≡ mesh, bit-exact
    """

    def __init__(self, handoff: str):
        if handoff not in ("sequential", "stale"):
            raise ValueError(f"unknown handoff {handoff!r}")
        self.handoff = handoff
        self.name = (
            "sequential_server" if handoff == "sequential" else "stale_server"
        )

    def run(self, strategy, data, *, wire, schedule, steps, stream, theta0, carry,
            executor, faults=None):
        if schedule is None:
            raise ValueError(
                f"transport {self.name!r} needs a contact schedule= "
                "(see repro.core.schedules)"
            )
        K = strategy.num_nodes(data)
        carry, t0 = _unwrap_fault_carry(carry, faults, self.name)
        if faults is not None:
            if faults.straggler > 0 or faults.quorum is not None:
                raise ValueError(
                    f"transport {self.name!r} contacts ONE node per round — "
                    "straggler/quorum fault modes only apply to update "
                    "transports (allreduce/delay_line); use dropout_p alone"
                )
        if carry is None:
            theta0 = _resolve_theta0(strategy, data, theta0)
            carry = (
                init_server(theta0),
                strategy.init_state(theta0, data),
                wire.init_state(theta0, K, stacked=True),
            )
        theta_template = carry[0].theta
        handoff = self.handoff
        down_const = wire.measure(theta_template)  # dense θ handed back
        static_up = wire.push_bytes(theta_template)
        if faults is not None and static_up is None:
            raise ValueError(
                f"faults= with wire {wire.name!r}: per-contact survivor "
                "accounting needs a shape-static push cost "
                "(wire.push_bytes); value-dependent wires (thresh) are "
                "not supported under faults"
            )
        # shape-static push cost → the per-contact owner-select psum on the
        # byte scalar is pure overhead; emit a placeholder instead (replaced
        # by exact integer accounting below)
        skip_up = static_up is not None
        T = len(schedule)
        if faults is not None:
            draws = faults.draws(t0, T, K)
            xs = (np.asarray(schedule), draws.u)
        else:
            xs = schedule

        def make_step(shard_data):
            """Per-contact step over whatever node slice the executor
            placed here (the full stack locally, a shard under a mesh)."""

            def step(c, xt):
                server, sstate, wstate = c
                if faults is not None:
                    k, u_t = xt
                    # contacted node answers iff its uniform clears the
                    # (possibly swept/traced) dropout threshold
                    alive = u_t[k] >= faults.dropout_p
                else:
                    k = xt
                theta_start = (
                    server.theta if handoff == "sequential"
                    else server.theta_prev
                )
                k_loc, mine = _exec.local_node(k)
                # masked compute: every shard traces the pusher's local
                # run at its own (clamped) slice index; only the owner's
                # result is real.  The strategy state stays replicated
                # (see MeshExecutor.run_server), so it is NOT selected.
                theta_new, sstate_new = strategy.local_step(
                    k_loc, theta_start, sstate, shard_data
                )
                wstate_new, theta_push, up = wire.encode_push(
                    wstate, k_loc, theta_start, theta_new
                )
                theta_push = _exec.from_owner(theta_push, mine)
                up = jnp.zeros(()) if skip_up else _exec.from_owner(up, mine)
                if faults is not None:
                    # dead contact: the round is a no-op — the server keeps
                    # its state, the node's wire state does not commit, and
                    # the trajectory records the unchanged θ
                    server_new, received_new = contact(
                        server, theta_push, handoff=handoff
                    )
                    received = jax.tree.map(
                        lambda n, o: jnp.where(alive, n, o),
                        received_new, server.theta,
                    )
                    server = jax.tree.map(
                        lambda n, o: jnp.where(alive, n, o), server_new, server
                    )
                    sstate = jax.tree.map(
                        lambda n, o: jnp.where(alive, n, o), sstate_new, sstate
                    )
                    wstate = _exec.commit_owner(wstate_new, wstate, mine & alive)
                else:
                    sstate = sstate_new
                    wstate = _exec.commit_owner(wstate_new, wstate, mine)
                    server, received = contact(
                        server, theta_push, handoff=handoff
                    )
                return (server, sstate, wstate), (received, up)

            return step

        st_tok = strategy.cache_token()
        cache_key = None
        if st_tok is not None:
            cache_key = (
                "server", handoff, st_tok, wire.cache_token(), skip_up,
                strategy.num_nodes(data),
            )
            if faults is not None:
                cache_key += (faults.cache_token(),)
        (server, sstate, wstate), (traj, ups) = executor.run_server(
            strategy=strategy, data=data, carry=carry, make_step=make_step,
            schedule=xs, wire=wire, cache_key=cache_key,
        )
        theta = executor.finalize(strategy, server.theta, sstate, data)
        if faults is not None:
            # exact host-side survivor accounting: the draws and schedule
            # are host arrays, so the per-contact byte stream never enters
            # the compiled step — a dropped contact costs nothing up or down
            alive_np = (
                draws.u[np.arange(T), np.asarray(schedule)]
                >= faults.dropout_p
            )
            ups = alive_np.astype(np.int64) * static_up
            downs = alive_np.astype(np.int64) * down_const
        else:
            if static_up is not None:
                # exact integer accounting — large models overflow f32
                # mantissas
                ups = np.full((T,), static_up, dtype=np.int64)
            downs = np.full((T,), down_const, dtype=np.int64)
        out_carry = (server, sstate, wstate)
        if faults is not None:
            out_carry = FaultCarry(inner=out_carry, next_round=t0 + T)
        return RawRun(
            theta=theta,
            state=sstate,
            trajectory=traj,
            uplink=ups,
            downlink=downs,
            rounds_per_step=1,
            event_kind="contact",
            extras={"faults": faults.describe()} if faults is not None else {},
            carry=out_carry,
        )


class UpdateTransport(Transport):
    """Synchronous Allreduce (staleness=0) or the bounded-staleness delay
    line (staleness=D>0): every round all nodes push an update message;
    the aggregate is applied — possibly D rounds late.

    Every round all nodes work, so the loop places on EVERY executor:
    the stacked scan, the mesh/multipod shard_map, the scenario sweep,
    and the composed ``mesh+sweep`` (a swept ``"staleness"`` supersedes
    the transport's own D — one depth-max(D) delay line shared by all
    scenarios, read at a batched per-scenario index)::

        api.fit(strategy, data, transport="allreduce", steps=100)
        api.fit(strategy, data, transport="delay_line", staleness=2,
                steps=100, executor="mesh")
    """

    def __init__(self, staleness: int = 0):
        if staleness < 0:
            raise ValueError("staleness must be >= 0")
        self.staleness = staleness
        self.name = "allreduce" if staleness == 0 else "delay_line"

    def run(self, strategy, data, *, wire, schedule, steps, stream, theta0, carry,
            executor, faults=None):
        K = strategy.num_nodes(data)
        if stream is not None:
            T = jax.tree.leaves(stream)[0].shape[0]
        elif steps is not None:
            T = steps
        else:
            raise ValueError(
                f"transport {self.name!r} needs steps= or a stream= with a "
                "leading time axis"
            )
        carry, t0 = _unwrap_fault_carry(carry, faults, self.name)
        p_sweep = executor.swept("dropout_p")
        if faults is None:
            if p_sweep is not None:
                raise ValueError(
                    "sweep={'dropout_p': ...} needs faults=FaultPlan(...) — "
                    "the plan supplies the shared per-round draws the swept "
                    "thresholds compare against"
                )
            draws = None
        else:
            if faults.quorum is not None and faults.quorum > K:
                raise ValueError(
                    f"quorum={faults.quorum} can never be met by K={K} nodes"
                )
            if strategy.aggregate_op != "sum" or (
                type(strategy).aggregate is not Strategy.aggregate
                and not getattr(strategy, "fault_maskable", False)
            ):
                raise ValueError(
                    f"faults= masks dropped nodes out of a SUM aggregate; "
                    f"{type(strategy).__name__} declares "
                    f"aggregate_op={strategy.aggregate_op!r}"
                    + (
                        " with an aggregate() override (set fault_maskable"
                        " = True only if the override is linear, so a"
                        " zeroed message drops out of it like a sum term)"
                        if type(strategy).aggregate is not Strategy.aggregate
                        else ""
                    )
                )
            if (
                type(strategy).uplink_bytes is not Strategy.uplink_bytes
                or type(strategy).downlink_bytes is not Strategy.downlink_bytes
            ):
                raise ValueError(
                    f"faults= meters survivors host-side from the plan's "
                    f"draws; {type(strategy).__name__}'s byte-accounting "
                    "overrides would disagree with it"
                )
            # the draws ride the scan as jit arguments (masks are data,
            # so round-varying faults never retrace the step)
            draws = faults.draws(t0, T, K)
        # a swept "staleness" supersedes the transport's own D: one delay
        # line of depth max(D_s) is shared, read at a per-scenario index;
        # stragglers deepen whatever line that leaves by their max lag
        stal_sweep = executor.swept("staleness")
        if stal_sweep is not None:
            D_buf = max(1, int(np.max(np.asarray(stal_sweep))))
        else:
            D_buf = self.staleness
        straggler = 0 if faults is None else faults.straggler
        D_buf += straggler
        resolved0 = None
        if carry is None and executor.swept("theta0") is None:
            resolved0 = _resolve_theta0(strategy, data, theta0)

        def make_carry(theta0=resolved0):
            th0 = (
                theta0 if theta0 is not None
                else _resolve_theta0(strategy, data, None)
            )
            delay = (
                delay_init(jax.tree.map(jnp.zeros_like, th0), D_buf)
                if D_buf > 0
                else ()
            )
            return (
                th0,
                strategy.init_state(th0, data),
                wire.init_state(th0, K, stacked=strategy.stacked_msgs),
                delay,
            )

        if carry is not None:
            theta_template = executor.scenario_template(carry[0])
        elif resolved0 is not None:
            theta_template = resolved0
        else:
            theta_template = executor.scenario_template(
                executor.swept("theta0")
            )
        # static byte accounting where possible (see Wire.push_bytes)
        up_is_static = (
            type(strategy).uplink_bytes is Strategy.uplink_bytes
            and wire.push_bytes(theta_template) is not None
        )
        down_is_static = type(strategy).downlink_bytes is Strategy.downlink_bytes
        if faults is not None and not up_is_static:
            raise ValueError(
                f"faults= with wire {wire.name!r}: per-survivor byte "
                "accounting needs a shape-static push cost "
                "(wire.push_bytes); value-dependent wires (thresh) are "
                "not supported under faults"
            )

        # per-step scalar stats (metric pmean, byte psum) defer to one
        # post-loop reduction on the stacked (T,) outputs — bitwise
        # identical, and it removes two tiny collectives from every round
        defer_ok = bool(getattr(strategy, "defer_stats", True))
        stats = _exec.StatsDeferral()
        # comm/compute overlap: a delay-tolerant transport (D >= 1) may
        # split each round's aggregate — every hop but the outermost runs
        # in-round, the outermost (inter-pod, expensive) completes at the
        # START of the next round so XLA overlaps it with that round's
        # local compute.  Bit-exact: the completed value is the same psum,
        # applied at the same (delayed) step it would have been anyway.
        # Sum-reductions only, and only the default aggregate/uplink paths
        # (overrides may inspect the aggregate mid-round).
        overlap_active = (
            bool(getattr(executor, "overlap", False))
            and D_buf >= 1
            and stal_sweep is None
            and executor.num_scenarios is None
            and strategy.stacked_msgs
            and strategy.aggregate_op == "sum"
            and type(strategy).aggregate is Strategy.aggregate
            and type(strategy).uplink_bytes is Strategy.uplink_bytes
            # a quorum abort would have to recall an in-flight partial;
            # keep faulted rounds on the plain aggregate path
            and faults is None
        )

        def make_step(shard_data, sweep_delay):
            """Per-round step against the executor's primitive set.

            ``shard_data`` is whatever node slice the executor placed here
            (the full stack locally, a shard under the mesh); ``sweep_delay``
            is the per-scenario staleness index under a sweep, else None.
            """

            def step(c, xt):
                if faults is not None:
                    (u_t, lag_t), batch = xt
                else:
                    batch = xt
                c0 = c  # pre-round carry — the quorum rollback target
                theta, sstate, wstate, delay = c
                if overlap_active:
                    buf2, pending, step0 = delay
                    # complete LAST round's outermost hop first, so the
                    # collective overlaps the local compute traced below
                    agg_done = _exec.aggregate_complete(pending)
                # named scopes name the device work in profiler traces
                with jax.named_scope("local_updates"):
                    msgs, sstate = strategy.local_updates(
                        theta, sstate, shard_data, batch
                    )
                with jax.named_scope("wire_encode"):
                    wstate_new, msgs_hat, up = wire.encode_updates(
                        wstate, msgs, stacked=strategy.stacked_msgs
                    )
                if faults is not None:
                    # participation: node k answers iff u_t[k] clears the
                    # (possibly swept, traced) threshold.  The global mask
                    # is replicated data; each shard masks only its own
                    # message rows, so the sum aggregate sees zeros for the
                    # dead and the result is placement-invariant.  Dead
                    # nodes' wire state freezes (they neither encoded nor
                    # sent — EF residuals must not absorb a discarded push).
                    alive = u_t >= faults.dropout_p
                    live = jnp.sum(alive.astype(jnp.int32))
                    if strategy.stacked_msgs:
                        alive_loc = _exec.local_rows(alive)

                        def _rows(sel, n, o):
                            return jnp.where(
                                sel.reshape(sel.shape + (1,) * (n.ndim - 1)),
                                n, o,
                            )

                        msgs_hat = jax.tree.map(
                            lambda x: _rows(alive_loc, x, jnp.zeros_like(x)),
                            msgs_hat,
                        )
                        wstate = jax.tree.map(
                            lambda n, o: _rows(alive_loc, n, o),
                            wstate_new, wstate,
                        )
                    else:
                        alive0 = alive[0]
                        msgs_hat = jax.tree.map(
                            lambda x: jnp.where(alive0, x, jnp.zeros_like(x)),
                            msgs_hat,
                        )
                        wstate = jax.tree.map(
                            lambda n, o: jnp.where(alive0, n, o),
                            wstate_new, wstate,
                        )
                else:
                    wstate = wstate_new
                up_override = strategy.uplink_bytes(msgs_hat, shard_data)
                if up_override is not None:
                    up = up_override
                elif up_is_static:
                    # replaced by exact integer accounting after the run
                    up = jnp.zeros(())
                else:
                    with _exec.deferring(stats if defer_ok else None):
                        up = _exec.sum_bytes(up)  # shard-local cost → global
                if overlap_active:
                    pending_new = _exec.aggregate_partial(msgs_hat)
                    if D_buf > 1:
                        buf2, agg = delay_push_pop(buf2, agg_done)
                    else:
                        agg = agg_done
                    delay = (buf2, pending_new, step0)
                else:
                    agg = _exec.broadcast(strategy.aggregate(msgs_hat))
                    if straggler > 0:
                        # the round completes when its slowest LIVE node
                        # responds: read the delay line at base + max lag
                        base = (
                            sweep_delay if sweep_delay is not None
                            else jnp.asarray(self.staleness, jnp.int32)
                        )
                        lag_eff = jnp.max(jnp.where(alive, lag_t, 0))
                        delay, agg = delay_push_read(
                            delay, agg, base + lag_eff
                        )
                    elif sweep_delay is not None:
                        delay, agg = delay_push_read(delay, agg, sweep_delay)
                    elif D_buf > 0:
                        delay, agg = delay_push_pop(delay, agg)
                theta_new, sstate = strategy.apply_update(
                    theta, agg, sstate, shard_data
                )
                if down_is_static:
                    down = jnp.zeros(())  # replaced after the run
                else:
                    down = strategy.downlink_bytes(theta_new, shard_data)
                    if down is None:
                        down = jnp.asarray(float(K * wire.measure(theta_new)))
                new_c = (theta_new, sstate, wstate, delay)
                if faults is not None and faults.quorum is not None:
                    # below quorum the server discards the round: the whole
                    # carry (θ, strategy state, wire state, delay line)
                    # rolls back to the pre-round value
                    proceed = live >= faults.quorum
                    new_c = jax.tree.map(
                        lambda n, o: jnp.where(proceed, n, o), new_c, c0
                    )
                with _exec.deferring(stats if defer_ok else None), \
                        jax.named_scope("round_metric"):
                    m = strategy.round_metric(new_c[0], new_c[1], shard_data)
                return new_c, (m, up, down)

            return step

        def enter_loop(c):
            # standard carry → overlapped carry: the delay line's NEWEST
            # slot becomes the in-flight partial (masked to the outer
            # hop's root shards, so the completing psum reproduces the
            # replicated value exactly); older slots stay a depth-(D-1)
            # line.  This keeps resume carries interchangeable between
            # overlap on/off.
            theta, sstate, wstate, delay = c
            newest = jax.tree.map(lambda b: b[D_buf - 1], delay.buffer)
            pending = _exec.mask_to_root(newest)
            if D_buf > 1:
                buf2 = DelayLine(
                    buffer=jax.tree.map(
                        lambda b: b[: D_buf - 1], delay.buffer
                    ),
                    step=delay.step,
                )
            else:
                buf2 = ()
            return (theta, sstate, wstate, (buf2, pending, delay.step))

        def exit_loop(c, ys):
            m, up, down = ys
            if overlap_active:
                # overlapped carry → standard carry: complete the last
                # round's pending hop and re-append it as the newest slot
                theta, sstate, wstate, (buf2, pending, step0) = c
                done = _exec.aggregate_complete(pending)
                if D_buf > 1:
                    delay = DelayLine(
                        buffer=jax.tree.map(
                            lambda b, d: jnp.concatenate(
                                [b, d[None]], axis=0
                            ),
                            buf2.buffer, done,
                        ),
                        step=buf2.step,
                    )
                else:
                    delay = DelayLine(
                        buffer=jax.tree.map(lambda d: d[None], done),
                        step=step0 + jnp.asarray(T, jnp.int32),
                    )
                c = (theta, sstate, wstate, delay)
            if stats.metric:
                m = _exec.metric_mean(m)
            if stats.bytes:
                up = _exec.sum_bytes(up)
            return c, (m, up, down)

        st_tok = strategy.cache_token()
        cache_key = None
        if st_tok is not None:
            cache_key = (
                "update", st_tok, wire.cache_token(), D_buf,
                stal_sweep is None, overlap_active, defer_ok,
                up_is_static, down_is_static, strategy.stacked_msgs, K,
            )
            if faults is not None:
                # the plan's seed is NOT in the token: draws are data, so
                # plans differing only in seed share one compiled program
                cache_key += (
                    faults.cache_token(dropout_swept=p_sweep is not None),
                )

        xs = stream if stream is not None else None
        if faults is not None:
            xs = ((draws.u, draws.lag), xs)
        carry, (traj, ups, downs) = executor.run_update(
            strategy=strategy, data=data, carry=carry,
            make_carry=make_carry, make_step=make_step, xs=xs, length=T,
            wire=wire, cache_key=cache_key,
            enter_loop=enter_loop if overlap_active else None,
            exit_loop=exit_loop if (overlap_active or defer_ok) else None,
            sweep_targets=(faults,) + tuple(getattr(wire, "stages", ())),
        )
        theta, sstate = carry[0], carry[1]
        theta = executor.finalize(strategy, theta, sstate, data)
        if faults is not None:
            # exact host-side survivor accounting from the same draws the
            # step masked with: uplink charges only live pushes; downlink
            # hands θ back to survivors, and only when quorum committed
            p_vals = (
                np.asarray(p_sweep, dtype=np.float64).reshape(-1)
                if p_sweep is not None
                else np.asarray([faults.dropout_p])
            )
            alive_np = draws.u[None, :, :] >= p_vals[:, None, None]
            live_np = alive_np.sum(axis=2).astype(np.int64)  # (S|1, T)
            ups = live_np * int(wire.push_bytes(theta_template))
            commit_np = (
                live_np >= faults.quorum
                if faults.quorum is not None
                else np.ones_like(live_np, dtype=bool)
            )
            downs = (
                np.where(commit_np, live_np, 0)
                * int(wire.measure(theta_template))
            )
            if p_sweep is None:
                ups, downs = ups[0], downs[0]
        else:
            if up_is_static:
                per_round = wire.push_bytes(theta_template) * (
                    K if strategy.stacked_msgs else 1
                )
                ups = np.full((T,), per_round, dtype=np.int64)
            if down_is_static:
                downs = np.full(
                    (T,), K * wire.measure(theta_template), dtype=np.int64
                )
        S = executor.num_scenarios
        if S is not None:
            ups = np.asarray(ups)
            downs = np.asarray(downs)
            if ups.ndim == 1:  # static costs are scenario-invariant
                ups = np.broadcast_to(ups, (S, T)).copy()
            if downs.ndim == 1:
                downs = np.broadcast_to(downs, (S, T)).copy()
        out_carry = carry
        if faults is not None:
            out_carry = FaultCarry(inner=carry, next_round=t0 + T)
        return RawRun(
            theta=theta,
            state=sstate,
            trajectory=traj,
            uplink=ups,
            downlink=downs,
            rounds_per_step=1,
            event_kind="allreduce",
            extras={"faults": faults.describe()} if faults is not None else {},
            carry=out_carry,
        )


class AdmmTransport(Transport):
    """Global-variable-consensus ADMM: the strategy supplies the per-node
    prox; every iteration costs two Allreduces of the consensus variable
    (z-update mean + residual norms), which is what the ledger charges.

    Wraps ``core.admm.consensus_admm``'s own three-stage loop rather
    than the executor step protocol, so runs are one-shot (no
    ``theta0=``/``carry=``), need a LOSSLESS wire (``Wire.lossless`` —
    compressing consensus pushes would change the algorithm), and run
    on the local executor only.
    """

    name = "admm_consensus"

    def __init__(self, *, rho: float = 1.0, g: str = "none", g_lam: float = 0.0):
        self.rho = rho
        self.g = g
        self.g_lam = g_lam

    def run(self, strategy, data, *, wire, schedule, steps, stream, theta0, carry,
            executor, faults=None):
        if faults is not None:
            raise ValueError(
                "admm_consensus wraps core.admm's own synchronous loop — "
                "consensus ADMM has no masked-participation form here; "
                "faults= applies to server/allreduce/delay_line transports"
            )
        if steps is None:
            raise ValueError("transport 'admm_consensus' needs steps= (iterations)")
        if theta0 is not None or carry is not None:
            raise ValueError(
                "admm_consensus runs are one-shot: warm-start (theta0=) and "
                "resume (carry=) are not supported — rerun with more steps"
            )
        if not wire.lossless:
            raise ValueError(
                "admm_consensus needs a lossless wire (dense) — compressing "
                "the consensus pushes would change the algorithm"
            )
        from repro.api.executor import LocalExecutor

        if not isinstance(executor, LocalExecutor):
            raise ValueError(
                "admm_consensus wraps core.admm's own inner loop — it runs "
                f"on the local executor only, not {executor.name!r}"
            )
        local_prox = strategy.make_local_prox(data)
        K = strategy.num_nodes(data)
        dim = strategy.dim(data)
        res = consensus_admm(
            local_prox, K, dim,
            rho=self.rho, g=self.g, g_lam=self.g_lam, iters=steps,
        )
        theta = executor.finalize(strategy, res.z, res.state, data)
        # two Allreduces of the (dim,) consensus variable per iteration
        per_iter = 2 * K * wire.measure(res.z)
        ups = np.full((steps,), per_iter, dtype=np.int64)
        return RawRun(
            theta=theta,
            state=res.state,
            trajectory=res.history,
            uplink=ups,
            downlink=ups,
            rounds_per_step=2,
            event_kind="allreduce",
            extras={"admm": res},
            carry=res.state,
        )


TRANSPORTS = (
    "sequential_server",
    "stale_server",
    "delay_line",
    "allreduce",
    "admm_consensus",
)


def make_transport(spec: str | Transport, **options) -> Transport:
    """Resolve a transport spec; ``options`` are transport-specific
    (``staleness`` for delay_line; ``rho``/``g``/``g_lam`` for
    admm_consensus)."""
    if isinstance(spec, Transport):
        if options:
            raise ValueError("transport options only apply to string specs")
        return spec
    if spec == "sequential_server":
        _expect(options, ())
        return ServerTransport("sequential")
    if spec == "stale_server":
        _expect(options, ())
        return ServerTransport("stale")
    if spec == "allreduce":
        _expect(options, ())
        return UpdateTransport(staleness=0)
    if spec == "delay_line":
        _expect(options, ("staleness",))
        return UpdateTransport(staleness=options.get("staleness", 1))
    if spec == "admm_consensus":
        _expect(options, ("rho", "g", "g_lam"))
        return AdmmTransport(**options)
    raise ValueError(f"unknown transport {spec!r} — one of {TRANSPORTS}")


def _expect(options: dict, allowed: tuple):
    unknown = set(options) - set(allowed)
    if unknown:
        raise TypeError(f"unexpected transport options: {sorted(unknown)}")
