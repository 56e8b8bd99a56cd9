"""Executor layer — WHERE a fit runs.

The Strategy/Transport/Wire decomposition (see ``docs/API.md``) says what
is learned, who talks to whom, and what crosses the network.  The
*executor* owns the remaining axis: where the per-round program is
placed.  The paper's §3.1 observation — the central-server Allreduce is
the two-phase simulation of what ``jax.lax.psum`` does natively — becomes
a pure placement choice: the same transport step runs

* ``local``  — K logical nodes stacked on one host (the classical
  simulation; bit-exact with the pre-executor engine);
* ``mesh``   — nodes placed on the data axis of a ``jax.sharding.Mesh``
  via ``shard_map``; aggregation is ``psum``/``pmean`` over the mesh axis
  and the wire's encode/decode (including the Pallas ``topk_compress``
  kernel) runs per shard, on the real hot path;
* ``multipod`` — the ``("pod", "data")`` production placement: the same
  shard_map'd step, but the ledger decomposes by reduction tier —
  intra-pod psum (cheap) vs inter-pod allreduce (the paper's expensive
  client↔server link), priced per hop;
* ``sweep``  — a vmapped leading *scenario* axis: S configurations
  (step sizes, regularizers, staleness levels, initial points) compile to
  ONE executable and return a batched ``FitResult`` with per-scenario
  ``CommLedger``s.

Executors COMPOSE: ``SweepExecutor(params, inner=MeshExecutor(...))``
(spec strings ``"mesh+sweep"`` / ``"multipod+sweep"`` with the scenario
values passed as ``fit(..., sweep={...})``) runs the scenario vmap
*inside* the shard_map body — S scenarios train per shard in one
executable, saturating the mesh, with per-scenario ``CommLedger``s (and,
under a multipod inner, the per-hop decomposition preserved per
scenario).  And the §5 *server* transports, which walk one sequential
contact schedule, now place on the mesh executors too: each contact's
``local_step`` runs masked on the shard owning the contacted node and
the push is replicated to every shard with one ``psum``
(``local_node`` / ``from_owner`` below) — local ≡ mesh bit-exact.

Transports do not hard-code stacked-axis arithmetic anymore; they express
their step against the executor-provided primitive set below —
``aggregate`` / ``broadcast`` / ``node_axis`` (+ the ``metric_mean`` /
``sum_bytes`` / ``num_node_shards`` / ``node_shard_index`` /
``node_global_index`` / ``local_node`` / ``from_owner`` /
``commit_owner`` helpers).  The primitives are ambient (a trace-time
context installed by the running executor) and resolve against the
context's ``core.topology.Topology``: a flat topology reduces every node
axis in one hop (today's behavior, bit-exact), a hierarchical one stages
the reduction intra-pod first and inter-pod last.  Under the local
executor every primitive degrades to the identity / the stacked
``server_allreduce``, keeping historical results bit-exact.  See
``docs/EXECUTORS.md`` for the full guide and the Transport × Executor
compatibility matrix.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.allreduce import (
    complete_allreduce,
    hierarchical_allreduce,
    mesh_allreduce,
    partial_allreduce,
    server_allreduce,
)
from repro.core.topology import Topology
from repro.launch.mesh import batch_axes, make_multipod_mesh, make_node_mesh
from repro.sharding.rules import current_mesh_context
from repro.telemetry import trace as _trace

PyTree = Any

# ----------------------------------------------------------------------------
# Ambient execution context + the primitive set
# ----------------------------------------------------------------------------

_ctx = threading.local()


class ExecContext(NamedTuple):
    """Trace-time placement info installed by the running executor."""

    node_axis: Any  # mesh axis name (or tuple) carrying nodes; None = stacked
    num_shards: int  # how many shards the node axis is split over
    #: reduction topology the primitives resolve against (None = single
    #: joint collective over ``node_axis``)
    topology: Any = None
    #: per-axis shard counts in ``node_axis`` order (for shard indexing)
    axis_sizes: Any = None
    #: logical nodes hosted per shard (K / num_shards); None locally
    nodes_per_shard: int | None = None
    #: stage the innermost hop as reduce-scatter → reduce → all-gather so
    #: each device reduces 1/K of the tree (set by the mesh executors'
    #: ``reduce_scatter`` knob; bit-exact with the staged psum path)
    reduce_scatter: bool = False


def current_exec_context() -> ExecContext | None:
    return getattr(_ctx, "value", None)


@contextmanager
def executing(ctx: ExecContext | None):
    prev = current_exec_context()
    _ctx.value = ctx
    try:
        yield
    finally:
        _ctx.value = prev


def node_axis():
    """The mesh axis name(s) carrying the node dimension, or None when the
    nodes are stacked locally."""
    ctx = current_exec_context()
    return None if ctx is None else ctx.node_axis


def num_node_shards() -> int:
    """How many shards the leading node axis is split over (1 locally).
    Strategies that derive per-node weights from ``data.shape[0]`` must
    multiply by this to recover the GLOBAL node count."""
    ctx = current_exec_context()
    return 1 if ctx is None else ctx.num_shards


def node_shard_index():
    """This shard's linear index along the node axis (0 locally) — the
    row-major position matching how ``P(node_axis)`` lays node slices out,
    so a strategy running on REPLICATED data can reconstruct which global
    nodes it owns (``shard * K_local + arange(K_local)``)."""
    ctx = current_exec_context()
    if ctx is None or ctx.node_axis is None:
        return jnp.asarray(0, jnp.int32)
    axes = (
        (ctx.node_axis,) if isinstance(ctx.node_axis, str) else ctx.node_axis
    )
    sizes = ctx.axis_sizes
    if sizes is None:
        if len(axes) > 1:
            raise ValueError(
                "node_shard_index over a multi-axis node placement needs "
                "ExecContext.axis_sizes (set by the mesh executors)"
            )
        sizes = (1,)  # single axis: the multiplier never applies
    idx = jnp.asarray(0, jnp.int32)
    for a, s in zip(axes, sizes):
        idx = idx * s + jax.lax.axis_index(a)
    return idx


def node_global_index(k_local):
    """Global node index of shard-local node ``k_local`` (identity
    locally).  Server-family strategies that index REPLICATED per-node
    structures — a pooled θ slot block, a stacked per-node RNG key array
    — recover the global position with this while still reading their
    data shard at the local index (the k-windows strategy is the
    canonical user)::

        def local_step(self, k, theta, state, data):
            kg = _exec.node_global_index(k)      # slot into replicated pools
            win = kwindows(state[kg], data[k], ...)
    """
    ctx = current_exec_context()
    if ctx is None or ctx.node_axis is None:
        return k_local
    return node_shard_index() * ctx.nodes_per_shard + k_local


def local_rows(x):
    """This shard's slice of a REPLICATED leading-node-axis array
    (identity locally).  The fault layer's per-round participation masks
    are global ``(K,)`` jit arguments replicated to every shard; each
    shard masks only the message rows it owns, so the masked aggregate
    is placement-invariant."""
    ctx = current_exec_context()
    if ctx is None or ctx.node_axis is None:
        return x
    Kl = ctx.nodes_per_shard
    return jax.lax.dynamic_slice_in_dim(
        x, node_shard_index() * Kl, Kl, axis=0
    )


def local_node(k):
    """Resolve a GLOBAL node index against this shard: returns
    ``(k_local, mine)`` where ``k_local`` indexes the shard's node slice
    (clamped into range, so non-owners can still trace the computation)
    and ``mine`` is True on exactly the shard hosting node ``k``.
    Locally this is the identity ``(k, True)``.

    This is how the §5 *sequential* schedule places on a mesh: a
    ``lax.switch`` over shards is not expressible inside ``shard_map``
    (every shard runs the same program), so each shard computes the
    contacted node's ``local_step`` masked — only the owner's result is
    real — and ``from_owner`` replicates it with one ``psum``.
    """
    ctx = current_exec_context()
    if ctx is None or ctx.node_axis is None:
        return k, jnp.asarray(True)
    Kl = ctx.nodes_per_shard
    off = k - node_shard_index() * Kl
    mine = (off >= 0) & (off < Kl)
    return jnp.clip(off, 0, Kl - 1), mine


def from_owner(tree: PyTree, mine) -> PyTree:
    """Replicate the owning shard's value to every shard (identity
    locally).  ``mine`` must be True on exactly one shard along the node
    axis; everyone else's contribution is zeroed, so the ``psum`` is an
    exact (fp-addition-with-zeros) broadcast of the owner's ``tree``."""
    ctx = current_exec_context()
    if ctx is None or ctx.node_axis is None:
        return tree

    def sel(x):
        if x.dtype == jnp.bool_:
            masked = jnp.where(mine, x, False)
            return jax.lax.psum(masked.astype(jnp.int32), ctx.node_axis) > 0
        return jax.lax.psum(
            jnp.where(mine, x, jnp.zeros_like(x)), ctx.node_axis
        )

    return jax.tree.map(sel, tree)


def commit_owner(new: PyTree, old: PyTree, mine) -> PyTree:
    """Commit a shard-LOCAL state update only on the owning shard: the
    owner keeps ``new``, everyone else keeps ``old`` (locally: ``new``).
    This is how per-node wire state (error-feedback residuals) stays
    correct under a mesh-placed server transport — non-owner shards
    trace the same encode but must not corrupt their rows."""
    ctx = current_exec_context()
    if ctx is None or ctx.node_axis is None:
        return new
    return jax.tree.map(lambda n, o: jnp.where(mine, n, o), new, old)


def aggregate(stacked: PyTree, op: str = "sum") -> PyTree:
    """Reduce per-node messages over the node axis, wherever it lives:
    the (shard-local) stacked axis 0, then — under a mesh placement — the
    native collective across shards, staged hop by hop through the
    ambient ``Topology`` (intra-pod psum first, inter-pod allreduce
    last; a flat topology is one joint collective).  Locally this IS
    ``server_allreduce`` (bit-exact with the pre-executor engine)."""
    reduced = server_allreduce(stacked, op=op)
    ctx = current_exec_context()
    if ctx is not None and ctx.node_axis is not None:
        if ctx.topology is not None:
            reduced = hierarchical_allreduce(
                reduced, ctx.topology.hops, op=op,
                reduce_scatter=ctx.reduce_scatter,
                axis_sizes=_ctx_size_map(ctx),
            )
        else:
            reduced = mesh_allreduce(reduced, ctx.node_axis, op=op)
    return reduced


def _ctx_size_map(ctx: ExecContext):
    """axis → shard count mapping for the ambient placement (None when the
    executor did not record sizes)."""
    if ctx.axis_sizes is None:
        return None
    axes = (
        (ctx.node_axis,) if isinstance(ctx.node_axis, str) else ctx.node_axis
    )
    return dict(zip(axes, ctx.axis_sizes))


def _overlap_hops(ctx: ExecContext):
    """The hop list the overlap split is defined over: the topology's
    hops, or the whole node axis as one hop (flat meshes)."""
    if ctx.topology is not None:
        return ctx.topology.hops
    return (ctx.node_axis,)


def aggregate_partial(stacked: PyTree, op: str = "sum") -> PyTree:
    """First half of the comm/compute-overlap split of ``aggregate``:
    the shard-local stack sum plus every hop EXCEPT the outermost
    (intra-pod under multipod; nothing extra on a flat mesh).  The
    outermost (expensive, inter-pod) hop is deferred — apply
    ``aggregate_complete`` one round later, so XLA can overlap the slow
    collective with the next round's local compute.  Sum-only: splitting
    a mean's final divide across rounds would break bit-exactness."""
    if op != "sum":
        raise ValueError(
            f"aggregate_partial only supports op='sum' (got {op!r}) — the "
            "overlap split defers the outermost hop, and a mean's final "
            "divide cannot move across rounds bit-exactly"
        )
    reduced = server_allreduce(stacked, op="sum")
    ctx = current_exec_context()
    if ctx is not None and ctx.node_axis is not None:
        reduced = partial_allreduce(reduced, _overlap_hops(ctx))
    return reduced


def aggregate_complete(pending: PyTree) -> PyTree:
    """Second half of the overlap split: the outermost hop's psum over a
    round-old ``aggregate_partial`` result.  Identity locally."""
    ctx = current_exec_context()
    if ctx is not None and ctx.node_axis is not None:
        return complete_allreduce(pending, _overlap_hops(ctx))
    return pending


def mask_to_root(tree: PyTree) -> PyTree:
    """Zero ``tree`` everywhere except the shards at index 0 of the
    OUTERMOST hop's axes.  Converts an already-complete (replicated)
    value into valid ``aggregate_complete`` input: the completing psum
    re-adds one real copy plus zeros — exact in fp — so a standard delay
    buffer slot can enter the overlapped schedule bit-exactly.  Identity
    locally."""
    ctx = current_exec_context()
    if ctx is None or ctx.node_axis is None:
        return tree
    outer = _overlap_hops(ctx)[-1]
    axes = getattr(outer, "axes", outer)
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    keep = None
    for a in axes:
        at_root = jax.lax.axis_index(a) == 0
        keep = at_root if keep is None else jnp.logical_and(keep, at_root)
    return jax.tree.map(
        lambda x: jnp.where(keep, x, jnp.zeros_like(x)), tree
    )


def broadcast(tree: PyTree) -> PyTree:
    """Phase 2 of the §3.1 two-step protocol: hand the aggregate back to
    every node.  ``aggregate`` already returns a replicated value under
    every placement, so this is the identity — it exists so transports can
    mark the downlink point explicitly (and future executors with
    non-replicating collectives have a hook)."""
    return tree


class StatsDeferral:
    """Trace-time flags for deferred statistics collectives.

    Per-step scalar stats (``metric_mean``'s pmean, ``sum_bytes``'s psum)
    each launch a tiny collective INSIDE the scan — pure per-round
    latency.  Both are elementwise across steps, so reducing the stacked
    ``(T,)`` outputs once after the loop is bitwise identical.  The
    transport allocates one of these, installs it with ``deferring``
    while tracing the step, and completes whatever got deferred in its
    ``exit_loop`` hook.  Valid only when the stat call is the OUTERMOST
    op of its expression (true for every in-repo ``round_metric``) —
    strategies that post-process the completed mean opt out via
    ``Strategy.defer_stats = False``.
    """

    __slots__ = ("metric", "bytes")

    def __init__(self):
        self.metric = False
        self.bytes = False


_defer = threading.local()


@contextmanager
def deferring(stats: StatsDeferral | None):
    """Route ``metric_mean``/``sum_bytes`` calls into deferred mode for
    the enclosed trace: they record the need on ``stats`` and return
    their input unchanged; the caller completes them post-loop."""
    prev = getattr(_defer, "value", None)
    _defer.value = stats
    try:
        yield
    finally:
        _defer.value = prev


def metric_mean(x: PyTree) -> PyTree:
    """Complete a node-mean statistic across shards (``pmean``); identity
    locally.  Strategies whose ``round_metric`` is a mean over the (local)
    node axis wrap it in this so the metric stays global under the mesh
    executor."""
    ctx = current_exec_context()
    if ctx is not None and ctx.node_axis is not None:
        stats = getattr(_defer, "value", None)
        if stats is not None:
            stats.metric = True
            return x
        return jax.tree.map(lambda v: jax.lax.pmean(v, ctx.node_axis), x)
    return x


def sum_bytes(x):
    """Total a shard-local byte count across shards (``psum``); identity
    locally."""
    ctx = current_exec_context()
    if ctx is not None and ctx.node_axis is not None:
        stats = getattr(_defer, "value", None)
        if stats is not None:
            stats.bytes = True
            return x
        return jax.lax.psum(x, ctx.node_axis)
    return x


# ----------------------------------------------------------------------------
# Program cache
# ----------------------------------------------------------------------------
#
# Profiling (ROADMAP "Make mesh actually fast") showed the mesh gap was
# never the collectives: an EAGER shard_map re-traces and re-lowers the
# whole scan on every fit call (~0.2s for the benchmark program, ~8 pjit
# compiles), while local fits ride jit's C++ dispatch cache.  The fix is
# the same cache, held explicitly: executors jit their placed program and
# memoize it by a config fingerprint, so repeated fits with the same
# strategy/transport/wire configuration skip straight to execution.
# Opt-in: a program is cached only when the transport hands the executor a
# ``cache_key`` (built from ``Strategy.cache_token()`` — strategies with
# unfingerprintable config return None and run uncached, exactly as
# before).  Data, carries and sweep values are jit ARGUMENTS, never baked.

_PROGRAM_CACHE: OrderedDict = OrderedDict()
_PROGRAM_CACHE_CAP = 128
_PROGRAM_CACHE_STATS = {"hits": 0, "misses": 0}


def _cache_enabled() -> bool:
    return os.environ.get("REPRO_PROGRAM_CACHE", "1") != "0"


def cached_program(key, build):
    """``build()`` → a jitted program, memoized under ``key`` (LRU).
    ``key=None`` (or ``REPRO_PROGRAM_CACHE=0``) bypasses the cache."""
    if key is None or not _cache_enabled():
        return build()
    try:
        fn = _PROGRAM_CACHE[key]
        _PROGRAM_CACHE.move_to_end(key)
        _PROGRAM_CACHE_STATS["hits"] += 1
        return fn
    except KeyError:
        pass
    _PROGRAM_CACHE_STATS["misses"] += 1
    fn = build()
    _PROGRAM_CACHE[key] = fn
    while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_CAP:
        _PROGRAM_CACHE.popitem(last=False)
    return fn


def program_cache_stats() -> dict:
    return {"size": len(_PROGRAM_CACHE), **_PROGRAM_CACHE_STATS}


def clear_program_cache() -> None:
    _PROGRAM_CACHE.clear()
    _PROGRAM_CACHE_STATS["hits"] = _PROGRAM_CACHE_STATS["misses"] = 0


def dispatch(key, build, label, *args):
    """``cached_program(key, build)(*args)`` with observability: when a
    tracer is ambient (``fit(..., tracer=...)``), the call is wrapped in
    a ``dispatch/<label>`` span tagged with the cache outcome (``hit`` =
    warm executable, ``miss`` = compile, ``uncached`` = no cache key) and
    fenced with ``jax.block_until_ready`` so the span covers device
    completion.  ``program_cache/{hit,miss,uncached}`` counters
    accumulate alongside.  With no tracer this is byte-for-byte the old
    ``cached_program(key, build)(*args)`` path — the fence is a pure
    wait either way, so traced dispatch stays bit-exact."""
    t = _trace.current_tracer()
    if t is None:
        return cached_program(key, build)(*args)
    if key is None or not _cache_enabled():
        state = "uncached"
        program = cached_program(key, build)
    else:
        hits_before = _PROGRAM_CACHE_STATS["hits"]
        program = cached_program(key, build)
        state = "hit" if _PROGRAM_CACHE_STATS["hits"] > hits_before else "miss"
    t.count(f"program_cache/{state}")
    with t.span(f"dispatch/{label}", cache=state):
        out = program(*args)
        jax.block_until_ready(out)
    return out


# ----------------------------------------------------------------------------
# Executors
# ----------------------------------------------------------------------------


class Executor:
    """Owns where a fit's per-round loop runs.

    Transports hand the executor ``make_carry`` / ``make_step`` factories
    plus the scan inputs; the executor decides placement (stacked scan,
    shard_map'd scan, vmapped scan — or a nesting of those) and installs
    the ambient primitive context the step body's
    ``aggregate``/``metric_mean``/… calls resolve against.  Two run
    hooks, one per transport family:

    * ``run_update(make_carry, make_step, …)`` — update-family
      transports (``allreduce`` / ``delay_line``): every round all nodes
      step, so the loop places anywhere (sharded, vmapped, or both).
      ``make_step(shard_data, sweep_delay)`` builds the per-round step
      against whatever node slice the executor placed here.
    * ``run_server(make_step, schedule, …)`` — server-family transports
      (``sequential_server`` / ``stale_server``): ONE node steps per
      contact.  Local and mesh executors place this (the mesh masks the
      pusher's compute onto its own shard); batching executors raise.
    """

    name = "executor"
    #: number of scenarios for batched executors; None = unbatched
    num_scenarios: int | None = None
    #: capability flag: True when this executor wants the transport to
    #: dispatch the outermost (inter-pod) hop asynchronously against the
    #: next round's local compute (delay-tolerant transports only; the
    #: mesh executors' ``overlap=`` knob)
    overlap: bool = False

    def swept(self, key: str):
        """The per-scenario values swept for ``key`` (None when not swept)."""
        return None

    def scenario_template(self, tree: PyTree) -> PyTree:
        """An unbatched representative of a possibly scenario-batched tree
        (used for shape-static byte accounting)."""
        return tree

    def finalize(self, strategy, theta, state, data):
        """Strategy finalize under this executor's batching (vmapped per
        scenario by the sweep executor; the serving executor additionally
        stands the result up behind an engine)."""
        return strategy.finalize(theta, state, data)

    def extra_metrics(self) -> dict:
        """Executor-specific entries merged into ``FitResult.metrics``
        (e.g. the serving executor's live engine)."""
        return {}

    def ledger_hops(self, strategy, data):
        """Per-tier decomposition of the per-round node messages —
        ``[(tier, messages, price_per_byte), ...]`` summing to K — or
        None for flat (single-tier) ledger accounting.  The engine uses
        this to attribute the materialized ledger's byte totals by hop."""
        return None

    def run_update(
        self, *, strategy, data, carry, make_carry, make_step, xs, length,
        wire=None, cache_key=None, enter_loop=None, exit_loop=None,
        sweep_targets=(),
    ):
        """Place and run the update loop.  ``cache_key`` (optional) keys
        the jitted program cache; ``enter_loop(carry)`` /
        ``exit_loop(carry, ys)`` are transport hooks running INSIDE the
        placed program (ambient context installed) immediately before /
        after the scan — the overlap schedule's carry conversions and the
        deferred-stats completion live there.  ``sweep_targets`` are
        extra objects (fault plans, chain-wire stages) whose attributes
        the sweep executor may rebind per scenario; non-sweep executors
        ignore them."""
        raise NotImplementedError

    def run_server(self, *, strategy, data, carry, make_step, schedule,
                   wire=None, cache_key=None):
        raise ValueError(
            "server transports walk one contact schedule sequentially — "
            f"executor {self.name!r} cannot place them; use "
            "executor='local' (or 'mesh'/'multipod' to run each contact's "
            "local_step on the shard owning the contacted node)"
        )


class LocalExecutor(Executor):
    """K logical nodes stacked on one host, one ``lax.scan``.

    No ambient context is installed, so every primitive is the stacked
    identity and results are bit-exact with the historical loops::

        res = api.fit(strategy, data, transport="allreduce", steps=100)
        # executor="local" is the default — these are the same run
        res = api.fit(strategy, data, transport="allreduce", steps=100,
                      executor="local")
    """

    name = "local"

    def run_update(
        self, *, strategy, data, carry, make_carry, make_step, xs, length,
        wire=None, cache_key=None, enter_loop=None, exit_loop=None,
        sweep_targets=(),
    ):
        if carry is None:
            carry = make_carry()

        def build():
            def prog(c, d, x):
                if enter_loop is not None:
                    c = enter_loop(c)
                c, ys = jax.lax.scan(make_step(d, None), c, x, length=length)
                if exit_loop is not None:
                    c, ys = exit_loop(c, ys)
                return c, ys

            return jax.jit(prog)

        key = (
            None if cache_key is None
            else ("local-update", cache_key, xs is None, length)
        )
        return dispatch(key, build, f"{self.name}-update", carry, data, xs)

    def run_server(self, *, strategy, data, carry, make_step, schedule,
                   wire=None, cache_key=None):
        def build():
            return jax.jit(
                lambda c, d, s: jax.lax.scan(make_step(d), c, s)
            )

        key = None if cache_key is None else ("local-server", cache_key)
        return dispatch(key, build, f"{self.name}-server", carry, data, schedule)


class ServingExecutor(LocalExecutor):
    """Train exactly like ``local``, then stand the finalized model up
    behind a ``repro.serve.ServeEngine`` — the ROADMAP's train→serve
    executor swap.  ``fit(..., executor="serve")`` returns a ``FitResult``
    whose ``metrics["serve_engine"]`` already answers requests (and, with
    ``registry=``/``publish_as=``, has been published first):

        res = api.fit(strategy, data, transport="allreduce", steps=400,
                      executor=api.ServingExecutor(mesh=mesh))
        y = res.metrics["serve_engine"].predict(Xq)
    """

    name = "serve"

    def __init__(
        self, *, mesh=None, registry=None, publish_as: str | None = None,
        **engine_kw,
    ):
        if (registry is None) != (publish_as is None):
            raise ValueError(
                "publishing needs both registry= and publish_as="
            )
        self._mesh = mesh
        self._registry = registry
        self._publish_as = publish_as
        self._engine_kw = engine_kw
        self.engine = None

    def finalize(self, strategy, theta, state, data):
        from repro.serve.engine import ServeEngine

        final = super().finalize(strategy, theta, state, data)
        if self._registry is not None:
            self._registry.publish(self._publish_as, final)
        self.engine = ServeEngine(
            strategy, final, mesh=self._mesh, **self._engine_kw
        )
        return final

    def extra_metrics(self) -> dict:
        return {} if self.engine is None else {"serve_engine": self.engine}


class ResolvedPlacement(NamedTuple):
    """A mesh executor's resolved placement."""

    mesh: Mesh
    axes: tuple  # ordered node axes
    axis: Any  # squashed spec entry: the tuple, or the single axis name
    num_shards: int
    topology: Topology


class MeshExecutor(Executor):
    """Place the K nodes on the data axis of a ``jax.sharding.Mesh``.

    For update transports the whole scan runs inside one ``shard_map``:
    each device hosts K/ndev nodes of the data (and the wire's per-node
    state, e.g. EF residuals), θ and the strategy state stay
    replicated, and ``aggregate`` completes shard-local reductions with
    ``psum``/``pmean`` over the mesh axes — the §3.1 equivalence run in
    the native direction, staged hop by hop through the mesh's implied
    ``Topology`` (pod meshes reduce intra-pod first, then inter-pod;
    1-D meshes keep the single-collective behavior bit-exact).  Wire
    encode/decode executes per shard, so a compressed wire's kernels
    (Pallas ``topk_compress``) sit on the real per-device hot path.
    Server transports place too (``run_server``): the sequential
    schedule walks unchanged, with each contact's local_step masked
    onto the shard owning the contacted node — bit-exact with local.
    A ``SweepExecutor(..., inner=MeshExecutor(...))`` nests its
    scenario vmap inside the shard_map body via ``place_update``.

    ::

        res = api.fit(strategy, data, transport="allreduce", steps=100,
                      executor="mesh")            # all local devices
        res = api.fit(strategy, data, transport="allreduce", steps=100,
                      executor=api.MeshExecutor(mesh))   # explicit mesh

    Strategies with ``replicate_data=True`` (the cascade SVM, whose
    per-node training sets overlap through the shared global-SV pool)
    receive the FULL data on every shard and reconstruct their node
    slice from ``node_shard_index()`` instead (update transports only).

    Mesh resolution order: explicit ``mesh=`` → the active
    ``sharding.rules.MeshContext`` (its ``node_axes``) → a fresh 1-D
    ``("data",)`` mesh over all local devices (``launch.mesh``).  See
    ``docs/EXECUTORS.md``.
    """

    name = "mesh"

    def __init__(
        self,
        mesh: Mesh | None = None,
        *,
        reduce_scatter: bool | str = "auto",
        overlap: bool = True,
    ):
        self._mesh = mesh
        #: "auto" stages the innermost hop as reduce-scatter → all-gather
        #: only on TPU (on CPU the ring passes cost more than they save);
        #: True/False force it.  Either way bit-exact with staged psum.
        self.reduce_scatter = reduce_scatter
        #: let delay-tolerant transports overlap the outermost hop with
        #: the next round's compute (opt-out knob; bit-exact either way)
        self.overlap = bool(overlap)

    def _rs_active(self) -> bool:
        if self.reduce_scatter == "auto":
            return jax.default_backend() == "tpu"
        return bool(self.reduce_scatter)

    def _default_mesh(self) -> Mesh:
        return make_node_mesh()

    def _topology(self, axes, mesh) -> Topology:
        return Topology.from_mesh(axes)

    def _validate_mesh(self, mesh: Mesh) -> None:
        pass

    def resolve(self) -> ResolvedPlacement:
        mesh = self._mesh
        axes = None
        if mesh is None:
            mc = current_mesh_context()
            if mc is not None:
                mesh, axes = mc.mesh, mc.node_axes
            else:
                mesh = self._default_mesh()
        self._validate_mesh(mesh)
        if axes is None:
            axes = batch_axes(mesh)
        if not axes:
            raise ValueError(
                f"mesh {mesh} has no 'data'/'pod' axis to place nodes on"
            )
        # placement keeps the mesh's axis order (pods hold contiguous node
        # ranges); the topology orders the REDUCTION hops independently
        # (intra-pod first, inter-pod last)
        topology = self._topology(axes, mesh)
        axes = tuple(axes)
        axis = axes if len(axes) > 1 else axes[0]
        ndev = 1
        for a in axes:
            ndev *= mesh.shape[a]
        return ResolvedPlacement(
            mesh=mesh, axes=axes, axis=axis, num_shards=ndev, topology=topology
        )

    def _placement_context(self, r: ResolvedPlacement, K: int) -> ExecContext:
        return ExecContext(
            node_axis=r.axis, num_shards=r.num_shards, topology=r.topology,
            axis_sizes=tuple(r.mesh.shape[a] for a in r.axes),
            nodes_per_shard=K // r.num_shards,
            reduce_scatter=self._rs_active(),
        )

    @staticmethod
    def _mesh_fingerprint(mesh: Mesh):
        return (
            tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(str(d) for d in mesh.devices.flat),
        )

    def _check_divisible(self, K: int, ndev: int) -> None:
        if K % ndev != 0:
            raise ValueError(
                f"{K} nodes cannot be placed evenly on {ndev} mesh shards"
            )

    def place_update(self, *, strategy, data, carry, body, xs,
                     scenario_axis: bool = False, cache_key=None):
        """Shard-map an update-family loop body onto the resolved mesh.

        ``body(carry, shard_data, xs)`` runs per shard with the ambient
        primitive context installed — a plain scan for the bare mesh
        executor, or a scenario-vmapped scan when a ``SweepExecutor``
        composes with this placement (``scenario_axis=True``: every
        carry component then has a leading S axis, so the per-node wire
        state shards on its SECOND axis).  This is the inner-vmap hook
        ``run_update`` is built on.
        """
        from repro.api.strategy import Strategy

        r = self.resolve()
        mesh, axis, ndev = r.mesh, r.axis, r.num_shards
        if data is None:
            raise ValueError(
                "mesh executor needs data with a leading node axis to shard"
            )
        if not strategy.stacked_msgs:
            raise ValueError(
                "mesh executor needs per-node stacked messages "
                "(strategy.stacked_msgs=True)"
            )
        if type(strategy).aggregate is not Strategy.aggregate:
            raise NotImplementedError(
                f"{type(strategy).__name__} overrides aggregate(); the mesh "
                "executor only places op-based reductions (set aggregate_op "
                "to 'sum'/'mean'/'max'/'any' instead)"
            )
        K = strategy.num_nodes(data)
        self._check_divisible(K, ndev)
        ctx = self._placement_context(r, K)
        # carry = (theta, strategy state, wire state, delay line): everything
        # replicated except the per-node wire state, which lives with its node
        wspec = P(None, axis) if scenario_axis else P(axis)
        cspec = (P(), P(), wspec, P())
        # replicate-data strategies see the whole dataset on every shard
        # and slice their own nodes out via node_shard_index()
        dspec = P() if strategy.replicate_data else P(axis)

        def shard_body(c, d, x):
            with executing(ctx):
                return body(c, d, x)

        def build():
            if xs is None:
                inner = shard_map(
                    lambda c, d: shard_body(c, d, None), mesh=mesh,
                    in_specs=(cspec, dspec), out_specs=(cspec, P()),
                    check_vma=False,
                )
                return jax.jit(lambda c, d, x: inner(c, d))
            return jax.jit(shard_map(
                shard_body, mesh=mesh, in_specs=(cspec, dspec, P()),
                out_specs=(cspec, P()), check_vma=False,
            ))

        key = None if cache_key is None else (
            "mesh-update", type(self).__name__, cache_key, scenario_axis,
            xs is None, self._rs_active(), bool(strategy.replicate_data),
            self._mesh_fingerprint(mesh),
        )
        return dispatch(key, build, f"{self.name}-update", carry, data, xs)

    def run_update(
        self, *, strategy, data, carry, make_carry, make_step, xs, length,
        wire=None, cache_key=None, enter_loop=None, exit_loop=None,
        sweep_targets=(),
    ):
        if carry is None:
            carry = make_carry()

        def body(c, d, x):
            if enter_loop is not None:
                c = enter_loop(c)
            c, ys = jax.lax.scan(make_step(d, None), c, x, length=length)
            if exit_loop is not None:
                c, ys = exit_loop(c, ys)
            return c, ys

        key = None if cache_key is None else (cache_key, length)
        return self.place_update(
            strategy=strategy, data=data, carry=carry, body=body, xs=xs,
            cache_key=key,
        )

    def run_server(self, *, strategy, data, carry, make_step, schedule,
                   wire=None, cache_key=None):
        """Place the §5 sequential schedule on the mesh: data shards over
        the node axis, every contact's ``local_step`` runs masked on each
        shard (``local_node`` resolves the contacted node against the
        shard's slice) and only the owner's push survives the
        ``from_owner`` psum — bit-exact with the local walk, because
        adding the non-owners' zeros is exact in fp.

        The strategy's ``state`` stays REPLICATED here: ``local_step``
        must either pass it through or update it identically on every
        shard (true for every in-repo server strategy; per-node mutable
        state belongs in the wire state, which shards with its node and
        commits owner-only).
        """
        if data is None:
            raise ValueError(
                "mesh-placed server transports need data with a leading "
                "node axis to shard; closure-based strategies "
                "(FunctionStrategy over captured data) run executor='local'"
            )
        if strategy.replicate_data:
            raise ValueError(
                f"{type(strategy).__name__} declares replicate_data=True — "
                "its contacts read the whole dataset, so there is nothing "
                "to place; use executor='local' for server transports"
            )
        r = self.resolve()
        mesh, axis, ndev = r.mesh, r.axis, r.num_shards
        K = strategy.num_nodes(data)
        self._check_divisible(K, ndev)
        ctx = self._placement_context(r, K)
        # carry = (server state, strategy state, wire state): the server
        # and strategy state are replicated, the per-node wire state
        # (EF residuals) lives with its node's shard
        cspec = (P(), P(), P(axis))

        def body(c, d, sched):
            with executing(ctx):
                return jax.lax.scan(make_step(d), c, sched)

        def build():
            return jax.jit(shard_map(
                body, mesh=mesh, in_specs=(cspec, P(axis), P()),
                out_specs=(cspec, P()), check_vma=False,
            ))

        key = None if cache_key is None else (
            "mesh-server", type(self).__name__, cache_key,
            self._rs_active(), self._mesh_fingerprint(mesh),
        )
        return dispatch(
            key, build, f"{self.name}-server", carry, data, schedule
        )


class MultiPodExecutor(MeshExecutor):
    """The production placement: nodes on ``("pod", "data")`` of a
    multi-pod mesh, with the ledger decomposed by reduction tier.

    Execution is the same shard_map'd step as ``MeshExecutor`` on the
    same mesh — the staged intra-pod-psum + inter-pod-allreduce program
    both executors derive from the mesh's ``Topology`` — so the theta
    trajectory is bit-exact with ``executor="mesh"``.  What changes is
    the accounting: ``ledger_hops`` attributes the per-round node
    messages to tiers (K−P intra-pod pushes, P inter-pod root pushes for
    P pods), each priced per byte, so ``ledger.summary()["by_hop"]``
    reports the paper's cheap-vs-expensive link split instead of one
    lump sum.

    Mesh resolution order: explicit ``mesh=`` → the active
    ``sharding.rules.MeshContext`` → ``launch.mesh.make_multipod_mesh()``
    over the local devices (pass
    ``make_production_mesh(multi_pod=True)`` explicitly for the 512-chip
    production shape).
    """

    name = "multipod"

    def __init__(
        self,
        mesh: Mesh | None = None,
        *,
        intra_price: float | None = None,
        inter_price: float | None = None,
        calibrate: bool = False,
        reduce_scatter: bool | str = "auto",
        overlap: bool = True,
    ):
        super().__init__(mesh, reduce_scatter=reduce_scatter, overlap=overlap)
        self._intra_price = intra_price
        self._inter_price = inter_price
        #: measure per-hop prices on the actual mesh instead of the ×1/×10
        #: defaults (``core.topology.calibrate_prices`` — one-shot,
        #: memoized per device set); explicit ``*_price=`` overrides win
        self._calibrate = calibrate

    def _default_mesh(self) -> Mesh:
        return make_multipod_mesh()

    def _topology(self, axes, mesh) -> Topology:
        intra_p, inter_p = self._intra_price, self._inter_price
        if self._calibrate:
            from repro.core.topology import calibrate_prices

            prices = calibrate_prices(mesh)
            if intra_p is None:
                intra_p = prices["intra_pod"]
            if inter_p is None:
                inter_p = prices["inter_pod"]
        return Topology.from_mesh(
            axes, intra_price=intra_p, inter_price=inter_p
        )

    def _validate_mesh(self, mesh: Mesh) -> None:
        if "pod" not in mesh.axis_names:
            raise ValueError(
                f"multipod executor needs a mesh with a 'pod' axis, got "
                f"axes {mesh.axis_names} — build one with "
                "launch.mesh.make_multipod_mesh() or "
                "make_production_mesh(multi_pod=True)"
            )

    def ledger_hops(self, strategy, data):
        r = self.resolve()
        K = strategy.num_nodes(data)
        return r.topology.hop_messages(K, dict(r.mesh.shape))


class SweepExecutor(Executor):
    """Batch S scenarios into one executable with ``jax.vmap``.

    ``params`` maps names to length-S arrays:

    * a strategy attribute name (``"lr"``, ``"l2"``, ``"rho"``, …) — the
      attribute is rebound per scenario while the step is traced, so any
      scalar hyperparameter a strategy reads from ``self`` sweeps without
      the strategy knowing;
    * a WIRE attribute name (names not found on the strategy are looked
      up on the wire) — e.g. the threshold wire's ``"tau"``, which makes
      the compression ratio itself sweepable: the sparsifier is
      value-dependent but shape-static, so S thresholds share one
      executable where per-scenario top-k fractions would each need a
      different static k;
    * the reserved key ``"staleness"`` — handled by the update transport,
      which sizes one depth-max(D) delay line and reads it at a batched
      per-scenario index (``core.staleness.delay_push_read``), so D=0…D_max
      share one compiled program;
    * the reserved key ``"theta0"`` — a (S, …)-batched initial parameter.

    Structural knobs (top-k fraction, wire choice, transport identity)
    change compiled shapes and cannot ride one executable — run those as
    separate ``fit`` calls.

    ``inner=`` composes the sweep with a mesh placement: with
    ``SweepExecutor(params, inner=MeshExecutor(...))`` (spec strings
    ``"mesh+sweep"`` / ``"multipod+sweep"`` + ``fit(..., sweep=params)``)
    the scenario vmap runs INSIDE the shard_map body — each device hosts
    its node slice and trains all S scenarios on it in one executable,
    so a hyperparameter search saturates the mesh instead of idling it::

        sw = api.SweepExecutor({"lr": jnp.asarray([0.02, 0.1])},
                               inner=api.MeshExecutor(mesh))
        res = api.fit(strategy, data, transport="allreduce", steps=200,
                      executor=sw)   # == executor="mesh+sweep", sweep={...}

    Results are bit-exact with S independent fits on the same inner
    executor, and a ``MultiPodExecutor`` inner keeps its per-hop ledger
    decomposition — per scenario.

    The engine materializes one ``CommLedger`` per scenario from the
    batched byte counts; ``FitResult.theta`` / ``.trajectory`` /
    ``metrics["carry"]`` all gain a leading S axis (the carry resumes a
    later swept ``fit`` with the same executor shape).
    """

    name = "sweep"
    RESERVED = ("staleness", "theta0")

    def __init__(self, params: dict, *, inner: "Executor | str | None" = None):
        if not params:
            raise ValueError("sweep executor needs at least one swept parameter")
        # values may be pytrees (a batched theta0 for model-pytree
        # strategies); every leaf's leading axis is the scenario axis
        self.params = {
            k: jax.tree.map(jnp.asarray, v) for k, v in params.items()
        }
        counts = {}
        for k, v in self.params.items():
            leaves = jax.tree.leaves(v)
            if not leaves:
                raise ValueError(f"swept parameter {k!r} has no array leaves")
            per_leaf = {int(leaf.shape[0]) for leaf in leaves}
            if len(per_leaf) != 1:
                raise ValueError(
                    f"swept parameter {k!r} leaves disagree on scenario count"
                )
            counts[k] = per_leaf.pop()
        if len(set(counts.values())) != 1:
            raise ValueError(
                f"swept parameters disagree on scenario count: {counts}"
            )
        self.num_scenarios = next(iter(counts.values()))
        if inner is not None and not isinstance(inner, Executor):
            inner = make_executor(inner)
        if isinstance(inner, (ServingExecutor, SweepExecutor)):
            raise ValueError(
                f"sweep cannot nest a {inner.name!r} executor — inner= "
                "takes a mesh placement (MeshExecutor/MultiPodExecutor) "
                "or None/local"
            )
        if isinstance(inner, LocalExecutor):
            inner = None  # local inner ≡ the plain vmapped sweep
        if inner is not None and not isinstance(inner, MeshExecutor):
            raise ValueError(
                f"unsupported sweep inner executor {inner.name!r} — use "
                "MeshExecutor/MultiPodExecutor (or None for the local vmap)"
            )
        self.inner = inner
        if inner is not None:
            self.name = f"{inner.name}+sweep"

    def swept(self, key: str):
        return self.params.get(key)

    def scenario_template(self, tree: PyTree) -> PyTree:
        return jax.tree.map(lambda x: x[0], tree)

    def finalize(self, strategy, theta, state, data):
        from repro.api.strategy import Strategy

        if type(strategy).finalize is Strategy.finalize:
            return theta
        return jax.vmap(lambda th, st: strategy.finalize(th, st, data))(
            theta, state
        )

    def ledger_hops(self, strategy, data):
        # a multipod inner keeps its per-hop pricing — applied by the
        # engine to every scenario's ledger
        if self.inner is None:
            return None
        return self.inner.ledger_hops(strategy, data)

    def _resolve_targets(self, strategy, wire, extra=()):
        attrs = {
            k: v for k, v in self.params.items() if k not in self.RESERVED
        }
        targets = {}
        for k in attrs:
            if hasattr(strategy, k):
                targets[k] = strategy
            elif wire is not None and hasattr(wire, k):
                targets[k] = wire
            else:
                # transport-supplied extras: fault plans, chain-wire stages
                for obj in extra:
                    if obj is not None and hasattr(obj, k):
                        targets[k] = obj
                        break
                else:
                    raise ValueError(
                        f"swept parameter {k!r} is not an attribute of "
                        f"{type(strategy).__name__}, the wire, or the fault "
                        f"plan (reserved keys: {self.RESERVED})"
                    )
        return attrs, targets

    @staticmethod
    @contextmanager
    def _rebound(targets, vals):
        """Rebind swept strategy/wire attributes for the duration of one
        scenario's trace (the saved Python values are restored after)."""
        saved = {k: getattr(targets[k], k) for k in vals}
        try:
            for k, v in vals.items():
                setattr(targets[k], k, v)
            yield
        finally:
            for k, v in saved.items():
                setattr(targets[k], k, v)

    def _params_fingerprint(self):
        """Byte-level fingerprint of the swept values — the composed path
        closes over them (they become compiled constants), so they must
        key the program cache."""
        import numpy as np

        out = []
        for k in sorted(self.params):
            for leaf in jax.tree.leaves(self.params[k]):
                a = np.asarray(leaf)
                out.append((k, str(a.dtype), a.shape, a.tobytes()))
        return tuple(out)

    def run_update(
        self, *, strategy, data, carry, make_carry, make_step, xs, length,
        wire=None, cache_key=None, enter_loop=None, exit_loop=None,
        sweep_targets=(),
    ):
        attrs, targets = self._resolve_targets(strategy, wire, sweep_targets)
        stal = self.params.get("staleness")
        theta0s = self.params.get("theta0")

        # The scenario-batched carry is built OUTSIDE any cached program:
        # theta0 resolution can read data values, so baking it into a
        # memoized executable would pin the first fit's start point.
        if carry is None:
            if attrs or theta0s is not None:

                def build_carry(vals, th0):
                    with self._rebound(targets, vals):
                        return (
                            make_carry() if th0 is None
                            else make_carry(theta0=th0)
                        )

                carry = jax.vmap(
                    build_carry,
                    in_axes=(
                        {k: 0 for k in attrs},
                        None if theta0s is None else 0,
                    ),
                )(attrs, theta0s)
            else:
                # only "staleness" swept: every scenario starts from the
                # same carry; the lanes diverge through the read index
                c0 = make_carry()
                S = self.num_scenarios
                carry = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (S,) + x.shape), c0
                )

        # enter_loop is the overlap hook; sweeps never activate overlap
        # (Executor.overlap stays False here), so only the stats-completion
        # exit hook is threaded through — applied to the full (S, T, …)
        # stack, where the deferred collectives stay elementwise.
        if self.inner is None:

            def build():
                def prog(attrs_, stal_, c_, d_, x_):
                    def one(vals, st, c1):
                        with self._rebound(targets, vals):
                            return jax.lax.scan(
                                make_step(d_, st), c1, x_, length=length
                            )

                    c2, ys = jax.vmap(
                        one,
                        in_axes=(
                            {k: 0 for k in attrs},
                            None if stal is None else 0,
                            0,
                        ),
                    )(attrs_, stal_, c_)
                    if exit_loop is not None:
                        c2, ys = exit_loop(c2, ys)
                    return c2, ys

                return jax.jit(prog)

            key = None if cache_key is None else (
                "sweep-local", cache_key, tuple(sorted(attrs)),
                stal is None, xs is None, length, self.num_scenarios,
            )
            return dispatch(
                key, build, f"{self.name}-update", attrs, stal, carry, data, xs
            )

        # --- mesh-composed: scenario vmap INSIDE the shard_map body ---
        # Each shard vmaps the scan over scenarios, so the executable is
        # shard_map(vmap(scan)) — S scenarios per device.  The swept
        # values are compiled constants here, hence the fingerprint in
        # the cache key.
        def body(c, d, x):
            def one(vals, st, c1):
                with self._rebound(targets, vals):
                    return jax.lax.scan(
                        make_step(d, st), c1, x, length=length
                    )

            c2, ys = jax.vmap(
                one,
                in_axes=({k: 0 for k in attrs}, None if stal is None else 0, 0),
            )(attrs, stal, c)
            if exit_loop is not None:
                c2, ys = exit_loop(c2, ys)
            return c2, ys

        key = None if cache_key is None else (
            "sweep-composed", cache_key, tuple(sorted(attrs)),
            stal is None, length, self.num_scenarios,
            self._params_fingerprint(),
        )
        return self.inner.place_update(
            strategy=strategy, data=data, carry=carry, body=body, xs=xs,
            scenario_axis=True, cache_key=key,
        )

    def run_server(self, *, strategy, data, carry, make_step, schedule,
                   wire=None, cache_key=None):
        raise ValueError(
            "server transports walk one contact schedule sequentially — "
            "the sweep executor cannot batch them; use executor='local' "
            "(or 'mesh'/'multipod' for shard placement)"
        )


EXECUTORS = ("local", "mesh", "multipod", "sweep", "serve")
#: composed spec strings: the sweep's scenario vmap nested inside a mesh
#: placement (scenario values via ``fit(..., sweep={...})``)
COMPOSED_EXECUTORS = ("mesh+sweep", "multipod+sweep")


def make_executor(
    spec: str | Executor | None, sweep_params: dict | None = None
) -> Executor:
    """Resolve an executor spec.

    ``spec`` is an ``Executor`` instance, ``None``/``"local"``, ``"mesh"``
    (nodes over all local devices / the active mesh context),
    ``"multipod"`` (the ``("pod", "data")`` hierarchical placement with
    per-hop ledger pricing), ``"serve"`` (local fit, finalized model
    handed to a ``ServeEngine``), ``"sweep"``, or a composed
    ``"mesh+sweep"`` / ``"multipod+sweep"`` — the scenario vmap nested
    inside the shard_map body.  The sweep spec strings need their
    scenario values supplied as ``sweep_params`` (what ``fit``'s
    ``sweep=`` kwarg forwards)::

        make_executor("mesh+sweep", {"lr": jnp.asarray([0.02, 0.1])})
        # ≡ SweepExecutor({"lr": ...}, inner=MeshExecutor())

    Configured instances (``MeshExecutor(mesh)``, ``MultiPodExecutor(
    mesh, intra_price=, inter_price=)``, ``SweepExecutor(params,
    inner=)``, ``ServingExecutor(...)``) pass through unchanged.
    """
    if isinstance(spec, Executor):
        if sweep_params is not None:
            raise ValueError(
                "sweep= only applies to string executor specs — configure "
                "SweepExecutor(params, inner=...) directly instead"
            )
        return spec
    parts = tuple((spec or "local").split("+"))
    if "sweep" in parts:
        inner_parts = tuple(p for p in parts if p != "sweep")
        if len(inner_parts) + 1 != len(parts) or inner_parts not in (
            (), ("local",), ("mesh",), ("multipod",)
        ):
            raise ValueError(
                f"unknown executor {spec!r} — sweep composes as "
                f"{COMPOSED_EXECUTORS}"
            )
        if sweep_params is None:
            raise ValueError(
                "the sweep executor needs scenario parameters — pass "
                "fit(..., sweep={'lr': [...], ...}) alongside the spec "
                "string, or a configured api.SweepExecutor({...})"
            )
        inner = inner_parts[0] if inner_parts else None
        return SweepExecutor(sweep_params, inner=inner)
    if sweep_params is not None:
        base = spec or "local"
        hint = (
            f"executor='{base}+sweep' (or 'sweep')"
            if base in ("local", "mesh", "multipod")
            else f"one of {COMPOSED_EXECUTORS} or 'sweep'"
        )
        raise ValueError(
            f"sweep= scenario parameters need a sweep executor — {hint}"
        )
    if spec is None or spec == "local":
        return LocalExecutor()
    if spec == "mesh":
        return MeshExecutor()
    if spec == "multipod":
        return MultiPodExecutor()
    if spec == "serve":
        return ServingExecutor()
    raise ValueError(f"unknown executor {spec!r} — one of {EXECUTORS}")
