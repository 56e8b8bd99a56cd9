"""Sharding rules: logical-axis activation constraints + name-based param specs.

Strategy (DESIGN.md §5):

* params — tensor parallel on the ``model`` axis (attention heads, FFN
  hidden, experts, vocab), optional FSDP on the ``data``/``pod`` axes for
  architectures whose parameter+optimizer state exceeds per-chip HBM;
* activations — batch on (``pod``, ``data``); sequence on ``data`` when the
  batch is too small to shard (``long_500k`` decode); hidden/heads on
  ``model``.

A ``MeshContext`` (set by the launcher) carries the mesh + logical→physical
axis mapping; model code calls ``maybe_shard(x, "batch", "seq", None)``
which becomes ``with_sharding_constraint`` under a mesh and a no-op without.
"""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis of type Auto.

    ``jax.make_mesh`` builds Explicit axes on JAX 0.9, and on Explicit
    axes ``with_sharding_constraint`` and scatter/gather over sharded
    operands raise.  This repo places arrays by constraint
    (``maybe_shard``) and by ``device_put`` (``place_params``) and lets
    the compiler propagate the rest, so the ``launch.mesh`` builders,
    ``MeshContext`` and ``ServeEngine`` pass their meshes through here.
    """
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@dataclass
class MeshContext:
    mesh: Mesh
    # logical axis name -> physical mesh axis (or tuple of axes) or None
    logical: dict = field(default_factory=dict)
    fsdp: bool = False

    def __post_init__(self):
        self.mesh = auto_axes(self.mesh)

    @property
    def batch_axes(self):
        return self.logical.get("batch")

    @property
    def model_axis(self):
        return self.logical.get("model")

    @property
    def node_axes(self) -> tuple:
        """Physical mesh axes that place the paper's K nodes (data
        parallelism) — what the mesh executor shards the node axis over."""
        return tuple(a for a in self.mesh.axis_names if a in ("pod", "data"))

    @property
    def pod_axis(self) -> str | None:
        """The inter-pod tier's mesh axis, when this mesh spans pods."""
        return "pod" if "pod" in self.mesh.axis_names else None

    @property
    def intra_pod_axes(self) -> tuple:
        """Node axes below the pod tier (the cheap intra-pod reduction)."""
        return tuple(a for a in self.node_axes if a != "pod")

    def topology(self, **prices):
        """The reduction ``core.topology.Topology`` this mesh implies:
        hierarchical (intra-pod psum + inter-pod allreduce) when a pod
        axis exists, flat otherwise.  ``prices`` forwards
        ``intra_price``/``inter_price`` per-byte hop prices."""
        from repro.core.topology import Topology

        return Topology.from_mesh(self.node_axes, **prices)


def set_mesh_context(ctx: MeshContext | None):
    _ctx.value = ctx


def current_mesh_context() -> MeshContext | None:
    return getattr(_ctx, "value", None)


def maybe_shard(x: jnp.ndarray, *logical_axes) -> jnp.ndarray:
    """Apply a sharding constraint if a mesh context is active.

    ``logical_axes`` entries are logical names ("batch", "seq", "model",
    "expert", ...) or None; unknown names map to None (replicated).
    """
    ctx = current_mesh_context()
    if ctx is None:
        return x
    spec = P(*[ctx.logical.get(a) if a is not None else None for a in logical_axes])
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ----------------------------------------------------------------------------
# Parameter partition specs (name-based rules)
# ----------------------------------------------------------------------------
#
# Each rule: (path regex, spec builder).  Builders receive (ndim, model, fsdp)
# where `model`/`fsdp` are the physical axis names (fsdp may be None) and must
# return a PartitionSpec of length == ndim of the *unstacked* leaf; leading
# scan/stack dims are padded with None automatically (we pad on the left to
# the leaf's actual ndim).

def _pad(spec_tail: tuple, ndim: int) -> P:
    pad = ndim - len(spec_tail)
    if pad < 0:  # leaf smaller than rule (e.g. reduced configs) — replicate
        return P()
    return P(*((None,) * pad + spec_tail))


def _rules(model, fsdp, expert_axes=None):
    # NOTE: order matters — first match wins.
    e = expert_axes if expert_axes is not None else model
    e_fsdp = None if expert_axes is not None else fsdp
    return [
        # embeddings / lm head: vocab over model, d over fsdp
        (r"embed/embedding$", (model, fsdp)),
        (r"lm_head/kernel$", (fsdp, model)),
        # MoE experts: expert dim over model (expert parallelism); with
        # ``expert_axes`` the expert dim spans several axes (2-D EP) and is
        # never FSDP-gathered
        (r"experts/w_gate$", (e, e_fsdp, None)),
        (r"experts/w_up$", (e, e_fsdp, None)),
        (r"experts/w_down$", (e, None, e_fsdp)),
        (r"router/kernel$", (None, None)),
        # attention (GQA)
        (r"\bwq/kernel$", (fsdp, model)),
        (r"\bwk/kernel$", (fsdp, model)),
        (r"\bwv/kernel$", (fsdp, model)),
        (r"\bwo/kernel$", (model, fsdp)),
        (r"\bw(q|k|v)/bias$", (model,)),
        # MLA
        (r"w_dq/kernel$", (fsdp, None)),
        (r"w_uq/kernel$", (None, model)),
        (r"w_dkv/kernel$", (fsdp, None)),
        (r"w_kr/kernel$", (fsdp, None)),
        (r"w_uk/kernel$", (None, model)),
        (r"w_uv/kernel$", (None, model)),
        (r"w_o/kernel$", (model, fsdp)),
        # dense FFN
        (r"w_gate/kernel$", (fsdp, model)),
        (r"w_up/kernel$", (fsdp, model)),
        (r"w_down/kernel$", (model, fsdp)),
        (r"w_in/kernel$", (fsdp, model)),
        (r"w_out/kernel$", (model, fsdp)),
        # mamba
        (r"in_proj/kernel$", (fsdp, model)),
        (r"conv_w$", (None, model)),
        (r"conv_b$", (model,)),
        (r"x_proj/kernel$", (model, None)),
        (r"dt_proj/kernel$", (None, model)),
        (r"dt_proj/bias$", (model,)),
        (r"A_log$", (model, None)),
        (r"\bD$", (model,)),
        (r"out_proj/kernel$", (model, fsdp)),
        # mLSTM
        (r"up_proj/kernel$", (fsdp, model)),
        (r"down_proj/kernel$", (model, fsdp)),
        (r"w_[ifzo]/kernel$", (fsdp, None)),
        (r"mh_norm/scale$", (model,)),
        # sLSTM ffn
        (r"ffn_up/kernel$", (fsdp, model)),
        (r"ffn_down/kernel$", (model, fsdp)),
        # everything else (norms, biases, small projections): replicated
    ]


def partition_params(params, *, model_axis="model", fsdp_axis=None,
                     expert_axes=None):
    """Build a PartitionSpec pytree matching ``params`` via name rules.
    ``model_axis=None`` disables tensor parallelism (pure DP/FSDP);
    ``expert_axes`` overrides the expert-dim sharding (2-D EP)."""
    rules = _rules(model_axis, fsdp_axis, expert_axes)
    compiled = [(re.compile(rx), tail) for rx, tail in rules]

    def assign(path, leaf):
        pstr = "/".join(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path
        )
        for rx, tail in compiled:
            if rx.search(pstr):
                return _pad(tail, leaf.ndim)
        return P()

    return jax.tree_util.tree_map_with_path(assign, params)


def make_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def place_params(mesh: Mesh, params, *, model_axis="model", fsdp_axis=None,
                 expert_axes=None):
    """Partition ``params`` by the name rules and put them on ``mesh`` in
    one step.  Axis names absent from the mesh degrade to replication, so
    callers (e.g. the serving engine) can pass any mesh — a pure-data mesh
    simply replicates every parameter."""
    model = model_axis if model_axis in mesh.axis_names else None
    fsdp = fsdp_axis if fsdp_axis and fsdp_axis in mesh.axis_names else None
    if expert_axes is not None:
        ea = (expert_axes,) if isinstance(expert_axes, str) else expert_axes
        if not all(a in mesh.axis_names for a in ea):
            expert_axes = None
    spec = partition_params(
        params, model_axis=model, fsdp_axis=fsdp, expert_axes=expert_axes
    )
    return jax.device_put(params, make_shardings(mesh, spec))
