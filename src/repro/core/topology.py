"""Reduction topologies — WHICH LINK a message crosses, and what it costs.

The paper's cost model (§3, §5) does not price communication by byte
alone: the client↔server round trip is the expensive tier and the
intra-cluster reduction the cheap one.  A ``Topology`` makes that
distinction first-class: it is an ordered list of ``Hop``s, each naming
the mesh axes reduced at that stage (innermost first), a tier name for
the ledger, and a per-byte price.  ``core.allreduce.hierarchical_allreduce``
executes the hops as staged ``psum``s; ``CommLedger`` decomposes its byte
totals by tier through ``Topology.hop_messages``.

Two canonical instances:

* ``Topology.flat(axes)`` — one hop over every node axis at once: the
  classical undifferentiated client-server accounting (today's behavior).
* ``Topology.from_mesh(axes)`` — ``pod`` split out as its own outermost
  ``inter_pod`` hop, everything else reduced first as ``intra_pod`` —
  the hierarchical aggregation (intra-pod psum, then inter-pod
  allreduce) that Verbraeken et al. and Gu et al. identify as the
  scaling mechanism for the client-server architecture.

The byte decomposition telescopes so tiers always sum to the flat total:
with K node messages and g_h aggregation groups remaining after hop h
(g_0 = K), hop h carries g_{h-1} − g_h messages (every participant except
the group roots), and the outermost hop carries all g_{H-1} root pushes
to the server.  Σ_h m_h = K — exactly the flat uplink count.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Mapping

#: default per-byte prices by tier: the inter-pod (client↔server) link is
#: priced an order of magnitude above the intra-pod reduction, the
#: paper's expensive-vs-cheap tier split (override per ``Hop``).
DEFAULT_PRICES = {"flat": 1.0, "intra_pod": 1.0, "inter_pod": 10.0}


@dataclass(frozen=True)
class Hop:
    """One reduction stage: a joint psum over ``axes``, priced per byte."""

    axes: tuple  # mesh axis name(s) reduced together at this stage
    name: str  # ledger tier ("flat" / "intra_pod" / "inter_pod" / ...)
    price_per_byte: float = 1.0

    def __post_init__(self):
        axes = (self.axes,) if isinstance(self.axes, str) else tuple(self.axes)
        object.__setattr__(self, "axes", axes)

    def size(self, axis_sizes: Mapping[str, int]) -> int:
        s = 1
        for a in self.axes:
            s *= int(axis_sizes[a])
        return s


@dataclass(frozen=True)
class Topology:
    """Ordered reduction hops, innermost (cheapest) first."""

    hops: tuple

    def __post_init__(self):
        object.__setattr__(self, "hops", tuple(self.hops))
        if not self.hops:
            raise ValueError("a Topology needs at least one hop")
        seen = set()
        for hop in self.hops:
            for a in hop.axes:
                if a in seen:
                    raise ValueError(f"axis {a!r} appears in more than one hop")
                seen.add(a)

    @property
    def axes(self) -> tuple:
        """All mesh axes the topology reduces over, hop order."""
        return tuple(a for hop in self.hops for a in hop.axes)

    @property
    def tiers(self) -> tuple:
        return tuple(h.name for h in self.hops)

    # -- construction --------------------------------------------------------

    @staticmethod
    def flat(axes, *, name: str = "flat", price_per_byte: float | None = None):
        """One undifferentiated hop over every node axis — the classical
        single-tier client-server accounting."""
        price = DEFAULT_PRICES.get(name, 1.0) if price_per_byte is None else price_per_byte
        return Topology((Hop(axes=axes, name=name, price_per_byte=price),))

    @staticmethod
    def from_mesh(
        axes,
        *,
        pod_axis: str = "pod",
        intra_price: float | None = None,
        inter_price: float | None = None,
    ):
        """Split ``pod_axis`` out as the outermost ``inter_pod`` hop; the
        remaining node axes reduce first as one ``intra_pod`` hop.  A mesh
        without a pod axis degrades to the single-hop flat topology (so
        existing 1-D node meshes keep bit-exact behavior)."""
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        intra = tuple(a for a in axes if a != pod_axis)
        if pod_axis not in axes:
            # single-tier mesh: the whole reduction is the "intra" link
            return Topology.flat(intra, price_per_byte=intra_price)
        intra_p = DEFAULT_PRICES["intra_pod"] if intra_price is None else intra_price
        inter_p = DEFAULT_PRICES["inter_pod"] if inter_price is None else inter_price
        hops = []
        if intra:
            hops.append(Hop(axes=intra, name="intra_pod", price_per_byte=intra_p))
        hops.append(Hop(axes=(pod_axis,), name="inter_pod", price_per_byte=inter_p))
        return Topology(tuple(hops))

    # -- calibration ---------------------------------------------------------

    @staticmethod
    def calibrated(mesh, *, pod_axis: str = "pod"):
        """``from_mesh`` with prices measured on ``mesh`` by
        ``calibrate_prices`` instead of the ×1/×10 defaults."""
        prices = calibrate_prices(mesh, pod_axis=pod_axis)
        return Topology.from_mesh(
            tuple(mesh.axis_names),
            pod_axis=pod_axis,
            intra_price=prices["intra_pod"],
            inter_price=prices["inter_pod"],
        )

    # -- ledger decomposition ------------------------------------------------

    def hop_messages(self, num_nodes: int, axis_sizes: Mapping[str, int]):
        """Decompose K per-round node messages across tiers.

        Returns ordered ``[(tier, messages, price_per_byte), ...]`` with
        messages summing exactly to ``num_nodes``: hop h carries
        ``g_{h-1} − g_h`` messages (g_h = aggregation groups remaining
        after hop h; g_0 = K) and the outermost hop carries all
        ``g_{H-1}`` group-root pushes to the server.
        """
        sizes = [h.size(axis_sizes) for h in self.hops]
        # groups remaining after hop h = product of the outer hop sizes
        groups = []
        g = 1
        for s in reversed(sizes[1:]):
            g *= s
            groups.append(g)
        groups = list(reversed(groups)) + [0]  # g_H unused; sentinel
        out = []
        g_prev = int(num_nodes)
        for i, hop in enumerate(self.hops):
            if i == len(self.hops) - 1:
                m = g_prev  # every top-level group root pushes to the server
            else:
                g_next = groups[i]
                if g_prev % g_next:
                    raise ValueError(
                        f"{num_nodes} nodes do not divide into {g_next} "
                        f"groups at hop {hop.name!r}"
                    )
                m = g_prev - g_next
                g_prev = g_next
            out.append((hop.name, m, hop.price_per_byte))
        return out


# -- price calibration -------------------------------------------------------

#: memoized calibration results per (device set, pod split, sample size):
#: the microbenchmark is a one-shot property of the host, not of any fit
_CALIBRATION_CACHE: dict = {}


def calibrate_prices(
    mesh,
    *,
    pod_axis: str = "pod",
    sample_kib: int = 256,
    repeats: int = 5,
    cache: bool = True,
) -> dict:
    """One-shot per-hop bandwidth microbenchmark on the actual ``mesh``.

    Times a jitted psum over the intra-pod axes and one over the pod
    axis (best of ``repeats`` over a ``sample_kib`` f32 payload),
    normalizes so the intra tier costs 1.0 per byte, and returns a price
    mapping shaped like ``DEFAULT_PRICES``::

        {"flat": 1.0, "intra_pod": 1.0, "inter_pod": <measured ratio>,
         "seconds": {...}, "sample_bytes": ..., "calibrated": True}

    Feed the prices into ``Topology.from_mesh(intra_price=...,
    inter_price=...)`` (or use ``Topology.calibrated``) so
    ``CommLedger.priced_cost()`` reflects the host that actually ran,
    not the ×1/×10 guess.  Results are memoized per device set — the
    measurement is a property of the machine, so every fit on the same
    mesh shares one calibration.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    key = (
        tuple(str(d) for d in mesh.devices.flatten()),
        axes,
        pod_axis,
        int(sample_kib),
    )
    if cache and key in _CALIBRATION_CACHE:
        return dict(_CALIBRATION_CACHE[key])

    n = max((int(sample_kib) * 1024) // 4, 128)
    x = jnp.zeros((n,), jnp.float32)

    def _timed(hop_axes) -> float | None:
        if not hop_axes:
            return None
        fn = jax.jit(
            shard_map(
                lambda v: jax.lax.psum(v, hop_axes),
                mesh=mesh,
                in_specs=P(),
                out_specs=P(),
                check_vma=False,
            )
        )
        jax.block_until_ready(fn(x))  # compile outside the timed region
        best = None
        for _ in range(max(int(repeats), 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    intra = tuple(a for a in axes if a != pod_axis)
    t_intra = _timed(intra)
    t_inter = _timed((pod_axis,) if pod_axis in axes else ())

    if t_intra and t_inter:
        ratio = max(t_inter / t_intra, 1e-3)
    else:
        ratio = DEFAULT_PRICES["inter_pod"] if t_inter else 1.0
    out = {
        "flat": 1.0,
        "intra_pod": 1.0,
        "inter_pod": float(ratio),
        "seconds": {"intra_pod": t_intra, "inter_pod": t_inter},
        "sample_bytes": n * 4,
        "calibrated": True,
    }
    _CALIBRATION_CACHE[key] = dict(out)
    return out
