"""HLO collective parsing + roofline model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.telemetry.hlo import _shape_bytes, collective_stats
from repro.telemetry.roofline import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    model_flops_train,
    roofline,
)


def test_shape_bytes():
    assert _shape_bytes("f32[128,1024]") == 128 * 1024 * 4
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(f32[4], s32[2])") == 16 + 8
    assert _shape_bytes("pred[8]") == 8


def test_collective_stats_detects_psum():
    mesh = jax.make_mesh((jax.device_count(),), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    @jax.jit
    def f(v):
        return jax.lax.with_sharding_constraint(
            v, NamedSharding(mesh, P())
        ) + 0.0

    # force an all-reduce via shard_map psum
    from jax import shard_map

    g = shard_map(
        lambda v: jax.lax.psum(v, "x"),
        mesh=mesh,
        in_specs=P("x"),
        out_specs=P(),
    )
    txt = jax.jit(g).lower(jnp.ones((jax.device_count() * 4,))).compile().as_text()
    stats = collective_stats(txt)
    assert stats.get("all-reduce", {}).get("count", 0) >= 1
    assert stats["total_bytes"] > 0


def test_roofline_terms():
    r = roofline(
        flops_per_device=PEAK_FLOPS_BF16,  # exactly 1 second of compute
        bytes_per_device=HBM_BW * 2.0,  # 2 seconds of HBM
        collective_bytes_per_device=ICI_BW * 0.5,
        chips=256,
        model_flops=PEAK_FLOPS_BF16 * 256 * 0.5,
    )
    np.testing.assert_allclose(r.compute_s, 1.0)
    np.testing.assert_allclose(r.memory_s, 2.0)
    np.testing.assert_allclose(r.collective_s, 0.5)
    assert r.dominant == "memory"
    np.testing.assert_allclose(r.useful_ratio, 0.5)


def test_model_flops():
    assert model_flops_train(1e9, 1e6) == 6e15


def test_costprobe_segment_math():
    """combine(): full = base + Σ (R_s − 1)·marginal_s."""
    from repro.telemetry import costprobe

    # emulate the probe result combination with synthetic numbers
    base = {"flops": 10.0, "bytes": 100.0, "coll": 1.0}
    seg_plus = {"flops": 14.0, "bytes": 130.0, "coll": 1.5}  # marginal = 4/30/0.5
    R = 10
    expect_flops = 10.0 + (R - 1) * 4.0
    got = base["flops"] + (seg_plus["flops"] - base["flops"]) * (R - 1)
    assert got == expect_flops


def test_parse_replica_groups_explicit():
    from repro.telemetry.hlo import parse_replica_groups

    assert parse_replica_groups("{{0,1},{2,3}}") == [[0, 1], [2, 3]]
    assert parse_replica_groups("{{0,2,4,6},{1,3,5,7}}") == [
        [0, 2, 4, 6], [1, 3, 5, 7]
    ]


def test_parse_replica_groups_iota():
    from repro.telemetry.hlo import parse_replica_groups

    assert parse_replica_groups("[2,2]<=[4]") == [[0, 1], [2, 3]]
    # transposed iota: arange(4).reshape(2,2).T -> groups {0,2},{1,3}
    assert parse_replica_groups("[2,2]<=[2,2]T(1,0)") == [[0, 2], [1, 3]]
    assert parse_replica_groups("bogus") is None


def test_mesh_pod_map():
    from repro.telemetry.hlo import mesh_pod_map

    class FakeMesh:
        axis_names = ("pod", "data")
        shape = {"pod": 2, "data": 4}

    pod_of = mesh_pod_map(FakeMesh())
    assert [pod_of[i] for i in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]

    class NoPod:
        axis_names = ("data",)
        shape = {"data": 4}

    assert set(mesh_pod_map(NoPod()).values()) == {0}


def test_collective_stats_pod_attribution():
    """Synthetic per-device HLO: one intra-pod and one inter-pod
    all-reduce classified by their replica groups against a 2-pod map."""
    from repro.telemetry.hlo import collective_stats

    hlo = """
  %ar0 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %ar1 = f32[4]{0} all-reduce(f32[4]{0} %y), replica_groups={{0,2},{1,3}}, to_apply=%add
"""
    pod_of = {0: 0, 1: 0, 2: 1, 3: 1}
    stats = collective_stats(hlo, pod_of=pod_of)
    assert stats["all-reduce"]["count"] == 2
    assert stats["by_tier"]["intra_pod"] == {"count": 1, "bytes": 32}
    assert stats["by_tier"]["inter_pod"] == {"count": 1, "bytes": 16}


def test_collective_stats_pod_attribution_real_lowering():
    """A real staged hierarchical psum lowers to collectives whose
    replica groups classify as intra- then inter-pod (single-device runs
    degenerate to intra-pod only)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.allreduce import hierarchical_allreduce
    from repro.core.topology import Topology
    from repro.launch.mesh import make_multipod_mesh
    from repro.telemetry.hlo import collective_stats, mesh_pod_map

    mesh = make_multipod_mesh()
    topo = Topology.from_mesh(("pod", "data"))

    def f(v):
        return hierarchical_allreduce(v, topo.hops)

    g = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P()
    ))
    n = mesh.shape["pod"] * mesh.shape["data"]
    txt = g.lower(jnp.ones((n * 4,))).compile().as_text()
    stats = collective_stats(txt, pod_of=mesh_pod_map(mesh))
    by_tier = stats.get("by_tier", {})
    assert stats["total_count"] >= 1
    # everything must be attributed (no unparseable replica groups)
    assert by_tier.get("unattributed", {"count": 0})["count"] == 0
    if mesh.shape["pod"] > 1:
        assert by_tier["inter_pod"]["bytes"] > 0
        assert by_tier["intra_pod"]["bytes"] > 0
