"""Executor-layer tests: the same Strategy/Transport/Wire program must
produce the same fit under every placement.

* ``local`` — bit-exact with the pre-executor engine (covered by
  ``test_api_fit.py`` running entirely on the default executor; here we
  only check the explicit spec resolves to the same run).
* ``mesh``  — shard_map node placement matches the stacked scan within fp
  tolerance (reduction order differs), with IDENTICAL ledgers; exercised
  on however many devices the process has (the CI mesh job forces 8 fake
  CPU devices via XLA_FLAGS) plus an explicit 8-device subprocess check.
* ``multipod`` — the hierarchical ``("pod", "data")`` placement is
  BIT-EXACT with the flat mesh executor on the same mesh (both stage the
  reduction through the same mesh-derived topology; only the ledger
  accounting differs), and the per-hop ledger decomposition sums to the
  flat totals.
* ``sweep`` — a vmapped S-scenario batch matches S independent ``fit``
  calls, with per-scenario ledgers bit-for-bit equal on byte totals.
* ``mesh+sweep`` / ``multipod+sweep`` — the composed executor (scenario
  vmap INSIDE the shard_map body) matches S independent fits on the
  same inner executor: theta and per-scenario ledger totals bit-exact,
  trajectory to fp tolerance (the vmapped loss-metric reduction orders
  differently).
* mesh-placed SERVER transports — ``sequential_server``/``stale_server``
  under ``executor="mesh"`` walk the same sequential schedule with each
  contact's ``local_step`` masked onto the owning shard; bit-exact with
  the local walk (the ``from_owner`` psum adds only zeros).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import schedules
from repro.ml.linear import lsq_loss


def _make_problem(K=8, Nk=10, n=5, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(K, Nk, n)))
    w = jnp.asarray(rng.normal(size=(n,)))
    y = jnp.einsum("kni,i->kn", X, w)
    return X, y, w, n


class TestMeshEquivalence:
    """mesh executor ≡ local executor on whatever devices this process has
    (1 in a plain run; 8 under the CI mesh job's XLA_FLAGS)."""

    @pytest.mark.parametrize(
        "transport,kw",
        [("allreduce", {}), ("delay_line", {"staleness": 2})],
    )
    def test_matches_local(self, transport, kw):
        X, y, w, n = _make_problem()
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport=transport, steps=40, **kw)
        mesh = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport=transport, steps=40, executor="mesh", **kw)
        np.testing.assert_allclose(np.asarray(mesh.theta), np.asarray(loc.theta),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(mesh.trajectory),
                                   np.asarray(loc.trajectory),
                                   rtol=1e-5, atol=1e-6)
        assert mesh.ledger.summary() == loc.ledger.summary()
        assert mesh.metrics["executor"] == "mesh"

    def test_lbfgs_mean_aggregation(self):
        """aggregate_op="mean" completes with pmean across shards."""
        X, y, w, n = _make_problem()
        loc = api.fit(api.LBFGS(lsq_loss), (X, y), transport="allreduce", steps=15)
        mesh = api.fit(api.LBFGS(lsq_loss), (X, y), transport="allreduce",
                       steps=15, executor="mesh")
        np.testing.assert_allclose(np.asarray(mesh.theta), np.asarray(loc.theta),
                                   rtol=1e-4, atol=1e-5)
        assert mesh.ledger.summary() == loc.ledger.summary()

    def test_compressed_wire_encodes_per_shard(self):
        """top-k + EF composes with the mesh placement: the per-node
        encode runs inside the shard_map body, byte accounting unchanged."""
        X, y, w, n = _make_problem()
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", wire="topk:0.5+ef", steps=25)
        mesh = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="allreduce", wire="topk:0.5+ef", steps=25,
                       executor="mesh")
        assert mesh.ledger.summary() == loc.ledger.summary()
        assert float(mesh.trajectory[-1]) < float(mesh.trajectory[0])
        # compression actually metered: below the dense allreduce cost
        dense_up = 25 * X.shape[0] * n * 4
        assert mesh.ledger.uplink_bytes < dense_up

    def test_resume_carry_crosses_executors(self):
        """A mesh run's carry resumes on the local executor (the wire/EF
        state is reassembled to its global layout at the shard_map exit)."""
        X, y, w, n = _make_problem()
        full = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="allreduce", steps=30)
        first = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                        transport="allreduce", steps=15, executor="mesh")
        second = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                         transport="allreduce", steps=15,
                         carry=first.metrics["carry"])
        np.testing.assert_allclose(np.asarray(second.theta),
                                   np.asarray(full.theta),
                                   rtol=1e-5, atol=1e-6)


class TestMeshValidation:
    def test_server_transport_needs_shardable_data(self):
        """Closure-based strategies (no data to shard) cannot mesh-place
        a server transport — the masked-compute placement needs a data
        shard per node."""
        X, y, w, n = _make_problem(K=4)
        with pytest.raises(ValueError, match="local"):
            api.fit(api.FunctionStrategy(lambda k, t: t, num_nodes=4),
                    transport="sequential_server",
                    schedule=schedules.round_robin(4, 2),
                    theta0=jnp.zeros(n), executor="mesh")

    def test_admm_rejected(self):
        from repro.ml.linear import lasso_prox_builder

        X, y, w, n = _make_problem(K=4)
        with pytest.raises(ValueError, match="local"):
            api.fit(api.ProxStrategy(lasso_prox_builder), (X, y),
                    transport="admm_consensus", steps=5, g="l1", g_lam=0.1,
                    executor="mesh")

    def test_python_aggregate_override_rejected(self):
        """Strategies that override aggregate() with arbitrary Python
        cannot be placed on a mesh — only op-based reductions psum
        (set aggregate_op, e.g. the cascade SVM's "any" union)."""

        class Weird(api.GradientDescent):
            def aggregate(self, msgs):
                return jnp.median(msgs, axis=0)

        X, y, w, n = _make_problem()
        with pytest.raises(NotImplementedError, match="aggregate"):
            api.fit(Weird(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=2, executor="mesh")

    def test_uneven_placement_rejected(self):
        if jax.device_count() == 1:
            pytest.skip("needs >1 device to make K indivisible")
        K = jax.device_count() + 1
        X, y, w, n = _make_problem(K=K)
        with pytest.raises(ValueError, match="evenly"):
            api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=3, executor="mesh")

    def test_mesh_context_reuse(self):
        """An active sharding.rules.MeshContext supplies the mesh."""
        from repro.launch.mesh import make_node_mesh
        from repro.sharding.rules import MeshContext, set_mesh_context

        X, y, w, n = _make_problem()
        set_mesh_context(MeshContext(mesh=make_node_mesh(), logical={}))
        try:
            res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                          transport="allreduce", steps=10, executor="mesh")
        finally:
            set_mesh_context(None)
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=10)
        np.testing.assert_allclose(np.asarray(res.theta), np.asarray(loc.theta),
                                   rtol=1e-5, atol=1e-6)


class TestMeshServerTransports:
    """The §5 sequential schedule placed on the mesh: each contact's
    local_step runs masked on the shard owning the contacted node, the
    push is replicated with one psum — BIT-exact with the local walk
    (summing the non-owners' zeros is exact in fp)."""

    @pytest.mark.parametrize("transport", ["sequential_server", "stale_server"])
    @pytest.mark.parametrize("wire", ["dense", "topk:0.5+ef"])
    def test_matches_local(self, transport, wire):
        X, y, w, n = _make_problem()
        sched = schedules.round_robin(8, 5)
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport=transport, schedule=sched, wire=wire)
        mesh = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport=transport, schedule=sched, wire=wire,
                       executor="mesh")
        np.testing.assert_array_equal(np.asarray(loc.theta),
                                      np.asarray(mesh.theta))
        np.testing.assert_array_equal(np.asarray(loc.trajectory),
                                      np.asarray(mesh.trajectory))
        assert mesh.ledger.summary() == loc.ledger.summary()
        assert mesh.metrics["executor"] == "mesh"

    def test_random_schedule_matches_local(self):
        X, y, w, n = _make_problem()
        sched = schedules.asynchronous(jax.random.PRNGKey(0), 8, 40)
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="sequential_server", schedule=sched)
        mesh = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="sequential_server", schedule=sched,
                       executor="mesh")
        np.testing.assert_array_equal(np.asarray(loc.theta),
                                      np.asarray(mesh.theta))

    def test_multipod_decomposes_server_bytes(self):
        """The multipod placement accepts server transports too, with
        the contact traffic attributed across tiers (summing exactly to
        the flat totals)."""
        X, y, w, n = _make_problem()
        sched = schedules.round_robin(8, 5)
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="sequential_server", schedule=sched)
        mp = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                     transport="sequential_server", schedule=sched,
                     executor="multipod")
        np.testing.assert_array_equal(np.asarray(loc.theta),
                                      np.asarray(mp.theta))
        s = mp.ledger.summary()
        assert set(s["by_hop"]) == {"intra_pod", "inter_pod"}
        assert sum(v["total_bytes"] for v in s["by_hop"].values()) \
            == loc.ledger.total_bytes

    def test_kwindows_server_on_mesh(self):
        """A server strategy that mixes shard-local data indexing with
        global slot/key indexing (node_global_index) places bit-exactly."""
        from repro.ml.kwindows import KWindowsStrategy

        rng = np.random.default_rng(0)
        pts = np.concatenate([rng.normal(loc=c, scale=0.3, size=(80, 2))
                              for c in [(0, 0), (3, 3), (-3, 2)]])
        rng.shuffle(pts)
        Xs = jnp.asarray(pts.reshape(8, 30, 2))
        sched = schedules.round_robin(8, 1)

        def strat():
            return KWindowsStrategy(jax.random.PRNGKey(0), num_windows=3, r=1.0)

        loc = api.fit(strat(), Xs, transport="sequential_server",
                      schedule=sched)
        mesh = api.fit(strat(), Xs, transport="sequential_server",
                       schedule=sched, executor="mesh")
        for f in loc.theta._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(loc.theta, f)),
                np.asarray(getattr(mesh.theta, f)))
        assert mesh.ledger.summary() == loc.ledger.summary()

    def test_resume_carry_crosses_executors(self):
        """A mesh server run's carry resumes on the local executor (the
        wire state reassembles to its global layout at the shard_map
        exit) and vice versa."""
        X, y, w, n = _make_problem()
        sched = schedules.round_robin(8, 6)
        full = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="sequential_server", schedule=sched,
                       wire="topk:0.5+ef")
        half = schedules.round_robin(8, 3)
        a = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="sequential_server", schedule=half,
                    wire="topk:0.5+ef", executor="mesh")
        b = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="sequential_server", schedule=half,
                    wire="topk:0.5+ef", carry=a.metrics["carry"])
        np.testing.assert_array_equal(np.asarray(b.theta),
                                      np.asarray(full.theta))

    def test_replicate_data_strategy_rejected(self):
        """Replicate-data strategies have nothing to place — every shard
        reads the whole dataset — so the mesh server path refuses them."""
        class Rep(api.GradientDescent):
            replicate_data = True

        X, y, w, n = _make_problem()
        with pytest.raises(ValueError, match="replicate_data"):
            api.fit(Rep(lsq_loss, lr=0.1), (X, y),
                    transport="sequential_server",
                    schedule=schedules.round_robin(8, 2), executor="mesh")


class TestMeshEightDevices:
    """The acceptance check proper: 8 fake CPU devices in a subprocess
    (XLA device count is fixed at jax init, so in-process tests can't
    force it).  Covers the update transports, the mesh-placed SERVER
    transports (bitwise vs local), and the composed ``mesh+sweep``
    executor (S=4 scenarios bit-exact vs 4 independent mesh fits on
    theta and per-scenario ledger totals; trajectory to fp tolerance —
    the vmapped metric mean reduces in a different order)."""

    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro import api
from repro.core import schedules
from repro.ml.linear import lsq_loss

def bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return bool(a.shape == b.shape and
                (a.view(np.uint32) == b.view(np.uint32)).all())

rng = np.random.default_rng(0)
X = jnp.asarray(rng.normal(size=(8, 10, 5)))
w = jnp.asarray(rng.normal(size=(5,)))
y = jnp.einsum("kni,i->kn", X, w)
out = {"num_devices": jax.device_count()}
for transport, kw in [("allreduce", {}), ("delay_line", {"staleness": 2})]:
    loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                  transport=transport, steps=40, **kw)
    mesh = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                   transport=transport, steps=40, executor="mesh", **kw)
    out[transport] = {
        "theta_close": bool(np.allclose(loc.theta, mesh.theta,
                                        rtol=1e-5, atol=1e-6)),
        "traj_close": bool(np.allclose(loc.trajectory, mesh.trajectory,
                                       rtol=1e-5, atol=1e-6)),
        "ledger_equal": loc.ledger.summary() == mesh.ledger.summary(),
    }

# mesh-placed server transports: bitwise vs the local sequential walk
sched = schedules.round_robin(8, 5)
for transport in ("sequential_server", "stale_server"):
    for wire in ("dense", "topk:0.5+ef"):
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport=transport, schedule=sched, wire=wire)
        mesh = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport=transport, schedule=sched, wire=wire,
                       executor="mesh")
        out[f"{transport}/{wire}"] = {
            "theta_bitwise": bitwise(loc.theta, mesh.theta),
            "traj_bitwise": bitwise(loc.trajectory, mesh.trajectory),
            "ledger_equal": loc.ledger.summary() == mesh.ledger.summary(),
        }

# ACCEPTANCE — mesh+sweep: S=4 scenarios vs 4 independent mesh fits
LRS = (0.02, 0.05, 0.1, 0.2)
res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
              transport="allreduce", steps=40, executor="mesh+sweep",
              sweep={"lr": jnp.asarray(LRS)})
acc = {"theta_bitwise": True, "traj_close": True, "ledger_equal": True,
       "executor_name": res.metrics["executor"]}
for i, lr in enumerate(LRS):
    solo = api.fit(api.GradientDescent(lsq_loss, lr=lr), (X, y),
                   transport="allreduce", steps=40, executor="mesh")
    acc["theta_bitwise"] &= bitwise(res.theta[i], solo.theta)
    acc["traj_close"] &= bool(np.allclose(res.trajectory[i], solo.trajectory,
                                          rtol=1e-5, atol=1e-7))
    acc["ledger_equal"] &= (
        res.ledger[i].uplink_bytes == solo.ledger.uplink_bytes
        and res.ledger[i].downlink_bytes == solo.ledger.downlink_bytes
        and res.ledger[i].rounds == solo.ledger.rounds)
out["mesh+sweep"] = acc

# multipod inner: per-hop split preserved per scenario
res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
              transport="delay_line", staleness=1, steps=30,
              executor="multipod+sweep", sweep={"lr": jnp.asarray(LRS)})
split_ok = True
for led in res.ledger:
    s = led.summary()
    split_ok &= set(s["by_hop"]) == {"intra_pod", "inter_pod"}
    split_ok &= all(v["total_bytes"] > 0 for v in s["by_hop"].values())
    split_ok &= sum(v["total_bytes"] for v in s["by_hop"].values()) \
        == led.total_bytes
out["multipod+sweep"] = {"split_per_scenario": bool(split_ok)}

# reduce-scatter staging + comm/compute overlap: both knobs bit-exact on
# a real 8-shard mesh (the staged additions happen in the same order)
rs_on = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                transport="allreduce", steps=30,
                executor=api.MeshExecutor(reduce_scatter=True))
rs_off = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                 transport="allreduce", steps=30,
                 executor=api.MeshExecutor(reduce_scatter=False))
out["reduce_scatter"] = {
    "theta_bitwise": bitwise(rs_on.theta, rs_off.theta),
    "ledger_equal": rs_on.ledger.summary() == rs_off.ledger.summary(),
}
ov_on = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                transport="delay_line", staleness=2, steps=30,
                executor=api.MeshExecutor(overlap=True))
ov_off = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                 transport="delay_line", staleness=2, steps=30,
                 executor=api.MeshExecutor(overlap=False))
resumed = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                  transport="delay_line", staleness=2, steps=15,
                  executor=api.MeshExecutor(overlap=True))
resumed = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                  transport="delay_line", staleness=2, steps=15,
                  executor=api.MeshExecutor(overlap=False),
                  carry=resumed.metrics["carry"])
out["overlap"] = {
    "theta_bitwise": bitwise(ov_on.theta, ov_off.theta),
    "traj_bitwise": bitwise(ov_on.trajectory, ov_off.trajectory),
    "ledger_equal": ov_on.ledger.summary() == ov_off.ledger.summary(),
    "resume_bitwise": bitwise(resumed.theta, ov_off.theta),
}
print(json.dumps(out))
"""

    def test_mesh_matches_local_on_8_devices(self, fake_devices):
        out = fake_devices(self.SCRIPT)
        assert out["num_devices"] == 8
        for transport in ("allreduce", "delay_line"):
            assert out[transport] == {
                "theta_close": True, "traj_close": True, "ledger_equal": True
            }, out
        for transport in ("sequential_server", "stale_server"):
            for wire in ("dense", "topk:0.5+ef"):
                assert out[f"{transport}/{wire}"] == {
                    "theta_bitwise": True, "traj_bitwise": True,
                    "ledger_equal": True,
                }, out
        assert out["mesh+sweep"] == {
            "theta_bitwise": True, "traj_close": True, "ledger_equal": True,
            "executor_name": "mesh+sweep",
        }, out
        assert out["multipod+sweep"] == {"split_per_scenario": True}, out
        assert out["reduce_scatter"] == {
            "theta_bitwise": True, "ledger_equal": True,
        }, out
        assert out["overlap"] == {
            "theta_bitwise": True, "traj_bitwise": True,
            "ledger_equal": True, "resume_bitwise": True,
        }, out


class TestMultiPodEquivalence:
    """multipod (hierarchical + per-hop pricing) ≡ mesh (flat) on the SAME
    mesh: both executors derive the same staged reduction topology from
    the mesh, so theta/trajectory are BIT-EXACT; only the ledger
    attribution differs.  Runs on however many devices the process has
    (the multipod mesh degrades to (1, 1) on one device — the hop split
    stays nonzero because the server tier always exists)."""

    @pytest.mark.parametrize(
        "transport,kw,wire",
        [
            ("allreduce", {}, "dense"),
            ("allreduce", {}, "topk:0.5+ef"),
            ("delay_line", {"staleness": 2}, "dense"),
            ("delay_line", {"staleness": 2}, "topk:0.5+ef"),
        ],
    )
    def test_bit_exact_with_flat_mesh(self, transport, kw, wire):
        from repro.launch.mesh import make_multipod_mesh

        X, y, w, n = _make_problem()
        mesh = make_multipod_mesh()
        strat = lambda: api.GradientDescent(lsq_loss, lr=0.1)  # noqa: E731
        flat = api.fit(strat(), (X, y), transport=transport, wire=wire,
                       steps=30, executor=api.MeshExecutor(mesh), **kw)
        hier = api.fit(strat(), (X, y), transport=transport, wire=wire,
                       steps=30, executor=api.MultiPodExecutor(mesh), **kw)
        np.testing.assert_array_equal(np.asarray(flat.theta),
                                      np.asarray(hier.theta))
        np.testing.assert_array_equal(np.asarray(flat.trajectory),
                                      np.asarray(hier.trajectory))
        # same flat totals; the hierarchical run decomposes them by tier
        assert hier.ledger.total_bytes == flat.ledger.total_bytes
        assert hier.ledger.uplink_bytes == flat.ledger.uplink_bytes
        by_hop = hier.ledger.summary()["by_hop"]
        assert set(by_hop) == {"intra_pod", "inter_pod"}
        assert all(v["total_bytes"] > 0 for v in by_hop.values())
        assert sum(v["total_bytes"] for v in by_hop.values()) \
            == flat.ledger.total_bytes
        assert flat.ledger.summary()["by_hop"] == {}
        assert hier.metrics["executor"] == "multipod"

    def test_matches_local_and_ledger_totals(self):
        X, y, w, n = _make_problem()
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=40)
        mp = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                     transport="allreduce", steps=40, executor="multipod")
        np.testing.assert_allclose(np.asarray(mp.theta), np.asarray(loc.theta),
                                   rtol=1e-5, atol=1e-6)
        assert mp.ledger.total_bytes == loc.ledger.total_bytes

    def test_priced_cost_weights_inter_pod(self):
        """The expensive tier is priced above the cheap one, so the priced
        cost exceeds the flat byte count whenever inter-pod traffic
        exists (and custom prices flow through)."""
        X, y, w, n = _make_problem()
        mp = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                     transport="allreduce", steps=10,
                     executor=api.MultiPodExecutor(
                         intra_price=1.0, inter_price=5.0))
        s = mp.ledger.summary()
        inter = s["by_hop"]["inter_pod"]
        assert inter["price_per_byte"] == 5.0
        assert s["priced_cost"] == pytest.approx(
            s["total_bytes"] + 4.0 * inter["total_bytes"]
        )

    def test_pod_axis_required(self):
        from repro.launch.mesh import make_node_mesh

        X, y, w, n = _make_problem()
        with pytest.raises(ValueError, match="pod"):
            api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=2,
                    executor=api.MultiPodExecutor(make_node_mesh()))

    def test_resume_carry_crosses_to_local(self):
        X, y, w, n = _make_problem()
        full = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="allreduce", steps=30)
        first = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                        transport="allreduce", steps=15, executor="multipod")
        second = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                         transport="allreduce", steps=15,
                         carry=first.metrics["carry"])
        np.testing.assert_allclose(np.asarray(second.theta),
                                   np.asarray(full.theta),
                                   rtol=1e-5, atol=1e-6)


class TestMultiPodEightDevices:
    """The hierarchical≡flat acceptance suite on a REAL multi-shard
    placement: 8 fake CPU devices in a subprocess, a 2×4 ``("pod",
    "data")`` mesh for the transport×wire equivalence matrix and the
    2×2×2 ``("pod", "data", "model")`` production shape for the
    acceptance check proper (bit-exact theta, nonzero per-hop split
    summing to the flat total).  The CI ``multipod-2x4`` job runs this
    file under the same XLA_FLAGS."""

    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro import api
from repro.ml.linear import lsq_loss
from repro.ml.svm import CascadeStrategy

def bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return bool(a.shape == b.shape and
                (a.view(np.uint32) == b.view(np.uint32)).all())

rng = np.random.default_rng(0)
X = jnp.asarray(rng.normal(size=(8, 10, 5)))
w = jnp.asarray(rng.normal(size=(5,)))
y = jnp.einsum("kni,i->kn", X, w)
out = {"num_devices": jax.device_count()}

mesh24 = jax.make_mesh((2, 4), ("pod", "data"))
for transport, kw in [("allreduce", {}), ("delay_line", {"staleness": 2})]:
    for wire in ("dense", "topk:0.5+ef"):
        flat = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport=transport, wire=wire, steps=40,
                       executor=api.MeshExecutor(mesh24), **kw)
        hier = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport=transport, wire=wire, steps=40,
                       executor=api.MultiPodExecutor(mesh24), **kw)
        by_hop = hier.ledger.summary()["by_hop"]
        out[f"{transport}/{wire}"] = {
            "theta_bitwise": bitwise(flat.theta, hier.theta),
            "traj_bitwise": bitwise(flat.trajectory, hier.trajectory),
            "totals_equal": flat.ledger.total_bytes == hier.ledger.total_bytes,
            "split_nonzero": all(v["total_bytes"] > 0 for v in by_hop.values())
                             and set(by_hop) == {"intra_pod", "inter_pod"},
            "split_sums_to_flat": sum(v["total_bytes"] for v in by_hop.values())
                                  == flat.ledger.total_bytes,
        }

# acceptance: the (2, 2, 2) ("pod", "data", "model") production shape
mesh222 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
flat = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
               transport="allreduce", steps=40,
               executor=api.MeshExecutor(mesh222))
hier = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
               transport="allreduce", steps=40,
               executor=api.MultiPodExecutor(mesh222))
by_hop = hier.ledger.summary()["by_hop"]
out["mesh_2x2x2"] = {
    "theta_bitwise": bitwise(flat.theta, hier.theta),
    "traj_bitwise": bitwise(flat.trajectory, hier.trajectory),
    "split_nonzero": all(v["total_bytes"] > 0 for v in by_hop.values())
                     and len(by_hop) == 2,
    "split_sums_to_flat": sum(v["total_bytes"] for v in by_hop.values())
                          == flat.ledger.total_bytes,
}

# cascade SVM: the "any" union on a real multi-shard mesh (replicated data)
rng = np.random.default_rng(3)
Xs = jnp.asarray(rng.normal(size=(8, 6, 2)))
ys = jnp.asarray(np.sign(rng.normal(size=(8, 6))))
cl = api.fit(CascadeStrategy(C=1.0, iters=60), (Xs, ys),
             transport="allreduce", steps=3)
cm = api.fit(CascadeStrategy(C=1.0, iters=60), (Xs, ys),
             transport="allreduce", steps=3,
             executor=api.MeshExecutor(mesh24))
out["cascade"] = {
    "mask_equal": bool((np.asarray(cl.theta.sv_mask)
                        == np.asarray(cm.theta.sv_mask)).all()),
    "ledger_equal": cl.ledger.summary() == cm.ledger.summary(),
}
print(json.dumps(out))
"""

    def test_hierarchical_matches_flat_on_8_devices(self, fake_devices):
        out = fake_devices(self.SCRIPT)
        assert out["num_devices"] == 8
        for transport in ("allreduce", "delay_line"):
            for wire in ("dense", "topk:0.5+ef"):
                assert out[f"{transport}/{wire}"] == {
                    "theta_bitwise": True, "traj_bitwise": True,
                    "totals_equal": True, "split_nonzero": True,
                    "split_sums_to_flat": True,
                }, out
        assert out["mesh_2x2x2"] == {
            "theta_bitwise": True, "traj_bitwise": True,
            "split_nonzero": True, "split_sums_to_flat": True,
        }, out
        assert out["cascade"] == {"mask_equal": True, "ledger_equal": True}, out


class TestCascadeAnyReduction:
    """The cascade SVM's SV-mask union is an ``any``-reduction
    (psum-of-bools) — it now places on the mesh executors (with
    replicated data) instead of rejecting them."""

    def _problem(self, K=4):
        rng = np.random.default_rng(3)
        Xs = jnp.asarray(rng.normal(size=(K, 6, 2)))
        ys = jnp.asarray(np.sign(rng.normal(size=(K, 6))))
        return Xs, ys

    def test_local_mesh_equivalence(self):
        from repro.ml.svm import CascadeStrategy

        K = 4 if jax.device_count() == 1 else jax.device_count()
        Xs, ys = self._problem(K)
        loc = api.fit(CascadeStrategy(C=1.0, iters=60), (Xs, ys),
                      transport="allreduce", steps=3)
        mesh = api.fit(CascadeStrategy(C=1.0, iters=60), (Xs, ys),
                       transport="allreduce", steps=3, executor="mesh")
        np.testing.assert_array_equal(np.asarray(loc.theta.sv_mask),
                                      np.asarray(mesh.theta.sv_mask))
        np.testing.assert_allclose(np.asarray(loc.theta.alpha),
                                   np.asarray(mesh.theta.alpha),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(loc.trajectory),
                                      np.asarray(mesh.trajectory))
        # semantic (SVs-only) byte accounting completes across shards
        assert mesh.ledger.summary() == loc.ledger.summary()

    def test_multipod_decomposes_semantic_bytes(self):
        from repro.ml.svm import CascadeStrategy

        Xs, ys = self._problem(K=4 if jax.device_count() == 1 else
                               jax.device_count())
        loc = api.fit(CascadeStrategy(C=1.0, iters=60), (Xs, ys),
                      transport="allreduce", steps=3)
        mp = api.fit(CascadeStrategy(C=1.0, iters=60), (Xs, ys),
                     transport="allreduce", steps=3, executor="multipod")
        np.testing.assert_array_equal(np.asarray(loc.theta.sv_mask),
                                      np.asarray(mp.theta.sv_mask))
        s = mp.ledger.summary()
        assert sum(v["total_bytes"] for v in s["by_hop"].values()) \
            == loc.ledger.total_bytes

    def test_any_op_primitives(self):
        from repro.core.allreduce import server_allreduce

        m = jnp.asarray([[True, False, False], [False, False, True]])
        np.testing.assert_array_equal(
            np.asarray(server_allreduce(m, op="any")),
            np.array([True, False, True]),
        )


class TestThresholdWire:
    """The threshold sparsifier: value-dependent ratio, shape-static
    program — the knob that makes compression ratio sweepable."""

    def test_spec_parsing(self):
        w = api.make_wire("thresh:0.25")
        assert isinstance(w, api.ThresholdWire)
        assert w.tau == 0.25 and not w.error_feedback and not w.lossless
        wef = api.make_wire("thresh:0.25+ef")
        assert wef.error_feedback

    def test_push_cost_is_dynamic(self):
        w = api.make_wire("thresh:0.1")
        assert w.push_bytes(jnp.zeros(8)) is None

    def test_threshold_zero_meters_dense_count(self):
        X, y, w, n = _make_problem()
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", wire="thresh:0.0", steps=10)
        dense_up = 10 * X.shape[0] * n * (4 + 4)  # index + f32 per entry
        assert res.ledger.uplink_bytes == dense_up

    def test_higher_tau_fewer_bytes(self):
        X, y, w, n = _make_problem()
        lo = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                     transport="allreduce", wire="thresh:0.01", steps=20)
        hi = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                     transport="allreduce", wire="thresh:0.3", steps=20)
        assert hi.ledger.uplink_bytes < lo.ledger.uplink_bytes
        assert float(hi.trajectory[-1]) < float(hi.trajectory[0])

    def test_tau_sweeps_compression_ratio(self):
        """One executable, S thresholds: per-scenario results and byte
        totals match S independent fits — the ratio is now a swept axis
        (per-scenario top-k fractions would each need a static k)."""
        X, y, w, n = _make_problem()
        taus = (0.0, 0.05, 0.2)
        sw = api.SweepExecutor({"tau": jnp.asarray(taus)})
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", wire="thresh:0.1", steps=25,
                      executor=sw)
        totals = []
        for i, tau in enumerate(taus):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                           transport="allreduce",
                           wire=api.ThresholdWire(tau), steps=25)
            np.testing.assert_allclose(np.asarray(res.theta[i]),
                                       np.asarray(solo.theta),
                                       rtol=1e-6, atol=1e-7)
            assert res.ledger[i].total_bytes == solo.ledger.total_bytes
            totals.append(res.ledger[i].total_bytes)
        assert totals[0] > totals[1] > totals[2]  # ratio actually swept

    def test_tau_sweep_with_error_feedback(self):
        X, y, w, n = _make_problem()
        sw = api.SweepExecutor({"tau": jnp.asarray([0.02, 0.2])})
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", wire="thresh:0.1+ef", steps=20,
                      executor=sw)
        solo = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="allreduce",
                       wire=api.ThresholdWire(0.2, error_feedback=True),
                       steps=20)
        np.testing.assert_allclose(np.asarray(res.theta[1]),
                                   np.asarray(solo.theta),
                                   rtol=1e-6, atol=1e-7)
        assert res.ledger[1].total_bytes == solo.ledger.total_bytes

    def test_mesh_placement_matches_local(self):
        X, y, w, n = _make_problem()
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", wire="thresh:0.05+ef", steps=20)
        mesh = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="allreduce", wire="thresh:0.05+ef", steps=20,
                       executor="mesh")
        np.testing.assert_allclose(np.asarray(mesh.theta), np.asarray(loc.theta),
                                   rtol=1e-5, atol=1e-6)
        assert mesh.ledger.summary() == loc.ledger.summary()

    def test_admm_rejects_lossy_threshold(self):
        from repro.ml.linear import lasso_prox_builder

        X, y, w, n = _make_problem(K=4)
        with pytest.raises(ValueError, match="lossless"):
            api.fit(api.ProxStrategy(lasso_prox_builder), (X, y),
                    transport="admm_consensus", steps=5, g="l1", g_lam=0.1,
                    wire="thresh:0.1")


class TestTopologyLedger:
    """core.topology decomposition + CommLedger per-hop accounting."""

    def test_hop_messages_telescope(self):
        from repro.core.topology import Topology

        topo = Topology.from_mesh(("pod", "data"))
        msgs = topo.hop_messages(8, {"pod": 2, "data": 4})
        assert [(n, m) for n, m, _ in msgs] == [
            ("intra_pod", 6), ("inter_pod", 2)
        ]
        assert sum(m for _, m, _ in msgs) == 8

    def test_flat_topology_single_tier(self):
        from repro.core.topology import Topology

        topo = Topology.from_mesh(("data",))
        assert topo.tiers == ("flat",)
        assert topo.hop_messages(8, {"data": 4}) == [("flat", 8, 1.0)]

    def test_duplicate_axis_rejected(self):
        from repro.core.topology import Hop, Topology

        with pytest.raises(ValueError, match="more than one hop"):
            Topology((Hop(("data",), "a"), Hop(("data",), "b")))

    def test_record_hop(self):
        from repro.core.allreduce import CommLedger

        led = CommLedger()
        led.record_hop(jnp.zeros(4), "intra_pod", fanin=6)
        led.record_hop(jnp.zeros(4), "inter_pod", fanin=2,
                       price_per_byte=10.0)
        s = led.summary()
        assert led.total_bytes == (6 + 2) * 16 * 2
        assert s["by_hop"]["intra_pod"]["uplink_bytes"] == 96
        assert s["by_hop"]["inter_pod"]["uplink_bytes"] == 32
        assert s["priced_cost"] == 96 * 2 + 32 * 2 * 10.0

    def test_attribute_hops_preserves_totals(self):
        from repro.core.allreduce import CommLedger

        led = CommLedger(uplink_bytes=1001, downlink_bytes=777)
        led.attribute_hops([("intra_pod", 6, 1.0), ("inter_pod", 2, 10.0)])
        s = led.summary()
        assert sum(v["uplink_bytes"] for v in s["by_hop"].values()) == 1001
        assert sum(v["downlink_bytes"] for v in s["by_hop"].values()) == 777

    def test_merge_folds_hops(self):
        from repro.core.allreduce import CommLedger

        a, b = CommLedger(), CommLedger()
        a.record_hop(jnp.zeros(2), "inter_pod", fanin=1)
        b.record_hop(jnp.zeros(2), "inter_pod", fanin=3)
        a.merge(b)
        assert a.hops["inter_pod"]["uplink_bytes"] == 8 + 24

    def test_merge_mixed_prices_stays_exact(self):
        """Merging ledgers priced under different link prices keeps the
        exact cost (per-contribution accumulation, not first-price-wins)."""
        from repro.core.allreduce import CommLedger

        a, b = CommLedger(), CommLedger()
        a.record_hop(jnp.zeros(25), "inter_pod", fanin=1, price_per_byte=10.0)
        b.record_hop(jnp.zeros(25), "inter_pod", fanin=1, price_per_byte=100.0)
        a.merge(b)
        # 200 bytes @ x10 + 200 bytes @ x100
        assert a.priced_cost() == 200 * 10.0 + 200 * 100.0
        # summary reports the byte-weighted effective price
        assert a.summary()["by_hop"]["inter_pod"]["price_per_byte"] == 55.0

    def test_merge_empty_ledger_is_identity(self):
        """Folding a fresh ledger in (either direction) changes nothing —
        the executor merge path hits this every time a shard was idle."""
        from repro.core.allreduce import CommLedger

        a = CommLedger()
        a.record_hop(jnp.zeros(4), "inter_pod", fanin=2, price_per_byte=3.0)
        before = a.summary()
        a.merge(CommLedger())
        assert a.summary() == before

        empty = CommLedger()
        empty.merge(a)
        assert empty.summary() == before

    def test_zero_byte_hop_keeps_decomposition_consistent(self):
        """A hop that moved nothing (empty tree / fanin 0) must neither
        poison priced_cost nor divide-by-zero in the summary."""
        from repro.core.allreduce import CommLedger

        led = CommLedger()
        led.record_hop(jnp.zeros(4), "intra_pod", fanin=0,
                       price_per_byte=10.0)
        assert led.total_bytes == 0
        assert led.priced_cost() == 0.0
        s = led.summary()
        assert s["by_hop"]["intra_pod"]["total_bytes"] == 0
        # effective price of zero bytes reports the flat default, not NaN
        assert s["by_hop"]["intra_pod"]["price_per_byte"] == 1.0

    def test_merge_disjoint_hop_sets_unions(self):
        """Ledgers recorded on different tiers (e.g. one pod's intra-pod
        stage, another's inter-pod stage) merge to the union with each
        bucket intact."""
        from repro.core.allreduce import CommLedger

        a, b = CommLedger(), CommLedger()
        a.record_hop(jnp.zeros(4), "intra_pod", fanin=6)
        b.record_hop(jnp.zeros(4), "inter_pod", fanin=2,
                     price_per_byte=10.0)
        a.merge(b)
        assert set(a.hops) == {"intra_pod", "inter_pod"}
        assert a.hops["intra_pod"]["uplink_bytes"] == 96
        assert a.hops["inter_pod"]["uplink_bytes"] == 32
        assert a.priced_cost() == 96 * 2 + 32 * 2 * 10.0
        # and the flat totals still cover exactly the attributed bytes
        assert a.total_bytes == sum(
            h["uplink_bytes"] + h["downlink_bytes"] for h in a.hops.values()
        )

    def test_attribute_hops_on_empty_ledger(self):
        """Attributing zero recorded bytes is legal (tiers all get 0);
        a non-positive message count is the caller bug that raises."""
        from repro.core.allreduce import CommLedger

        led = CommLedger()
        led.attribute_hops([("intra_pod", 6, 1.0), ("inter_pod", 2, 10.0)])
        assert led.total_bytes == 0
        assert all(
            h["uplink_bytes"] == h["downlink_bytes"] == 0
            for h in led.hops.values()
        )
        with pytest.raises(ValueError, match="positive message count"):
            CommLedger(uplink_bytes=8).attribute_hops([("flat", 0, 1.0)])

    def test_hierarchical_allreduce_flat_hop_is_mesh_allreduce(self):
        """A single flat hop over all node axes is exactly the joint
        collective (the bit-exact degradation the refactor promises)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from repro.core.allreduce import hierarchical_allreduce, mesh_allreduce
        from repro.core.topology import Topology
        from repro.launch.mesh import make_node_mesh

        mesh = make_node_mesh()
        topo = Topology.flat(("data",))
        x = jnp.arange(jax.device_count() * 3, dtype=jnp.float32)

        def staged(v):
            return hierarchical_allreduce(v, topo.hops)

        def joint(v):
            return mesh_allreduce(v, "data")

        fa = shard_map(staged, mesh=mesh, in_specs=P("data"), out_specs=P())
        fb = shard_map(joint, mesh=mesh, in_specs=P("data"), out_specs=P())
        np.testing.assert_array_equal(np.asarray(fa(x)), np.asarray(fb(x)))


class TestSweepEquivalence:
    """sweep over S scenarios ≡ S independent fits; ledgers bit-for-bit."""

    LRS = (0.02, 0.05, 0.1, 0.2)

    def test_lr_sweep_matches_independent_fits(self):
        X, y, w, n = _make_problem()
        sw = api.SweepExecutor({"lr": jnp.asarray(self.LRS)})
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=30, executor=sw)
        assert np.asarray(res.theta).shape == (4, n)
        assert np.asarray(res.trajectory).shape == (4, 30)
        assert isinstance(res.ledger, list) and len(res.ledger) == 4
        for i, lr in enumerate(self.LRS):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=lr), (X, y),
                           transport="allreduce", steps=30)
            np.testing.assert_allclose(np.asarray(res.theta[i]),
                                       np.asarray(solo.theta),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(np.asarray(res.trajectory[i]),
                                       np.asarray(solo.trajectory),
                                       rtol=1e-6, atol=1e-7)
            # acceptance: byte totals bit-for-bit
            assert res.ledger[i].uplink_bytes == solo.ledger.uplink_bytes
            assert res.ledger[i].downlink_bytes == solo.ledger.downlink_bytes
            assert res.ledger[i].rounds == solo.ledger.rounds

    def test_staleness_sweep_matches_independent_fits(self):
        """S staleness levels share one depth-max(D) delay line read at a
        batched index — one compiled executable."""
        X, y, w, n = _make_problem()
        Ds = (0, 1, 2, 3)
        sw = api.SweepExecutor({"staleness": jnp.asarray(Ds)})
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                      transport="delay_line", steps=40, executor=sw)
        for i, D in enumerate(Ds):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                           transport="delay_line", staleness=D, steps=40)
            np.testing.assert_allclose(np.asarray(res.theta[i]),
                                       np.asarray(solo.theta),
                                       rtol=1e-6, atol=1e-7)
            assert res.ledger[i].total_bytes == solo.ledger.total_bytes

    def test_theta0_sweep(self):
        X, y, w, n = _make_problem()
        theta0s = jnp.asarray(np.random.default_rng(1).normal(size=(3, n)))
        sw = api.SweepExecutor({"theta0": theta0s})
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=20, executor=sw)
        for i in range(3):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                           transport="allreduce", steps=20,
                           theta0=theta0s[i])
            np.testing.assert_allclose(np.asarray(res.theta[i]),
                                       np.asarray(solo.theta),
                                       rtol=1e-6, atol=1e-7)

    def test_pytree_theta0_sweep(self):
        """theta0 may be a model PYTREE with batched leaves (the
        launch/train.py param dicts), not just a flat vector."""
        from repro.api.strategy import OptimizerStrategy
        from repro.optim import adam

        rng = np.random.default_rng(2)
        Xb = jnp.asarray(rng.normal(size=(6, 4, 3)))
        yb = jnp.asarray(rng.normal(size=(6, 4)))

        def loss_fn(theta, batch):
            Xt, yt = batch
            return 0.5 * jnp.mean(((Xt @ theta["w"]) + theta["b"] - yt) ** 2)

        theta0s = {
            "w": jnp.asarray(rng.normal(size=(2, 3))),
            "b": jnp.asarray(rng.normal(size=(2,))),
        }
        sw = api.SweepExecutor({"theta0": theta0s})
        assert sw.num_scenarios == 2
        res = api.fit(OptimizerStrategy(loss_fn, adam(0.1)), None,
                      transport="delay_line", staleness=0,
                      stream=(Xb, yb), executor=sw)
        for i in range(2):
            solo = api.fit(OptimizerStrategy(loss_fn, adam(0.1)), None,
                           transport="delay_line", staleness=0,
                           stream=(Xb, yb),
                           theta0=jax.tree.map(lambda x: x[i], theta0s))
            np.testing.assert_allclose(np.asarray(res.theta["w"][i]),
                                       np.asarray(solo.theta["w"]),
                                       rtol=1e-6, atol=1e-7)

    def test_sweep_carry_resume(self):
        """A swept run resumes from its batched carry."""
        X, y, w, n = _make_problem()
        sw = api.SweepExecutor({"lr": jnp.asarray(self.LRS)})
        full = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                       transport="allreduce", steps=30, executor=sw)
        a = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=15, executor=sw)
        b = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=15, executor=sw,
                    carry=a.metrics["carry"])
        np.testing.assert_allclose(np.asarray(b.theta), np.asarray(full.theta),
                                   rtol=1e-6, atol=1e-7)

    def test_compressed_wire_sweeps(self):
        """EF residual state batches per scenario alongside θ."""
        X, y, w, n = _make_problem()
        sw = api.SweepExecutor({"lr": jnp.asarray([0.05, 0.1])})
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", wire="topk:0.5+ef", steps=20,
                      executor=sw)
        solo = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                       transport="allreduce", wire="topk:0.5+ef", steps=20)
        np.testing.assert_allclose(np.asarray(res.theta[0]),
                                   np.asarray(solo.theta),
                                   rtol=1e-6, atol=1e-7)
        assert res.ledger[0].total_bytes == solo.ledger.total_bytes


class TestMeshSweepComposition:
    """mesh+sweep (scenario vmap INSIDE the shard_map body) ≡ S
    independent fits on the inner mesh executor: theta and per-scenario
    ledger byte totals BIT-exact, trajectory to fp tolerance (the
    vmapped loss-metric mean reduces in a different order)."""

    LRS = (0.02, 0.05, 0.1, 0.2)

    def test_lr_sweep_matches_independent_mesh_fits(self):
        X, y, w, n = _make_problem()
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=30,
                      executor="mesh+sweep",
                      sweep={"lr": jnp.asarray(self.LRS)})
        assert res.metrics["executor"] == "mesh+sweep"
        assert np.asarray(res.theta).shape == (4, n)
        assert isinstance(res.ledger, list) and len(res.ledger) == 4
        for i, lr in enumerate(self.LRS):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=lr), (X, y),
                           transport="allreduce", steps=30, executor="mesh")
            np.testing.assert_array_equal(np.asarray(res.theta[i]),
                                          np.asarray(solo.theta))
            np.testing.assert_allclose(np.asarray(res.trajectory[i]),
                                       np.asarray(solo.trajectory),
                                       rtol=1e-5, atol=1e-7)
            assert res.ledger[i].uplink_bytes == solo.ledger.uplink_bytes
            assert res.ledger[i].downlink_bytes == solo.ledger.downlink_bytes
            assert res.ledger[i].rounds == solo.ledger.rounds

    def test_staleness_sweep_composes_with_mesh(self):
        """The shared depth-max(D) delay line reads at a per-scenario
        index inside the shard_map body — D levels × mesh placement in
        one executable."""
        X, y, w, n = _make_problem()
        Ds = (0, 1, 3)
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                      transport="delay_line", steps=25,
                      executor=api.SweepExecutor({"staleness": jnp.asarray(Ds)},
                                                 inner=api.MeshExecutor()))
        for i, D in enumerate(Ds):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                           transport="delay_line", staleness=D, steps=25,
                           executor="mesh")
            np.testing.assert_array_equal(np.asarray(res.theta[i]),
                                          np.asarray(solo.theta))
            assert res.ledger[i].total_bytes == solo.ledger.total_bytes

    def test_tau_sweep_composes_with_mesh(self):
        """Swept WIRE attributes (the threshold sparsifier's τ) ride the
        composed executable; the traced per-scenario byte counts psum
        across shards and still match independent mesh fits exactly."""
        X, y, w, n = _make_problem()
        taus = (0.0, 0.05, 0.2)
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", wire="thresh:0.1", steps=25,
                      executor="mesh+sweep", sweep={"tau": jnp.asarray(taus)})
        totals = []
        for i, tau in enumerate(taus):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                           transport="allreduce",
                           wire=api.ThresholdWire(tau), steps=25,
                           executor="mesh")
            np.testing.assert_array_equal(np.asarray(res.theta[i]),
                                          np.asarray(solo.theta))
            assert res.ledger[i].total_bytes == solo.ledger.total_bytes
            totals.append(res.ledger[i].total_bytes)
        assert totals[0] > totals[1] > totals[2]  # ratio actually swept

    def test_multipod_inner_keeps_per_hop_split(self):
        """Under a multipod inner every scenario's ledger decomposes per
        hop, each split summing exactly to that scenario's flat total."""
        X, y, w, n = _make_problem()
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=20,
                      executor="multipod+sweep",
                      sweep={"lr": jnp.asarray(self.LRS)})
        assert res.metrics["executor"] == "multipod+sweep"
        for i in range(len(self.LRS)):
            s = res.ledger[i].summary()
            assert set(s["by_hop"]) == {"intra_pod", "inter_pod"}
            assert all(v["total_bytes"] > 0 for v in s["by_hop"].values())
            assert sum(v["total_bytes"] for v in s["by_hop"].values()) \
                == res.ledger[i].total_bytes

    def test_composed_resume(self):
        """A composed run's batched carry resumes a later composed fit —
        EF wire state included — matching one uninterrupted run."""
        X, y, w, n = _make_problem()
        kw = dict(executor="mesh+sweep",
                  sweep={"staleness": jnp.asarray([0, 2])},
                  transport="delay_line", wire="topk:0.5+ef")
        full = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                       steps=30, **kw)
        a = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                    steps=15, **kw)
        b = api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                    steps=15, carry=a.metrics["carry"], **kw)
        np.testing.assert_array_equal(np.asarray(b.theta),
                                      np.asarray(full.theta))

    def test_theta0_sweep_composes(self):
        X, y, w, n = _make_problem()
        theta0s = jnp.asarray(np.random.default_rng(1).normal(size=(3, n)))
        res = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=20,
                      executor=api.SweepExecutor({"theta0": theta0s},
                                                 inner=api.MeshExecutor()))
        for i in range(3):
            solo = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                           transport="allreduce", steps=20,
                           theta0=theta0s[i], executor="mesh")
            np.testing.assert_array_equal(np.asarray(res.theta[i]),
                                          np.asarray(solo.theta))

    def test_spec_strings_and_sweep_kwarg(self):
        sw = {"lr": jnp.asarray([0.1, 0.2])}
        ex = api.make_executor("mesh+sweep", sw)
        assert isinstance(ex, api.SweepExecutor)
        assert isinstance(ex.inner, api.MeshExecutor)
        assert ex.name == "mesh+sweep"
        ex = api.make_executor("multipod+sweep", sw)
        assert isinstance(ex.inner, api.MultiPodExecutor)
        assert api.make_executor("sweep", sw).inner is None
        # local inner collapses to the plain vmapped sweep
        assert api.SweepExecutor(sw, inner="local").inner is None
        assert set(api.COMPOSED_EXECUTORS) == {"mesh+sweep", "multipod+sweep"}

    def test_composition_errors(self):
        sw = {"lr": jnp.asarray([0.1, 0.2])}
        with pytest.raises(ValueError, match="scenario parameters"):
            api.make_executor("mesh+sweep")
        with pytest.raises(ValueError, match="sweep"):
            api.make_executor("mesh", sw)  # params without a sweep spec
        with pytest.raises(ValueError, match="sweep"):
            api.make_executor(api.MeshExecutor(), sw)  # instance + sweep=
        with pytest.raises(ValueError, match="nest"):
            api.SweepExecutor(sw, inner=api.ServingExecutor())
        with pytest.raises(ValueError, match="unknown executor"):
            api.make_executor("serve+sweep", sw)


class TestExecutorErrors:
    def test_unknown_executor(self):
        with pytest.raises(ValueError, match="unknown executor"):
            api.make_executor("cluster")

    def test_bare_sweep_string_rejected(self):
        with pytest.raises(ValueError, match="SweepExecutor"):
            api.make_executor("sweep")

    def test_sweep_needs_params(self):
        with pytest.raises(ValueError, match="at least one"):
            api.SweepExecutor({})

    def test_sweep_scenario_count_mismatch(self):
        with pytest.raises(ValueError, match="disagree"):
            api.SweepExecutor({"lr": jnp.zeros(3), "l2": jnp.zeros(4)})

    def test_sweep_unknown_attribute(self):
        X, y, w, n = _make_problem(K=4)
        sw = api.SweepExecutor({"momentum": jnp.asarray([0.1, 0.2])})
        with pytest.raises(ValueError, match="momentum"):
            api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=3, executor=sw)

    def test_server_transport_rejects_sweep(self):
        X, y, w, n = _make_problem(K=4)
        sw = api.SweepExecutor({"lr": jnp.asarray([0.1, 0.2])})
        with pytest.raises(ValueError, match="local"):
            api.fit(api.FunctionStrategy(lambda k, t: t, num_nodes=4),
                    transport="sequential_server",
                    schedule=schedules.round_robin(4, 2),
                    theta0=jnp.zeros(n), executor=sw)

    def test_all_executors_listed(self):
        assert set(api.EXECUTORS) == {
            "local", "mesh", "multipod", "sweep", "serve"
        }

    def test_explicit_local_is_default(self):
        X, y, w, n = _make_problem(K=4)
        a = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=10)
        b = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                    transport="allreduce", steps=10, executor="local")
        np.testing.assert_array_equal(np.asarray(a.theta), np.asarray(b.theta))
        assert a.ledger.summary() == b.ledger.summary()


class TestDynamicDelayRead:
    """core.staleness.delay_push_read ≡ delay_push_pop at delay == depth."""

    def test_matches_push_pop_at_full_depth(self):
        from repro.core.staleness import delay_init, delay_push_pop, delay_push_read

        rng = np.random.default_rng(0)
        D = 3
        a = delay_init(jnp.zeros(4), D)
        b = delay_init(jnp.zeros(4), D)
        for t in range(8):
            g = jnp.asarray(rng.normal(size=4))
            a, pa = delay_push_pop(a, g)
            b, pb = delay_push_read(b, g, jnp.asarray(D))
            np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
            np.testing.assert_array_equal(np.asarray(a.buffer), np.asarray(b.buffer))

    def test_zero_delay_reads_fresh(self):
        from repro.core.staleness import delay_init, delay_push_read

        s = delay_init(jnp.zeros(3), 2)
        g = jnp.asarray([1.0, 2.0, 3.0])
        _, read = delay_push_read(s, g, jnp.asarray(0))
        np.testing.assert_array_equal(np.asarray(read), np.asarray(g))


class TestReduceScatterStaging:
    """MeshExecutor(reduce_scatter=True) restages the innermost hop as
    psum_scatter → all_gather — BIT-exact with the flat staged psum
    (same additions, same order, different wire schedule)."""

    @pytest.mark.parametrize(
        "transport,kw", [("allreduce", {}), ("delay_line", {"staleness": 2})]
    )
    def test_rs_on_off_bitwise(self, transport, kw):
        X, y, w, n = _make_problem()
        on = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                     transport=transport, steps=30,
                     executor=api.MeshExecutor(reduce_scatter=True), **kw)
        off = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport=transport, steps=30,
                      executor=api.MeshExecutor(reduce_scatter=False), **kw)
        np.testing.assert_array_equal(np.asarray(on.theta),
                                      np.asarray(off.theta))
        np.testing.assert_array_equal(np.asarray(on.trajectory),
                                      np.asarray(off.trajectory))
        assert on.ledger.summary() == off.ledger.summary()

    def test_auto_resolution(self):
        assert api.MeshExecutor(reduce_scatter=True)._rs_active() is True
        assert api.MeshExecutor(reduce_scatter=False)._rs_active() is False
        auto = api.MeshExecutor()._rs_active()
        assert auto is (jax.default_backend() == "tpu")


class TestCommComputeOverlap:
    """MeshExecutor(overlap=True) dispatches the outermost hop against
    the NEXT round's local compute on delay-tolerant transports.  The
    schedule change re-slots which delay-buffer entry completes when —
    but the values entering each round are identical, so theta,
    trajectory, ledger AND the resume carry are bit-exact with
    overlap=False."""

    @pytest.mark.parametrize("staleness", [1, 2])
    def test_overlap_on_off_bitwise(self, staleness):
        X, y, w, n = _make_problem()
        on = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                     transport="delay_line", staleness=staleness, steps=30,
                     executor=api.MeshExecutor(overlap=True))
        off = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="delay_line", staleness=staleness, steps=30,
                      executor=api.MeshExecutor(overlap=False))
        loc = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="delay_line", staleness=staleness, steps=30)
        for a, b in [(on, off), (on, loc)]:
            np.testing.assert_array_equal(np.asarray(a.theta),
                                          np.asarray(b.theta))
            np.testing.assert_array_equal(np.asarray(a.trajectory),
                                          np.asarray(b.trajectory))
            assert a.ledger.summary() == b.ledger.summary()

    @pytest.mark.parametrize("staleness", [1, 2])
    def test_resume_carry_interchangeable(self, staleness):
        """A carry saved from an overlapped run resumes bit-exactly on a
        non-overlapped executor (and vice versa): exit_loop converts the
        in-flight pending partial back to plain delay-line layout."""
        X, y, w, n = _make_problem()
        gd = lambda: api.GradientDescent(lsq_loss, lr=0.1)
        full = api.fit(gd(), (X, y), transport="delay_line",
                       staleness=staleness, steps=30)
        for ex_a, ex_b in [
            (api.MeshExecutor(overlap=True), api.MeshExecutor(overlap=False)),
            (api.MeshExecutor(overlap=False), api.MeshExecutor(overlap=True)),
            (api.MeshExecutor(overlap=True), "local"),
        ]:
            first = api.fit(gd(), (X, y), transport="delay_line",
                            staleness=staleness, steps=15, executor=ex_a)
            second = api.fit(gd(), (X, y), transport="delay_line",
                             staleness=staleness, steps=15, executor=ex_b,
                             carry=first.metrics["carry"])
            np.testing.assert_array_equal(np.asarray(second.theta),
                                          np.asarray(full.theta))

    def test_overlap_declined_for_mean_aggregate(self):
        """LBFGS aggregates with op="mean" — the overlap split's deferred
        outer hop cannot carry the final divide, so the transport declines
        overlap and runs the synchronous schedule (still correct)."""
        X, y, w, n = _make_problem()
        on = api.fit(api.LBFGS(lsq_loss), (X, y), transport="delay_line",
                     staleness=1, steps=15,
                     executor=api.MeshExecutor(overlap=True))
        loc = api.fit(api.LBFGS(lsq_loss), (X, y), transport="delay_line",
                      staleness=1, steps=15)
        np.testing.assert_allclose(np.asarray(on.theta), np.asarray(loc.theta),
                                   rtol=1e-5, atol=1e-6)


class TestCalibratedPrices:
    """MultiPodExecutor(calibrate=True) replaces the x1/x10 default hop
    prices with measured per-byte costs (core.topology.calibrate_prices):
    placement and math are untouched — only the priced ledger changes."""

    def test_calibrate_smoke(self):
        X, y, w, n = _make_problem()
        cal = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=10,
                      executor=api.MultiPodExecutor(calibrate=True))
        ref = api.fit(api.GradientDescent(lsq_loss, lr=0.1), (X, y),
                      transport="allreduce", steps=10, executor="multipod")
        np.testing.assert_array_equal(np.asarray(cal.theta),
                                      np.asarray(ref.theta))
        s_cal, s_ref = cal.ledger.summary(), ref.ledger.summary()
        assert set(s_cal["by_hop"]) == set(s_ref["by_hop"])
        for hop, v in s_cal["by_hop"].items():
            assert v["total_bytes"] == s_ref["by_hop"][hop]["total_bytes"]
            assert v["price_per_byte"] > 0.0

    def test_explicit_price_beats_calibration(self):
        ex = api.MultiPodExecutor(calibrate=True, inter_price=42.0)
        r = ex.resolve()
        inter = [h for h in r.topology.hops if h.name == "inter_pod"]
        if inter:  # single-device meshes may degrade to one tier
            assert inter[0].price_per_byte == 42.0

    def test_calibrate_prices_memoized(self):
        from repro.core.topology import calibrate_prices
        mesh = api.MeshExecutor().resolve().mesh
        a = calibrate_prices(mesh)
        b = calibrate_prices(mesh)  # second call is the memo (copied out)
        assert a == b
        assert a["calibrated"] is True
        assert a["intra_pod"] > 0.0 and a["inter_pod"] > 0.0


class TestProgramCache:
    """Executors memoize their jitted placed program by config
    fingerprint (Strategy.cache_token + wire + transport shape) so
    repeated fits skip retrace/relower — the core of the mesh speedup."""

    def setup_method(self):
        from repro.api import executor as _exec
        _exec.clear_program_cache()

    def _fit(self, **kw):
        X, y, w, n = _make_problem()
        st = kw.pop("strategy", None) or api.GradientDescent(lsq_loss, lr=0.1)
        return st, api.fit(st, (X, y), transport="allreduce", steps=10, **kw)

    def test_repeat_fit_hits(self):
        from repro.api import executor as _exec
        st = api.GradientDescent(lsq_loss, lr=0.1)
        X, y, w, n = _make_problem()
        api.fit(st, (X, y), transport="allreduce", steps=10, executor="mesh")
        miss0 = _exec.program_cache_stats()["misses"]
        res = api.fit(st, (X, y), transport="allreduce", steps=10,
                      executor="mesh")
        stats = _exec.program_cache_stats()
        assert stats["hits"] >= 1
        assert stats["misses"] == miss0  # no new program built
        loc = api.fit(st, (X, y), transport="allreduce", steps=10)
        np.testing.assert_array_equal(np.asarray(res.theta),
                                      np.asarray(loc.theta))

    def test_different_config_misses(self):
        from repro.api import executor as _exec
        st = api.GradientDescent(lsq_loss, lr=0.1)
        X, y, w, n = _make_problem()
        api.fit(st, (X, y), transport="allreduce", steps=10, executor="mesh")
        m0 = _exec.program_cache_stats()["misses"]
        # different wire → different program
        api.fit(st, (X, y), transport="allreduce", steps=10, executor="mesh",
                wire="topk:0.5+ef")
        # different lr → different cache_token
        api.fit(api.GradientDescent(lsq_loss, lr=0.2), (X, y),
                transport="allreduce", steps=10, executor="mesh")
        assert _exec.program_cache_stats()["misses"] > m0

    def test_data_is_an_argument_not_baked(self):
        """Same config + different data must REUSE the program and
        produce the new data's answer (data is a jit argument)."""
        from repro.api import executor as _exec
        st = api.GradientDescent(lsq_loss, lr=0.1)
        X, y, w, n = _make_problem(seed=0)
        X2, y2, w2, _ = _make_problem(seed=1)
        api.fit(st, (X, y), transport="allreduce", steps=10, executor="mesh")
        m0 = _exec.program_cache_stats()["misses"]
        res = api.fit(st, (X2, y2), transport="allreduce", steps=10,
                      executor="mesh")
        assert _exec.program_cache_stats()["misses"] == m0
        loc = api.fit(st, (X2, y2), transport="allreduce", steps=10)
        np.testing.assert_array_equal(np.asarray(res.theta),
                                      np.asarray(loc.theta))

    def test_env_optout_bypasses(self, monkeypatch):
        from repro.api import executor as _exec
        monkeypatch.setenv("REPRO_PROGRAM_CACHE", "0")
        st = api.GradientDescent(lsq_loss, lr=0.1)
        X, y, w, n = _make_problem()
        api.fit(st, (X, y), transport="allreduce", steps=10, executor="mesh")
        api.fit(st, (X, y), transport="allreduce", steps=10, executor="mesh")
        assert _exec.program_cache_stats() == {
            "size": 0, "hits": 0, "misses": 0
        }

    def test_clear_resets(self):
        from repro.api import executor as _exec
        st = api.GradientDescent(lsq_loss, lr=0.1)
        X, y, w, n = _make_problem()
        api.fit(st, (X, y), transport="allreduce", steps=10, executor="mesh")
        assert _exec.program_cache_stats()["size"] >= 1
        _exec.clear_program_cache()
        assert _exec.program_cache_stats() == {
            "size": 0, "hits": 0, "misses": 0
        }
