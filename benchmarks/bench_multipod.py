"""Multi-pod hierarchical execution: predicted vs measured byte split.

Runs the same GD workload under the flat mesh executor and the multipod
executor on a 2×4 ``("pod", "data")`` mesh of 8 fake CPU devices (forced
in a SUBPROCESS, since the XLA device count is fixed at jax init), then
reports three things side by side:

* the ledger's PREDICTED split — flat lump vs per-hop (intra-pod /
  inter-pod) decomposition, priced per byte;
* the MEASURED split — ``telemetry.hlo.collective_stats`` over the
  compiled hierarchical aggregate's HLO, with each collective attributed
  to a tier by its replica groups (per-device bytes);
* the equivalence check (theta bitwise flat ≡ hierarchical) and compiled
  wall-clock for both placements.

Writes ``BENCH_multipod.json`` next to the repo root; also pluggable into
``benchmarks.run`` (rows of ``name,us_per_call,derived``).

Run:
  PYTHONPATH=src python -m benchmarks.bench_multipod
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

STEPS = 200

SCRIPT = r"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import api
from repro.api import executor as X
from repro.core.allreduce import hierarchical_allreduce
from repro.core.topology import calibrate_prices
from repro.launch.mesh import make_multipod_mesh
from repro.ml.linear import lsq_loss
from repro.telemetry.hlo import collective_stats, mesh_pod_map

K, NK, N, STEPS = 8, 64, 256, %(steps)d

rng = np.random.default_rng(0)
Xs = jnp.asarray(rng.normal(size=(K, NK, N)))
w = jnp.asarray(rng.normal(size=(N,)))
y = jnp.einsum("kni,i->kn", Xs, w)
data = (Xs, y)

mesh = make_multipod_mesh(num_pods=2)


def timed(fn, repeats=3):
    out = fn()
    jax.block_until_ready(out.theta)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out.theta)
        best = min(best, time.perf_counter() - t0)
    return best, out


dt_flat, flat = timed(lambda: api.fit(
    api.GradientDescent(lsq_loss, lr=0.05), data, transport="allreduce",
    steps=STEPS, executor=api.MeshExecutor(mesh)))
dt_hier, hier = timed(lambda: api.fit(
    api.GradientDescent(lsq_loss, lr=0.05), data, transport="allreduce",
    steps=STEPS, executor=api.MultiPodExecutor(mesh)))

a, b = np.asarray(flat.theta), np.asarray(hier.theta)
bitwise = bool((a.view(np.uint32) == b.view(np.uint32)).all())

# measured: compiled HLO of the hierarchical aggregate on the real mesh
mpe = api.MultiPodExecutor(mesh)
r = mpe.resolve()
ctx = X.ExecContext(
    node_axis=r.axis, num_shards=r.num_shards, topology=r.topology,
    axis_sizes=tuple(mesh.shape[a] for a in r.axes),
)


def round_aggregate(stacked):
    with X.executing(ctx):
        return X.aggregate(stacked)


g = jax.jit(shard_map(
    round_aggregate, mesh=mesh, in_specs=P(r.axis), out_specs=P(),
    check_vma=False,
))
txt = g.lower(jnp.ones((K, N))).compile().as_text()
measured = collective_stats(txt, pod_of=mesh_pod_map(mesh))

# per-hop wall-time decomposition at the message shape: each hop's psum
# timed alone in a jitted shard_map loop — the measured cost ratio the
# calibrated prices should reflect
def hop_loop(axes):
    def body(v):
        def step(c, _):
            return c + jax.lax.psum(v[0], axes), ()
        return jax.lax.scan(
            step, jnp.zeros(v.shape[1:], v.dtype), None, length=STEPS
        )[0]
    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(r.axis), out_specs=P(),
        check_vma=False,
    ))

msg = jnp.ones((K, N))
hop_times = {}
for hop in r.topology.hops:
    prog = hop_loop(hop.axes)
    jax.block_until_ready(prog(msg))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(prog(msg))
        best = min(best, time.perf_counter() - t0)
    hop_times[hop.name] = best

# one-shot microbenchmark replacing the ×1/×10 default hop prices
prices = calibrate_prices(mesh)

# one traced hierarchical fit -> the RunReport markdown carried in the
# sidecar: per-hop bytes AND per-hop device times in one artifact
from repro.telemetry import RunReport, Tracer

tracer = Tracer()
traced = api.fit(
    api.GradientDescent(lsq_loss, lr=0.05), data, transport="allreduce",
    steps=STEPS, executor=api.MultiPodExecutor(mesh),
    wire="topk:0.1+ef", tracer=tracer, trace="phases",
)
run_report_md = RunReport.from_fit(traced, tracer=tracer).to_markdown()

out = {
    "run_report_md": run_report_md,
    "workload": {"K": K, "Nk": NK, "n": N, "steps": STEPS},
    "mesh": {"pod": 2, "data": 4},
    "env": {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "num_devices": jax.device_count(),
    },
    "equivalence": {"theta_bitwise_flat_vs_hierarchical": bitwise},
    "predicted": {
        "flat": flat.ledger.summary(),
        "hierarchical": hier.ledger.summary(),
    },
    "measured_hlo_per_device": {
        "by_tier": measured.get("by_tier", {}),
        "total_bytes": measured["total_bytes"],
        "total_count": measured["total_count"],
    },
    "timings": {
        "flat_wall_s": dt_flat,
        "hierarchical_wall_s": dt_hier,
        "per_hop_collective_s": hop_times,
    },
    "calibrated_prices": {
        k: v for k, v in prices.items() if k != "seconds"
    } | {"seconds": prices["seconds"]},
}
print(json.dumps(out))
""" % {"steps": STEPS}


def run(rows):
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    # the child runs on 8 virtual CPU devices; this process's own
    # environment is left as it is
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_multipod subprocess failed: {proc.stderr[-2000:]}"
        )
    results = json.loads(proc.stdout.strip().splitlines()[-1])

    flat = results["predicted"]["flat"]
    hier = results["predicted"]["hierarchical"]
    split = {
        name: v["total_bytes"] for name, v in hier["by_hop"].items()
    }
    rows.append((
        "multipod/flat",
        results["timings"]["flat_wall_s"] * 1e6 / STEPS,
        f"total_bytes={flat['total_bytes']}",
    ))
    rows.append((
        "multipod/hierarchical",
        results["timings"]["hierarchical_wall_s"] * 1e6 / STEPS,
        f"intra={split.get('intra_pod', 0)};inter={split.get('inter_pod', 0)}"
        f";priced={hier['priced_cost']:.0f}",
    ))

    out_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_multipod.json",
    )
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}")
    return results


if __name__ == "__main__":
    rows: list = []
    res = run(rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(",".join(str(c) for c in r))
    print(json.dumps(res["measured_hlo_per_device"], indent=2))
