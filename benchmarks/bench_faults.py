"""Client-fleet frontier: accuracy vs privacy noise vs bytes, faulted.

One ``mesh+sweep`` executable trains the dp-noise frontier under a
faulted fleet (seeded dropout + stragglers + a quorum gate): S values of
``dp_sigma`` share one compiled program, one fault-draw stream and one
8-fake-device mesh placement, yielding final loss and survivor-only
uplink bytes per scenario.  A second sweep walks ``dropout_p`` itself
(inverse-CDF coupled to the shared uniforms), and a traced faulted mesh
fit embeds its ``RunReport`` markdown in the sidecar.

Writes ``BENCH_faults.json`` next to the repo root; also pluggable into
``benchmarks.run`` (rows of ``name,us_per_call,derived``).

Run:
  PYTHONPATH=src python -m benchmarks.bench_faults
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

STEPS = 60

SCRIPT = r"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.api.executor import clear_program_cache, program_cache_stats
from repro.api.faults import FaultPlan
from repro.ml.linear import lsq_loss
from repro.telemetry import RunReport, Tracer

K, NK, N, STEPS = 8, 64, 256, %(steps)d

rng = np.random.default_rng(0)
Xs = jnp.asarray(rng.normal(size=(K, NK, N)))
w = jnp.asarray(rng.normal(size=(N,)))
y = jnp.einsum("kni,i->kn", Xs, w)
data = (Xs, y)
gd = lambda: api.GradientDescent(lsq_loss, lr=0.05)
plan = FaultPlan(seed=11, dropout_p=0.3, straggler=1, quorum=3)

def timed(fn):
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best

# the dp-sigma frontier: S noise levels, ONE faulted mesh+sweep
# executable — final loss vs survivor uplink bytes per scenario
sigmas = [0.0, 0.01, 0.05, 0.2, 1.0]
def dp_frontier():
    return api.fit(
        gd(), data, transport="allreduce", steps=STEPS,
        wire="dp:1.0,0.05", executor="mesh+sweep", faults=plan,
        sweep={"dp_sigma": jnp.asarray(sigmas)},
    )
clear_program_cache()
res = dp_frontier()
dt_frontier = timed(dp_frontier)
traj = np.asarray(res.trajectory)
ledgers = res.ledger if isinstance(res.ledger, list) else [res.ledger]
frontier = [
    {
        "dp_sigma": s,
        "final_loss": float(traj[i, -1]),
        "uplink_bytes": int(ledgers[i].uplink_bytes),
        "downlink_bytes": int(ledgers[i].downlink_bytes),
    }
    for i, s in enumerate(sigmas)
]

# dropout_p sweep against the SHARED draw stream (inverse-CDF coupling)
drops = [0.0, 0.2, 0.4, 0.6]
dres = api.fit(
    gd(), data, transport="allreduce", steps=STEPS,
    executor="mesh+sweep", faults=FaultPlan(seed=11, straggler=1),
    sweep={"dropout_p": jnp.asarray(drops)},
)
dtraj = np.asarray(dres.trajectory)
dledgers = dres.ledger if isinstance(dres.ledger, list) else [dres.ledger]
dropout_rows = [
    {
        "dropout_p": p,
        "final_loss": float(dtraj[i, -1]),
        "uplink_bytes": int(dledgers[i].uplink_bytes),
    }
    for i, p in enumerate(drops)
]

# fault overhead on the plain mesh path: faulted vs fault-free warm fit
def mesh_fit(faults=None):
    return api.fit(gd(), data, transport="allreduce", steps=STEPS,
                   executor="mesh", faults=faults)
dt_clean = timed(lambda: mesh_fit())
dt_faulted = timed(lambda: mesh_fit(plan))

# one compiled program across seeds: masks are jit arguments
clear_program_cache()
mesh_fit(FaultPlan(seed=1, dropout_p=0.3, straggler=1, quorum=3))
mesh_fit(FaultPlan(seed=2, dropout_p=0.3, straggler=1, quorum=3))
cache = program_cache_stats()

# traced faulted fit -> RunReport markdown for the sidecar
tracer = Tracer()
traced = api.fit(gd(), data, transport="allreduce", steps=STEPS,
                 executor="mesh", faults=plan, wire="dp:1.0,0.05",
                 tracer=tracer, trace="phases")
run_report_md = RunReport.from_fit(traced, tracer=tracer).to_markdown()

out = {
    "run_report_md": run_report_md,
    "workload": {"K": K, "Nk": NK, "n": N, "steps": STEPS},
    "fault_plan": plan.describe(),
    "env": {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "num_devices": jax.device_count(),
    },
    "dp_frontier": frontier,
    "dropout_sweep": dropout_rows,
    "timings": {
        "frontier_wall_s": dt_frontier,
        "mesh_clean_wall_s": dt_clean,
        "mesh_faulted_wall_s": dt_faulted,
        "faulted_over_clean": dt_faulted / dt_clean,
    },
    "program_cache_across_seeds": cache,
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
            f"bench_faults subprocess failed: {proc.stderr[-2000:]}"
        )
    results = json.loads(proc.stdout.strip().splitlines()[-1])

    for row in results["dp_frontier"]:
        rows.append((
            f"faults/dp_sigma={row['dp_sigma']}",
            results["timings"]["frontier_wall_s"] * 1e6 / STEPS,
            f"loss={row['final_loss']:.5f};up={row['uplink_bytes']}",
        ))
    for row in results["dropout_sweep"]:
        rows.append((
            f"faults/dropout_p={row['dropout_p']}",
            "-",
            f"loss={row['final_loss']:.5f};up={row['uplink_bytes']}",
        ))
    rows.append((
        "faults/mesh_overhead",
        results["timings"]["mesh_faulted_wall_s"] * 1e6 / STEPS,
        f"faulted_over_clean="
        f"{results['timings']['faulted_over_clean']:.3f}"
        f";programs={results['program_cache_across_seeds']['size']}",
    ))

    out_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_faults.json",
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
