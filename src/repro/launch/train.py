"""Training launcher — end-to-end driver usable on CPU (reduced configs)
and, unchanged, on a real mesh (full configs).

The per-step update pipeline is the unified ``repro.api`` engine:

* strategy  — ``OptimizerStrategy`` (gradient of the LM loss through a
  ``repro.optim`` optimizer);
* transport — ``delay_line`` (``--staleness D``: D=0 synchronous; D=1 the
  paper's literal one-step-stale protocol);
* wire      — ``--compress-topk f`` selects ``topk:f+ef`` (top-k
  sparsified push with error feedback), otherwise dense.

The driver calls ``api.fit`` in chunks aligned to the logging /
checkpoint cadence, resuming each chunk from the previous
``FitResult.metrics["carry"]`` so the delay line, error-feedback
residuals and optimizer state flow through unchanged.

``--sweep-staleness "0,1,2,4"`` runs all listed staleness levels as ONE
vmapped scenario batch (the sweep executor): every level shares one
compiled step and one data stream, and the driver reports the loss
trajectory per scenario — the cheapest way to pick D before a long run.

``--multipod`` installs a ``("pod", "data")`` multipod ``MeshContext``
(``launch.mesh.make_multipod_mesh``) so the model's activation-sharding
constraints place the batch over pods × intra-pod data shards — the
production placement, runnable on CPU with fake devices.  The two flags
COMPOSE: ``--sweep-staleness --multipod`` nests the activation sharding
inside the scenario vmap, so every staleness level trains mesh-placed in
the one executable (the executor-composition story of
``docs/EXECUTORS.md``, driven from the CLI).

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 50 --batch 8 --seq 128 --log-every 10
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import api
from repro.api.strategy import OptimizerStrategy
from repro.checkpoint import save
from repro.configs import get_config
from repro.data import synthetic_lm_batches
from repro.models import transformer as tf
from repro.optim import adam, clip_by_global_norm, warmup_cosine
from repro.utils.compile_cache import enable_compile_cache


def _chunk_end(done: int, steps: int, log_every: int, ckpt_every: int) -> int:
    """Next boundary where the driver needs control back."""
    targets = [steps, (done // log_every + 1) * log_every]
    if ckpt_every:
        targets.append((done // ckpt_every + 1) * ckpt_every)
    return min(t for t in targets if t > done)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", help="CPU smoke variant")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--staleness", type=int, default=0)
    ap.add_argument(
        "--sweep-staleness", default="",
        help="comma-separated staleness levels batched into one vmapped "
        "sweep (overrides --staleness; incompatible with checkpointing; "
        "composes with --multipod: the sweep then trains every level "
        "mesh-placed in one executable)",
    )
    ap.add_argument("--compress-topk", type=float, default=0.0)
    ap.add_argument(
        "--multipod", action="store_true",
        help="run under a ('pod', 'data') multipod MeshContext: activation "
        "batches shard over pods × data shards (the production placement; "
        "on CPU combine with XLA_FLAGS=--xla_force_host_platform_device_"
        "count=N for N fake devices)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--fault-seed", type=int, default=0,
        help="seed for the FaultPlan draw streams (used when any fault "
        "flag below is set; see docs/FAULTS.md)",
    )
    ap.add_argument(
        "--dropout-p", type=float, default=0.0,
        help="per-round per-node drop probability: dead nodes are masked "
        "out of the aggregate and cost zero uplink bytes",
    )
    ap.add_argument(
        "--straggler", type=int, default=0,
        help="max per-node integer lag per round; the delay line deepens "
        "by this many slots and reads at staleness + max(live lags)",
    )
    ap.add_argument(
        "--quorum", type=int, default=0,
        help="minimum surviving responders for a round to commit "
        "(0 = no quorum gate); below quorum the round rolls back",
    )
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder:
        raise SystemExit("use examples/whisper_train.py for enc-dec training")

    key = jax.random.key(args.seed)
    params = tf.init_params(key, cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    optimizer = clip_by_global_norm(
        adam(warmup_cosine(args.lr, args.steps // 10 + 1, args.steps)), 1.0
    )
    strategy = OptimizerStrategy(
        lambda p, batch: tf.loss_fn(p, cfg, batch), optimizer, has_aux=True
    )
    wire = f"topk:{args.compress_topk}+ef" if args.compress_topk > 0 else "dense"

    faults = None
    if args.dropout_p or args.straggler or args.quorum:
        from repro.api.faults import FaultPlan

        faults = FaultPlan(
            seed=args.fault_seed,
            dropout_p=args.dropout_p,
            straggler=args.straggler,
            quorum=args.quorum or None,
        )

    sweep_levels = None
    executor = "local"
    if args.sweep_staleness:
        if args.ckpt_dir:
            raise SystemExit("--sweep-staleness is incompatible with --ckpt-dir")
        sweep_levels = [int(s) for s in args.sweep_staleness.split(",")]
        executor = api.SweepExecutor({"staleness": jnp.asarray(sweep_levels)})

    mesh_note = ""
    if args.multipod:
        from repro.launch.mesh import make_multipod_mesh
        from repro.sharding.rules import MeshContext, set_mesh_context

        mesh = make_multipod_mesh()
        ndev = mesh.shape["pod"] * mesh.shape["data"]
        if args.batch % ndev:
            raise SystemExit(
                f"--batch {args.batch} must divide over the "
                f"{mesh.shape['pod']}x{mesh.shape['data']} multipod mesh"
            )
        set_mesh_context(
            MeshContext(mesh=mesh, logical={"batch": ("pod", "data")})
        )
        mesh_note = (
            f", mesh=pod:{mesh.shape['pod']}x data:{mesh.shape['data']}"
        )

    data = synthetic_lm_batches(args.seed, args.batch, args.seq, cfg.vocab_size)
    fault_note = f", faults={faults!r}" if faults is not None else ""
    print(
        f"training {cfg.name} ({n_params/1e6:.1f}M params, "
        f"staleness={sweep_levels or args.staleness}, wire={wire}"
        f"{mesh_note}{fault_note})"
    )
    t0 = time.time()
    history = []
    theta, carry, done = params, None, 0
    wire_bytes = 0
    kernel_hits = None
    while done < args.steps:
        end = _chunk_end(done, args.steps, args.log_every, args.ckpt_every)
        batches = [next(data) for _ in range(end - done)]
        stream = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
        res = api.fit(
            strategy,
            None,
            transport="delay_line",
            staleness=args.staleness,
            wire=wire,
            executor=executor,
            stream=stream,
            theta0=theta,
            carry=carry,
            faults=faults,
            tag="train",
        )
        theta, carry = res.theta, res.metrics["carry"]
        kernel_hits = res.metrics.get("wire_kernel_hits")
        if sweep_levels is None:
            wire_bytes += res.ledger.uplink_bytes
            losses = {"loss": float(res.trajectory[-1])}
            first = {"loss": float(res.trajectory[0])}
        else:
            wire_bytes += res.ledger[0].uplink_bytes  # identical across D
            traj = jnp.asarray(res.trajectory)
            losses = {f"loss_D{d}": float(traj[i, -1])
                      for i, d in enumerate(sweep_levels)}
            first = {f"loss_D{d}": float(traj[i, 0])
                     for i, d in enumerate(sweep_levels)}
        if done == 0:
            history.append({"step": 1, **first})
        done = end
        if done % args.log_every == 0 or done == args.steps:
            if history[-1]["step"] != done:
                history.append({"step": done, **losses})
            shown = "  ".join(f"{k} {v:.4f}" for k, v in losses.items())
            print(f"step {done:5d}  {shown}  ({(time.time()-t0)/done:.2f}s/step)")
        if args.ckpt_dir and args.ckpt_every and done % args.ckpt_every == 0:
            save(args.ckpt_dir, done, theta)
    final = {k: v for k, v in history[-1].items() if k != "step"}
    summary = {
        "final_loss": final["loss"] if sweep_levels is None else final,
        "uplink_bytes": wire_bytes,
        "wire_kernel_hits": kernel_hits,
        "history": history,
    }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
