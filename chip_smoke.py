#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU and check what comes out.

  python chip_smoke.py             # one chip: serve, train, fit
  python chip_smoke.py --chips 4   # four chips: the fit on the mesh and
                                   # multipod executors against local

One process runs every phase; none starts a child that touches JAX, so
the chip stays with this process.  The phases, at full published width
with random weights made from ``--seed``:

1. serve — continuous LM serving of ``qwen2-1.5b`` through
   ``ContinuousLMEngine``: every request resolves on the paged Pallas
   decode kernel, in one compiled step, and the kernel agrees with its
   XLA reference at the serving shapes.
2. train — ``xlstm-125m`` through ``repro.launch.train.main`` with a
   ``topk`` wire, so the fused top-k encode kernel is on the path; the
   loss stays finite.
3. fit — the paper's fit plane through ``api.fit`` on the ``local``
   executor: the §5 ``sequential_server`` and ``allreduce`` ×
   ``topk:0.25+ef`` over K=16 nodes × 8192 rows × 1024 features; the
   loss falls.

``--chips 4`` runs only the fit on ``mesh`` (4 devices) and ``multipod``
(a 2×2 ``("pod", "data")`` mesh) and the same fit on ``local``: θ agrees
within ``MeshCase.tol`` and the ledgers count the same bytes.

Each phase prints one ``phase <name>: {json}`` line.  The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.  Without a TPU the script exits nonzero and prints
no result.  Each phase is a plain function of its case, so a CPU test
runs them on reduced cases.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import schedules  # noqa: E402
from repro.kernels.decode_attention import ops as da_ops  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.ml.linear import lsq_loss  # noqa: E402
from repro.models import cache as cache_lib  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.serve import ContinuousLMEngine  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ----------------------------------------------------------------------------
# Phase 1: continuous LM serving
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class ServeCase:
    arch: str = "qwen2-1.5b"
    reduced: bool = False
    slots: int = 8
    page_size: int = 16
    requests: int = 16
    #: prompts are drawn from (prompt_len/2, prompt_len], one prefill bucket
    prompt_len: int = 256
    gen: int = 32
    seed: int = 0
    #: bf16 outputs of O(1): a few bf16 ulps of the XLA reference
    kernel_tol: float = 3e-2


def decode_kernel_error(cfg, case: ServeCase) -> float:
    """Max-abs difference between the paged Pallas decode kernel and
    ``decode_attention_xla`` over ``paged_view`` of the same arena, on
    random data at the engine's shapes: ``slots`` block rows over
    ``max_seq`` rounded up to whole pages, shuffled across the arena, in
    the compute dtype, with ragged valid lengths."""
    pps = -(-(case.prompt_len + case.gen) // case.page_size)
    B, Hq, Hkv, D = case.slots, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n_pages = 1 + B * pps
    dt = jnp.dtype(cfg.compute_dtype)
    kq, kk, kv, kl, kp = jax.random.split(jax.random.key(case.seed + 7), 5)
    q = jax.random.normal(kq, (B, Hq, D), dt)
    arena = cache_lib.PagedKVCache(
        k=jax.random.normal(kk, (n_pages, Hkv, case.page_size, D), dt),
        v=jax.random.normal(kv, (n_pages, Hkv, case.page_size, D), dt),
    )
    block = (1 + jax.random.permutation(kp, B * pps)).reshape(B, pps)
    valid = jax.random.randint(kl, (B,), 1, pps * case.page_size + 1)
    got = da_ops.paged_decode_attention(q, arena.k, arena.v, block, valid)
    ref = da_ops.decode_attention_xla(q, *cache_lib.paged_view(arena, block), valid)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))


def phase_serve(case: ServeCase) -> dict:
    cfg = get_config(case.arch)
    if case.reduced:
        cfg = cfg.reduced()
    err = decode_kernel_error(cfg, case)
    print(f"decode kernel vs decode_attention_xla at the serving shapes: "
          f"max_abs_err={err!r} tol={case.kernel_tol!r}", flush=True)
    check(err <= case.kernel_tol, f"decode kernel error {err} > {case.kernel_tol}")

    params = tf.init_params(jax.random.key(case.seed), cfg)
    engine = ContinuousLMEngine(
        cfg, params, n_slots=case.slots, page_size=case.page_size,
        max_seq=case.prompt_len + case.gen, seed=case.seed,
    )
    rng = np.random.default_rng(case.seed + 1)
    lengths = rng.integers(case.prompt_len // 2 + 1, case.prompt_len + 1,
                           size=case.requests)
    tickets = [
        engine.submit(rng.integers(0, cfg.vocab_size, size=n), max_new=case.gen)
        for n in lengths
    ]
    t0 = time.perf_counter()
    steps = engine.run_until_idle()
    wall = time.perf_counter() - t0
    outs = [t.result() for t in tickets]

    check(all(t.done for t in tickets), "a serving request did not resolve")
    check(all(o.shape == (case.gen,) for o in outs), "wrong generated length")
    check(all(((o >= 0) & (o < cfg.vocab_size)).all() for o in outs),
          "a generated id is outside the vocabulary")
    check(engine.kernel_plan["kernel"] == "paged_decode_attention",
          f"decode path is {engine.kernel_plan}")
    check(engine.kernel_hits["xla"] == 0 and engine.kernel_hits["pallas"] > 0,
          f"decode kernel hits {engine.kernel_hits}")
    check(engine.compiled_step_cache_size == 1,
          f"{engine.compiled_step_cache_size} compiled decode steps")
    return {
        "arch": cfg.name,
        "requests": len(tickets),
        "resolved": sum(t.done for t in tickets),
        "decode_steps": steps,
        "kernel_plan": engine.kernel_plan["path"],
        "kernel_hits": dict(engine.kernel_hits),
        "compiled_step_cache_size": engine.compiled_step_cache_size,
        "decode_kernel_max_abs_err": err,
        "wall_s_compile_included": wall,
        "sample": outs[0][:8].tolist(),
    }


# ----------------------------------------------------------------------------
# Phase 2: LM training
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class TrainCase:
    arch: str = "xlstm-125m"
    reduced: bool = False
    steps: int = 4
    batch: int = 8
    seq: int = 512
    topk: float = 0.25
    seed: int = 0


def phase_train(case: TrainCase) -> dict:
    argv = [
        "--arch", case.arch, "--steps", str(case.steps),
        "--batch", str(case.batch), "--seq", str(case.seq),
        "--compress-topk", str(case.topk), "--log-every", str(case.steps),
        "--seed", str(case.seed),
    ] + (["--reduced"] if case.reduced else [])
    t0 = time.perf_counter()
    out = train.main(argv)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in out["history"]]
    hits = out["wire_kernel_hits"]
    check(all(math.isfinite(x) for x in losses), f"training loss {losses}")
    check(hits is not None and hits["active"] and hits["kernel_leaves"] > 0,
          f"top-k wire kernel not on the path: {hits}")
    return {
        "arch": case.arch,
        "steps": case.steps,
        "losses": losses,
        "uplink_bytes": out["uplink_bytes"],
        "wire_kernel_hits": hits,
        "wall_s_compile_included": wall,
    }


# ----------------------------------------------------------------------------
# Phase 3: the paper's fit plane
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class FitCase:
    nodes: int = 16
    rows: int = 8192
    features: int = 1024
    #: allreduce rounds; sequential_server walks ``passes`` round-robin passes
    rounds: int = 40
    passes: int = 4
    lr: float = 0.5
    wire: str = "topk:0.25+ef"
    seed: int = 0


def fit_data(case: FitCase):
    """Least squares over K nodes, made on the device from the seed."""
    kx, kw, kn = jax.random.split(jax.random.key(case.seed), 3)
    X = jax.random.normal(kx, (case.nodes, case.rows, case.features), jnp.float32)
    w = jax.random.normal(kw, (case.features,), jnp.float32)
    y = jnp.einsum("knf,f->kn", X, w) + 0.1 * jax.random.normal(
        kn, (case.nodes, case.rows), jnp.float32
    )
    return X, y


@jax.jit
def _global_loss(theta, data):
    X, y = data
    return jnp.mean(jax.vmap(lsq_loss, in_axes=(None, 0, 0))(theta, X, y))


def _falls(theta, data, what: str) -> list:
    """The least-squares loss over all nodes at θ0 = 0 and at ``theta``;
    it must be finite and lower at the end."""
    first = float(_global_loss(jnp.zeros_like(theta), data))
    last = float(_global_loss(theta, data))
    check(math.isfinite(last), f"{what}: non-finite loss {last}")
    check(last < first, f"{what}: loss {first} -> {last} did not fall")
    return [first, last]


def phase_fit(case: FitCase) -> dict:
    data = fit_data(case)
    strategy = api.GradientDescent(lsq_loss, lr=case.lr)
    t0 = time.perf_counter()
    seq = api.fit(strategy, data, transport="sequential_server",
                  schedule=schedules.round_robin(case.nodes, case.passes))
    ar = api.fit(strategy, data, transport="allreduce", wire=case.wire,
                 steps=case.rounds)
    jax.block_until_ready((seq.theta, ar.theta))
    wall = time.perf_counter() - t0
    hits = ar.metrics["wire_kernel_hits"]
    check(hits["active"] and hits["kernel_leaves"] > 0,
          f"top-k wire kernel not on the path: {hits}")
    return {
        "shape": [case.nodes, case.rows, case.features],
        "sequential_server_loss_first_last": _falls(seq.theta, data,
                                                     "sequential_server"),
        f"allreduce_{case.wire}_loss_first_last": _falls(ar.theta, data,
                                                        "allreduce"),
        "ledger_bytes": {"sequential_server": seq.ledger.total_bytes,
                         "allreduce": ar.ledger.total_bytes},
        "wire_kernel_hits": hits,
        "wall_s_compile_included": wall,
    }


# ----------------------------------------------------------------------------
# --chips 4: the fit on real devices
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshCase:
    fit: FitCase = FitCase()
    devices: int = 4
    #: max |θ_mesh − θ_local| over max(1, max |θ_local|): the executors
    #: reduce in another order, and top-k may break a near-tie the other way
    tol: float = 1e-3


def phase_mesh(case: MeshCase) -> dict:
    data = fit_data(case.fit)
    strategy = api.GradientDescent(lsq_loss, lr=case.fit.lr)

    def run(executor):
        return api.fit(strategy, data, transport="allreduce", wire=case.fit.wire,
                       steps=case.fit.rounds, executor=executor)

    local = run("local")
    ref = np.asarray(local.theta)
    scale = max(1.0, float(np.max(np.abs(ref))))
    out = {"local_loss_first_last": _falls(local.theta, data, "local"),
           "ledger_bytes": {"local": local.ledger.total_bytes}}
    for name, ex in (("mesh", api.MeshExecutor()),
                     ("multipod", api.MultiPodExecutor())):
        placement = ex.resolve()
        res = run(ex)
        mesh_devices = {d.id for d in placement.mesh.devices.flat}
        theta_devices = {d.id for d in res.theta.sharding.device_set}
        err = float(np.max(np.abs(np.asarray(res.theta) - ref))) / scale
        check(len(mesh_devices) == case.devices and theta_devices == mesh_devices,
              f"{name}: mesh devices {mesh_devices}, θ on {theta_devices}")
        check(ex._rs_active(), f"{name}: reduce_scatter='auto' not taken")
        check(err <= case.tol, f"{name}: θ differs from local by {err} > {case.tol}")
        check(res.ledger.total_bytes == local.ledger.total_bytes,
              f"{name}: ledger {res.ledger.total_bytes} B != local "
              f"{local.ledger.total_bytes} B")
        out[name] = {
            "mesh": dict(placement.mesh.shape),
            "devices": sorted(mesh_devices),
            "reduce_scatter": ex._rs_active(),
            "theta_rel_err_vs_local": err,
            "loss_first_last": _falls(res.theta, data, name),
        }
        out["ledger_bytes"][name] = res.ledger.total_bytes
    out["tol"] = case.tol
    return out


# ----------------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------------


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh/multipod fit against local")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(
            MeshCase(fit=FitCase(seed=args.seed), devices=len(devices))))]
    else:
        phases = [
            ("serve", lambda: phase_serve(ServeCase(seed=args.seed))),
            ("train", lambda: phase_train(TrainCase(seed=args.seed))),
            ("fit", lambda: phase_fit(FitCase(seed=args.seed))),
        ]
    failed = []
    for name, run in phases:
        try:
            result = run()
        except Exception:  # noqa: BLE001 — report it, run the other phases
            traceback.print_exc()
            print(f"phase {name}: FAILED", flush=True)
            failed.append(name)
            continue
        result["peak_bytes_in_use"] = _peak_bytes(devices[0])
        print(f"phase {name}: {json.dumps(result)}", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
