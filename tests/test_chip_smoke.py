"""``chip_smoke.py`` on the CPU: its phases on reduced cases, with the
kernels forced onto the path in interpret mode, so the control flow and
the kernel-path checks the script makes on the chip are exercised here.

On the CPU, ``use_kernel="auto"`` picks the XLA reference and the wires'
jnp path; the tests steer both to the Pallas kernels, which then run in
interpret mode because the backend is not a TPU.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture
def kernels_forced(monkeypatch):
    from repro.api import executor, wire
    from repro.models import attention
    from repro.serve import continuous

    monkeypatch.setattr(
        continuous, "resolve_decode_attn",
        lambda use_kernel, **kw: attention.resolve_decode_attn(True, **kw),
    )
    monkeypatch.setattr(
        continuous, "decode_kernel_plan",
        lambda cfg, use_kernel="auto": attention.decode_kernel_plan(
            cfg, use_kernel=True
        ),
    )
    monkeypatch.setattr(wire._FusedWire, "_kernel_active", lambda self: True)
    monkeypatch.setattr(executor.MeshExecutor, "_rs_active", lambda self: True)


SERVE = chip_smoke.ServeCase(reduced=True, slots=2, requests=3,
                             prompt_len=12, gen=3, page_size=4)
FIT = chip_smoke.FitCase(nodes=4, rows=1024, features=256, rounds=10,
                         passes=2, lr=0.3)


def test_serve_phase_reduced(kernels_forced):
    out = chip_smoke.phase_serve(SERVE)
    assert out["resolved"] == out["requests"] == 3
    assert out["kernel_plan"] == "pallas"
    assert out["kernel_hits"]["xla"] == 0
    assert out["compiled_step_cache_size"] == 1
    assert out["decode_kernel_max_abs_err"] <= SERVE.kernel_tol


def test_serve_phase_refuses_the_xla_path():
    """Without the kernel on the path the phase fails, as it would on a
    chip where ``auto`` did not pick the kernel."""
    with pytest.raises(chip_smoke.SmokeFailure, match="decode path"):
        chip_smoke.phase_serve(SERVE)


def test_train_phase_reduced(kernels_forced, monkeypatch):
    # train.main turns on the persistent compile cache; a test keeps none
    monkeypatch.setattr(chip_smoke.train, "enable_compile_cache", lambda: None)
    out = chip_smoke.phase_train(
        chip_smoke.TrainCase(reduced=True, steps=2, batch=2, seq=16)
    )
    assert out["wire_kernel_hits"]["active"]
    assert out["wire_kernel_hits"]["kernel_leaves"] > 0
    assert len(out["losses"]) == 2


def test_fit_phase_reduced(kernels_forced):
    out = chip_smoke.phase_fit(FIT)
    first, last = out["sequential_server_loss_first_last"]
    assert last < first
    first, last = out["allreduce_topk:0.25+ef_loss_first_last"]
    assert last < first


def test_fit_phase_fails_when_the_loss_rises(kernels_forced):
    # more features than rows per node: the local steps diverge at this lr
    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke.phase_fit(chip_smoke.FitCase(
            nodes=4, rows=32, features=256, rounds=10, passes=2, lr=0.3
        ))


def test_mesh_phase_on_four_virtual_devices(fake_devices):
    out = fake_devices(f"""
import json, sys
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke
from repro.api import executor, wire
wire._FusedWire._kernel_active = lambda self: True
executor.MeshExecutor._rs_active = lambda self: True
case = chip_smoke.MeshCase(fit=chip_smoke.FitCase(
    nodes=16, rows=1024, features=256, rounds=20, lr=0.5))
print(json.dumps(chip_smoke.phase_mesh(case)))
""", devices=4)
    for name in ("mesh", "multipod"):
        assert len(out[name]["devices"]) == 4
        assert out[name]["reduce_scatter"]
        assert out[name]["theta_rel_err_vs_local"] <= out["tol"]
        assert out["ledger_bytes"][name] == out["ledger_bytes"]["local"]
    assert out["multipod"]["mesh"] == {"pod": 2, "data": 2}


def test_main_refuses_a_cpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs a TPU" in captured.err


def test_benchmark_runner_fails_on_a_module_that_does_not_run(monkeypatch):
    from benchmarks import run

    monkeypatch.setattr(run, "MODULES", {"missing": "benchmarks.no_such_bench"})
    monkeypatch.setattr(sys, "argv", ["run"])
    with pytest.raises(SystemExit, match="missing"):
        run.main()
