"""The program's own spans in the benchmark: the exclusive span time that
``step_host_ms.serve_decode`` reads, that reader on a tiny traced serving
run on the CPU, the readers of the device trace unmoved on the small chip
trace, and the chip trace of the program's names
(``data/program.xplane.pb``, made by ``record_program_trace.py``)."""

import contextlib
import io
import json
import math
from pathlib import Path

import pytest

from chipbench import harness, spans
from chipbench import run as run_mod
from chipbench import tracereduce as tr
from chipbench.tests import tiny

DATA = Path(__file__).parent / "data"

# What every reader of the benchmark gives on the small chip trace.
SMALL_READERS = {
    "collective_exposed_ms.fit": None,
    "decode_attention_roofline": None,
    "decode_step_ms": None,
    "device_idle_share.fit": 99.8200208225577,
    "device_idle_share.serve_decode": 99.8200208225577,
    "device_idle_share.serve_prefill": 99.8200208225577,
    "fit_round_roofline": 20.529751863016983,
    "join_ms": None,
    "mfu.decode": None,
    "mfu.fit_round": None,
    "mfu.prefill": None,
    "step_host_ms.serve_decode": None,
    "topk_encode_roofline": 8.02850117918611,
}


def test_exclusive_span_time():
    recs = [
        {"name": "serve/step", "id": 0, "parent": None, "dur": 10_000.0},
        {"name": "serve/decode_step", "id": 1, "parent": 0, "dur": 8_000.0},
        {"name": "serve/decode_step.wait", "id": 2, "parent": 1, "dur": 6_000.0},
        {"name": "serve/prefill", "id": 3, "parent": 0, "dur": 1_000.0},
        {"name": "serve/step", "id": 4, "parent": None, "dur": 500.0},  # no decode
        {"name": "serve/step", "id": 5, "parent": None, "dur": None},  # still open
    ]
    waits = ("serve/decode_step.wait", "serve/prefill")
    got = spans.exclusive_ms(recs, "serve/step", lambda n: n in waits,
                             having="serve/decode_step")
    assert got == [3.0]
    assert sorted(spans.exclusive_ms(recs, "serve/step", lambda n: n in waits)) == [0.5, 3.0]
    # spans without ids (a program that records none) give nothing
    assert spans.exclusive_ms([{"name": "serve/step", "dur": 1.0}], "serve/step",
                              lambda n: False) == []
    assert spans.mean([]) is None


def test_step_host_reader_on_a_tiny_traced_serving_run(tmp_path):
    """The serving engine's spans reach the reader in a traced run; the
    readers of the device trace find no device plane on the CPU."""
    root = tiny.make_root(tmp_path)
    bench = tiny.bench_for(root)
    names = ["step_host_ms.serve_decode", "decode_step_ms", "device_idle_share.serve_decode"]
    bench["per_layer"] = [{"name": m, "unit": "ms", "moves": "output_tokens_per_s"}
                          for m in names]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run_mod.main(["--workload", "serve.tiny.saturated", "--seed", str(2**33 + 5),
                           "--seconds", "1", "--trace", "1"],
                          require_tpu=False, root=root, bench=bench)
    assert rc == 0
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["metrics"]) == {"step_host_ms.serve_decode", "decode_step_ms"}
    host = out["metrics"]["step_host_ms.serve_decode"]["value"]
    assert math.isfinite(host) and 0 < host


@pytest.mark.parametrize("reader", sorted(SMALL_READERS))
def test_readers_on_small_trace(reader):
    from chipbench.peaks import peaks

    facts = {"rounds": 1, "nodes": 1, "features": 2000, "rows": 2048, "chips": 1,
             "decode_tokens": 1, "decode_valid": 100}
    obs = harness.Observed(trace=tr.load(DATA / "small.xplane.pb"), facts=facts,
                           peak=peaks("TPU v5 lite"))
    assert harness.metric_reader(reader).read(obs) == SMALL_READERS[reader]


def test_recorded_program_trace():
    """Two ``serve/step`` spans, each with a ``serve/decode_step`` child
    that runs a jitted step and a ``serve/emit`` child that sleeps 50 ms:
    on the chip the spans share the device's clock, nest as written, and
    the two sleeps are the trace's idle gaps.  The named scopes ride in
    the operations' metadata."""
    from jax.profiler import ProfileData

    path = DATA / "program.xplane.pb"
    raw = path.read_bytes()
    program = sorted(
        (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
        for p in ProfileData.from_serialized_xspace(raw).planes if p.name.startswith("/host:")
        for ln in p.lines for e in ln.events if e.name.startswith("serve/"))
    assert [n for _, _, n in program] == ["serve/step", "serve/decode_step", "serve/emit"] * 2
    steps = [(s, e) for s, e, n in program if n == "serve/step"]
    for s, e, _ in program:
        assert any(a <= s and e <= b for a, b in steps)
    emits = [(s, e) for s, e, n in program if n == "serve/emit"]
    t = tr.load(path)
    ((plane, ops),) = t.devices.items()
    lo, hi = t.window()
    gaps = sorted(tr.subtract([(lo, hi)], tr.busy(t, plane)), key=lambda g: g[0] - g[1])[:2]
    for s, e in gaps:
        assert 0.045e9 < e - s < 0.08e9
        assert any(a <= (s + e) // 2 < b for a, b in emits)
    for scope in ("weight_cast", "kv_gather", "round_metric"):
        assert f"/{scope}/".encode() in raw, scope
