"""Tracing plane: overhead/exactness contracts, Chrome export schema,
RunReport joins, and the serve-path spans.

The load-bearing guarantees (docs/OBSERVABILITY.md):

* a fit with a tracer (on, off, or absent) returns BITWISE-identical
  results — tracing is host-side only, never inside the compiled
  program;
* ``trace="phases"`` replays fenced probes AFTER the fit, so it is
  bit-exact by construction — asserted anyway;
* ``export_chrome`` emits valid trace-event JSON (``ph``/``ts``/``pid``/
  ``tid``/``name`` on every event) loadable in Perfetto;
* mesh/multipod placements get per-hop collective spans and per-phase
  device timings (8-fake-device subprocess case);
* ``ServeMetrics`` keeps a bounded latency window evicting oldest-first
  with p50/p95/p99 over the survivors.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from collections import deque

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.ml.linear import lsq_loss
from repro.serve import MicroBatcher, ServeEngine, ServeMetrics
from repro.telemetry import RunReport, Tracer
from repro.telemetry import trace as trace_mod

K, NK, N, STEPS = 8, 12, 5, 25


@pytest.fixture()
def problem():
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(K, NK, N)))
    w = jnp.asarray(rng.normal(size=(N,)))
    y = jnp.einsum("kni,i->kn", X, w)
    return (X, y)


def _fit(data, **kw):
    return api.fit(
        api.GradientDescent(lsq_loss, lr=0.05), data,
        transport="allreduce", steps=STEPS, **kw,
    )


def _bitwise(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


class TestExactness:
    def test_tracer_off_bitwise_identical(self, problem):
        """The zero-overhead contract's correctness half: no-tracer,
        disabled-tracer, and live-tracer fits all produce the same bits
        (theta, trajectory, ledger)."""
        base = _fit(problem)
        for tracer in (Tracer(enabled=False), Tracer()):
            res = _fit(problem, tracer=tracer)
            assert _bitwise(base.theta, res.theta)
            assert _bitwise(base.trajectory, res.trajectory)
            assert base.ledger.summary() == res.ledger.summary()

    def test_disabled_tracer_records_nothing(self, problem):
        t = Tracer(enabled=False)
        _fit(problem, tracer=t)
        t.count("x")
        assert t.spans == [] and t.counters == {}

    def test_trace_phases_bit_exact(self, problem):
        """trace="phases" never touches the fit program — the probes
        replay afterwards — so results stay bitwise identical."""
        base = _fit(problem)
        t = Tracer()
        res = _fit(problem, tracer=t, trace="phases")
        assert _bitwise(base.theta, res.theta)
        assert _bitwise(base.trajectory, res.trajectory)
        assert base.ledger.summary() == res.ledger.summary()
        names = {s["name"] for s in t.spans}
        assert {"fit/loop", "phase/local_step", "phase/encode"} <= names

    def test_trace_phases_requires_tracer(self, problem):
        with pytest.raises(ValueError, match="tracer"):
            _fit(problem, trace="phases")
        with pytest.raises(ValueError, match="trace"):
            _fit(problem, trace="rounds")


class TestTracer:
    def test_span_nesting_and_summary(self):
        t = Tracer()
        with t.span("outer", round=1):
            with t.span("inner"):
                pass
            with t.span("inner"):
                pass
        agg = t.summary()
        assert agg["inner"]["count"] == 2
        assert agg["outer"]["count"] == 1
        assert agg["outer"]["total_s"] >= agg["inner"]["total_s"]
        assert t.wall_s("outer") == agg["outer"]["total_s"]

    def test_counters_and_gauges(self):
        t = Tracer()
        t.count("hits")
        t.count("hits", 2)
        t.gauge("depth", 7)
        t.gauge("depth", 3)
        assert t.counters == {"hits": 3}
        assert t.gauges == {"depth": 3}

    def test_span_tags_mutable_inside(self):
        t = Tracer()
        with t.span("s", a=1) as rec:
            rec["tags"]["b"] = 2
        assert t.spans[0]["tags"] == {"a": 1, "b": 2}

    def test_ambient_span_noop_without_tracer(self):
        assert trace_mod.current_tracer() is None
        with trace_mod.span("nothing"):
            pass  # must not raise, must not record anywhere

    def test_ambient_activation(self):
        t = Tracer()
        with trace_mod.activated(t):
            assert trace_mod.current_tracer() is t
            with trace_mod.span("ambient"):
                pass
        assert trace_mod.current_tracer() is None
        assert [s["name"] for s in t.spans] == ["ambient"]

    def test_span_parents_nest_as_written(self):
        t = Tracer()
        with t.span("outer"):
            with t.span("inner"):
                with t.span("leaf"):
                    pass
            with t.span("inner"):
                pass
        with t.span("next"):
            pass
        by = {(s["name"], i): s for i, s in enumerate(t.spans)}
        outer, inner, leaf, inner2, nxt = (by[k] for k in sorted(by, key=lambda k: k[1]))
        assert outer["parent"] is None and nxt["parent"] is None
        assert inner["parent"] == outer["id"] and inner2["parent"] == outer["id"]
        assert leaf["parent"] == inner["id"]
        assert len({s["id"] for s in t.spans}) == 5

    def test_activated_none_keeps_the_enclosing_tracer(self, problem):
        t = Tracer()
        with trace_mod.activated(t):
            with trace_mod.activated(None) as inner:
                assert inner is t and trace_mod.current_tracer() is t
            assert trace_mod.current_tracer() is t
            # api.fit(tracer=None) records into the ambient tracer
            _fit(problem)
        assert trace_mod.current_tracer() is None
        names = [s["name"] for s in t.spans]
        assert names.count("fit/call") == 1 and "fit/loop" in names
        call = next(s for s in t.spans if s["name"] == "fit/call")
        for name in ("fit/loop", "fit/ledger", "fit/metrics"):
            assert next(s for s in t.spans if s["name"] == name)["parent"] == call["id"]
        dispatch = [s for s in t.spans if s["name"].startswith("dispatch/")]
        loop = next(s for s in t.spans if s["name"] == "fit/loop")
        assert dispatch and all(s["parent"] == loop["id"] for s in dispatch)

    def test_spans_land_in_a_profiler_trace(self, tmp_path):
        """An enabled tracer's span is a profiler annotation too: it shows
        up on the profile's host plane, nested as written and no longer
        than the span's in-memory interval."""
        import time
        from pathlib import Path

        import jax
        from jax.profiler import ProfileData

        t = Tracer()
        with jax.profiler.trace(str(tmp_path)):
            with t.span("fit/call"):
                with t.span("dispatch/local-update"):
                    time.sleep(0.01)
                with t.span("fit/ledger"):
                    time.sleep(0.002)
        (xplane,) = Path(tmp_path).rglob("*.xplane.pb")
        names = {s["name"] for s in t.spans}
        got = {e.name: (int(e.start_ns), int(e.start_ns + e.duration_ns))
               for p in ProfileData.from_file(str(xplane)).planes if p.name.startswith("/host:")
               for ln in p.lines for e in ln.events if e.name in names}
        assert set(got) == names
        rec = {s["name"]: s for s in t.spans}
        for name, (s, e) in got.items():
            assert 0 < (e - s) / 1e3 <= rec[name]["dur"]
        call = got["fit/call"]
        for child in ("dispatch/local-update", "fit/ledger"):
            assert call[0] <= got[child][0] and got[child][1] <= call[1]
        assert got["dispatch/local-update"][1] <= got["fit/ledger"][0]

    def test_chrome_export_schema(self, problem, tmp_path):
        """The acceptance criterion: every exported event carries the
        trace-event schema keys, complete events carry dur, and the file
        is valid JSON under a traceEvents root."""
        t = Tracer()
        _fit(problem, tracer=t, trace="phases")
        t.count("custom", 3)
        path = t.export_chrome(str(tmp_path / "run.trace.json"))
        with open(path) as f:
            payload = json.load(f)
        events = payload["traceEvents"]
        assert events, "no events exported"
        for e in events:
            assert {"ph", "ts", "pid", "tid", "name"} <= set(e), e
            assert e["ph"] in ("X", "C", "M"), e
            if e["ph"] == "X":
                assert "dur" in e and e["dur"] >= 0
        names = {e["name"] for e in events if e["ph"] == "X"}
        assert "fit/loop" in names and "phase/local_step" in names
        assert any(
            e["ph"] == "C" and e["name"] == "custom" for e in events
        )

    def test_traceview_cli(self, problem, tmp_path):
        t = Tracer()
        _fit(problem, tracer=t)
        path = t.export_chrome(str(tmp_path / "run.trace.json"))
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "traceview.py"),
             path],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        assert "fit/loop" in proc.stdout


class TestNamedScopes:
    def test_fit_program_names_its_device_work(self, problem, monkeypatch):
        """The round program's operations carry the strategy's local step,
        the wire's encode and the round's metric in their op metadata
        (what a profiler trace reads as each operation's scope)."""
        from repro.api import executor as ex

        built = []
        dispatch = ex.dispatch

        def capture(key, build, label, *args):
            built.append((build, args))
            return dispatch(key, build, label, *args)

        monkeypatch.setattr(ex, "dispatch", capture)
        _fit(problem, wire="topk:0.5+ef")
        build, args = built[0]
        text = build().lower(*args).compile().as_text()
        for scope in ("local_updates", "wire_encode", "round_metric"):
            assert f"/{scope}/" in text, scope


class TestRunReport:
    def test_from_fit_joins_everything(self, problem):
        t = Tracer()
        res = _fit(problem, wire="topk:0.5+ef", tracer=t, trace="phases")
        rep = RunReport.from_fit(res, tracer=t)
        d = rep.as_dict()
        assert d["config"]["wire"] == "topk:0.5+ef"
        assert d["comm"]["total_bytes"] == res.ledger.total_bytes
        assert "fit/loop" in d["spans"]
        assert "wire_kernel_hits" in d
        assert {"hits", "misses", "size"} <= set(d["program_cache"])
        json.dumps(d)  # the whole artifact is one JSON-serializable dict
        md = rep.to_markdown()
        assert "RunReport (fit)" in md and "fit/loop" in md

    def test_from_serve(self, problem):
        res = _fit(problem)
        strategy = api.GradientDescent(lsq_loss, lr=0.05)
        t = Tracer()
        eng = ServeEngine.from_fit(res, strategy, tracer=t)
        eng.predict(np.zeros((3, N), np.float32))
        rep = RunReport.from_serve(eng)
        d = rep.as_dict()
        assert d["serve"]["requests"] == 3
        assert "serve/predict" in d["spans"]
        assert "p99_latency_ms" in d["serve"]
        assert "RunReport (serve)" in rep.to_markdown()

    def test_sweep_fit_report(self, problem):
        t = Tracer()
        res = _fit(
            problem, tracer=t,
            executor=api.SweepExecutor({"lr": jnp.asarray([0.02, 0.1])}),
        )
        d = RunReport.from_fit(res, tracer=t).as_dict()
        assert d["comm"]["scenarios"] == 2
        json.dumps(d)

    def test_metrics_json(self, problem):
        res = _fit(problem, executor="serve")
        m = res.metrics_json()
        assert "carry" not in m
        assert m["serve_engine"] == "<ServeEngine>"
        assert m["transport"] == "allreduce"
        json.dumps(m)
        # and the raw metrics really are NOT serializable — the reason
        # metrics_json exists
        with pytest.raises(TypeError):
            json.dumps(res.metrics)


class TestServeTracing:
    def test_engine_spans_and_counters(self, problem):
        res = _fit(problem)
        strategy = api.GradientDescent(lsq_loss, lr=0.05)
        t = Tracer()
        eng = ServeEngine.from_fit(res, strategy, tracer=t)
        eng.predict(np.zeros((2, N), np.float32))
        eng.swap(res.theta)
        names = [s["name"] for s in t.spans]
        assert "serve/swap" in names and "serve/predict" in names
        assert t.counters["serve/requests"] == 2

    def test_engine_captures_ambient_tracer(self, problem):
        t = Tracer()
        res = _fit(problem, executor="serve", tracer=t)
        assert res.metrics["serve_engine"].tracer is t

    def test_batcher_queue_wait(self):
        now = [0.0]
        t = Tracer()
        mb = MicroBatcher(
            lambda X: X * 2.0, max_batch=4, clock=lambda: now[0], tracer=t,
        )
        mb.submit(np.zeros(3, np.float32))
        now[0] = 1.0
        mb.submit(np.zeros(3, np.float32))
        now[0] = 5.0
        mb.flush()
        (serve_span,) = [s for s in t.spans if s["name"] == "batcher/serve"]
        assert serve_span["tags"]["queue_wait_ms"] == pytest.approx(5000.0)
        assert serve_span["tags"]["valid"] == 2
        assert serve_span["tags"]["bucket"] == 2
        assert t.counters["batcher/queue_wait_s"] == pytest.approx(9.0)
        assert t.counters["batcher/requests"] == 2


class TestServeMetricsWindow:
    def test_p99_key(self):
        m = ServeMetrics()
        s = m.summary()
        assert "p99_latency_ms" in s and s["p99_latency_ms"] == 0.0

    def test_window_evicts_oldest_first(self):
        """The bounded latency window is a deque(maxlen=W): request W+1
        pushes out request 1, never a newer one — percentiles always
        describe the most recent W requests."""
        m = ServeMetrics(latencies_s=deque(maxlen=4))
        z = np.zeros(1, np.float32)
        for lat in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
            m.record_batch(1, 1, lat, z, z, tag="t")
        assert list(m.latencies_s) == [3.0, 4.0, 5.0, 6.0]
        s = m.summary()
        assert s["p99_latency_ms"] == pytest.approx(6000.0)
        assert s["p50_latency_ms"] == pytest.approx(5000.0)
        # exact totals are NOT windowed — all six requests counted
        assert s["requests"] == 6


class TestMultipodEightDevices:
    """Acceptance case: on a 2×4 ``("pod", "data")`` mesh (8 fake CPU
    devices, forced in a subprocess), a traced multipod fit with a
    topk+ef wire yields per-hop collective spans (``hop/intra_pod`` /
    ``hop/inter_pod``), per-phase wall times, cache state and kernel
    hits in ONE RunReport — and stays bitwise identical to the untraced
    fit."""

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
from repro.telemetry import RunReport, Tracer

rng = np.random.default_rng(0)
X = jnp.asarray(rng.normal(size=(8, 10, 16)))
w = jnp.asarray(rng.normal(size=(16,)))
y = jnp.einsum("kni,i->kn", X, w)
mesh = jax.make_mesh((2, 4), ("pod", "data"))

def fit(**kw):
    return api.fit(api.GradientDescent(lsq_loss, lr=0.05), (X, y),
                   transport="allreduce", steps=20,
                   executor=api.MultiPodExecutor(mesh),
                   wire="topk:0.5+ef", **kw)

base = fit()
tracer = Tracer()
res = fit(tracer=tracer, trace="phases")
a, b = np.asarray(base.theta), np.asarray(res.theta)
d = RunReport.from_fit(res, tracer=tracer).as_dict()
events = tracer.chrome_events()
out = {
    "num_devices": jax.device_count(),
    "theta_bitwise": bool((a.view(np.uint32) == b.view(np.uint32)).all()),
    "span_names": sorted({s["name"] for s in tracer.spans}),
    "by_hop": sorted(d["comm"]["by_hop"]),
    "hop_bytes_positive": all(
        h["total_bytes"] > 0 for h in d["comm"]["by_hop"].values()
    ),
    "has_kernel_hits": "wire_kernel_hits" in d,
    "report_json_ok": bool(json.dumps(d)),
    "schema_ok": all(
        {"ph", "ts", "pid", "tid", "name"} <= set(e) for e in events
    ),
}
print(json.dumps(out))
"""

    def test_per_hop_spans(self, fake_devices):
        out = fake_devices(self.SCRIPT)
        assert out["num_devices"] == 8
        assert out["theta_bitwise"], "traced multipod fit drifted"
        names = set(out["span_names"])
        assert {"hop/intra_pod", "hop/inter_pod", "phase/local_step",
                "phase/encode", "phase/stats_completion",
                "dispatch/multipod-update", "fit/loop"} <= names, names
        assert out["by_hop"] == ["inter_pod", "intra_pod"]
        assert out["hop_bytes_positive"]
        assert out["has_kernel_hits"]
        assert out["report_json_ok"] and out["schema_ok"]
