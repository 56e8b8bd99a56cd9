"""Record the trace of the program's own names that
``test_program_spans.py`` reads.

    python chipbench/tests/record_program_trace.py <program.xplane.pb>

On a TPU it runs, under the profiler and inside a ``bench/window`` host
span, a jitted ``step`` whose operations sit under named scopes
(``weight_cast`` inside a loop over stacked weights, ``kv_gather``,
``round_metric``), twice, each time inside a ``Tracer`` span
``serve/step`` with the children ``serve/decode_step`` (the step) and
``serve/emit`` (a sleep of 50 ms): a known idle gap under a program span.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from chipbench import tracereduce
    from repro.telemetry.trace import Tracer

    if jax.devices()[0].platform != "tpu":
        print("record_program_trace.py: needs a TPU", file=sys.stderr)
        return 1

    def step(ws, x, cache, idx):
        def layer(h, w):
            with jax.named_scope("weight_cast"):
                wb = w.astype(jnp.bfloat16)
            return jnp.tanh(h @ wb), None

        h, _ = jax.lax.scan(layer, x.astype(jnp.bfloat16), ws)
        with jax.named_scope("kv_gather"):
            kv = jnp.take(cache, idx, axis=0).reshape(-1, cache.shape[-1])
        with jax.named_scope("round_metric"):
            metric = jnp.mean(jnp.abs(kv.astype(jnp.float32) @ h[0].astype(jnp.float32)))
        return h, metric

    k = jax.random.split(jax.random.key(2), 3)
    ws = jax.random.normal(k[0], (4, 1024, 1024), jnp.float32)
    x = jax.random.normal(k[1], (128, 1024), jnp.float32)
    cache = jax.random.normal(k[2], (512, 16, 1024), jnp.bfloat16)
    idx = (jnp.arange(256, dtype=jnp.int32) * 7) % 512
    step = jax.jit(step)
    jax.block_until_ready(step(ws, x, cache, idx))
    tracer = Tracer()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(2):
            with tracer.span("serve/step"):
                with tracer.span("serve/decode_step"):
                    jax.block_until_ready(step(ws, x, cache, idx))
                with tracer.span("serve/emit"):
                    time.sleep(0.05)
    jax.profiler.stop_trace()
    # the trace names source files by absolute path: keep it free of
    # where the checkout lay (same length, so the protobuf stays valid)
    raw = tracereduce.find_xplane(d).read_bytes()
    prefix = str(REPO).encode() + b"/"
    Path(out).write_bytes(raw.replace(prefix, b"." * (len(prefix) - 1) + b"/"))
    shutil.rmtree(d, ignore_errors=True)
    tr = tracereduce.load(out)
    print("devices", list(tr.devices), "busy_s", tracereduce.busy_s(tr),
          "window_s", tracereduce.window_s(tr))
    print("top_ops", tracereduce.top_ops(tr))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
