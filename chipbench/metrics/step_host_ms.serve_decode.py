"""Host time per decode tick of the serving scheduler: over the
program's ``serve/step`` spans in the traced stretch that hold a
``serve/decode_step``, the mean of the span's duration minus what its
``serve/decode_step.wait`` and ``serve/prefill`` descendants cover (the
waits for the device), in ms.  What is left is the host's own work in
the tick: admitting requests, uploading the last tokens, block table,
lengths and seeds, dispatching the step, the first-token samples of
joins, and the per-slot token loop and retirements."""

from chipbench import spans

WAITS = ("serve/decode_step.wait", "serve/prefill")


def read(obs):
    return spans.mean(spans.exclusive_ms(obs.spans, "serve/step", lambda name: name in WAITS,
                                         having="serve/decode_step"))
