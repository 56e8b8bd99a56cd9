"""Share of the chip's bf16 peak that decoding the MLA + expert stack
reaches: the operations the decoded tokens need
(``opsbytes_mla_moe.decode_flops``: 2 per weight outside the routed
experts, the absorbed attention over each token's valid context, and
each route to a held expert, counted by the program's
``serve/moe_routes_held``), over the summed ``serve/decode_step`` spans,
over the peak.  Nothing where the program counts no held routes."""

from chipbench import opsbytes_mla_moe


def read(obs):
    f = obs.facts
    held = obs.counters.get("serve/moe_routes_held")
    if not f.get("decode_step_s") or not obs.peak or held is None:
        return None
    flops = opsbytes_mla_moe.decode_flops(obs.cfg, f["decode_tokens"], f["decode_valid"], held)
    return 100.0 * flops / f["decode_step_s"] / obs.peak["bf16_flops_per_s"]
