"""Share of its roofline that the paged MLA decode kernel reaches: the
least time for the flops and bytes that the slots' valid lengths need
(each valid position's latent row read once; rows of pages past a slot's
length do not count; ``opsbytes_mla_moe.mla_decode_cost``), over the
kernel's summed device time in the trace.  The kernel is the Pallas call
named ``paged_mla_decode``; the GQA kernel never matches."""

from chipbench import opsbytes, opsbytes_mla_moe, tracereduce

KERNEL = r"^paged_mla_decode"


def read(obs):
    f = obs.facts
    if obs.trace is None or not f.get("decode_tokens") or not obs.peak:
        return None
    secs = tracereduce.op_s(obs.trace, KERNEL, kernels=True)
    if secs <= 0:
        return None
    flops, nbytes = opsbytes_mla_moe.mla_decode_cost(obs.cfg, f["decode_valid"],
                                                     f["decode_tokens"], 2)
    return opsbytes.roofline_share(flops, nbytes, secs, obs.peak)[0]
