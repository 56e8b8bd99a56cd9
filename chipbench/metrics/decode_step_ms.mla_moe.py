"""Mean time of one continuous-batching decode step of the MLA + expert
stack: the program's ``serve/decode_step`` span, which closes after the
step's tokens are on the host, over the steps of the traced stretch."""


def read(obs):
    f = obs.facts
    if not f.get("decode_steps"):
        return None
    return 1e3 * f["decode_step_s"] / f["decode_steps"]
