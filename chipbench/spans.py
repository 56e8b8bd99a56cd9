"""Reading the program's own span records (``repro.telemetry.trace.Tracer``):
each record has a ``name``, a duration ``dur`` in microseconds, an ``id``
and the ``parent`` id of the span that was open on its thread when it
began.  A program without ``id``/``parent`` (or without the spans asked
for) gives nothing."""

from __future__ import annotations


def exclusive_ms(spans: list, top: str, minus, *, having: str | None = None) -> list:
    """For each closed span named ``top``: its duration minus the summed
    durations of its descendants whose names ``minus(name)`` accepts, in
    ms.  With ``having``, only the ``top`` spans that have a descendant of
    that name."""
    by_id = {s["id"]: s for s in spans if s.get("id") is not None and s.get("dur") is not None}
    out: dict = {i: [s["dur"], having is None] for i, s in by_id.items() if s["name"] == top}
    for s in by_id.values():
        take, mark = minus(s["name"]), s["name"] == having
        if not (take or mark):
            continue
        p = by_id.get(s.get("parent"))
        while p is not None and p["name"] != top:
            p = by_id.get(p.get("parent"))
        if p is None:
            continue
        if take:
            out[p["id"]][0] -= s["dur"]
        if mark:
            out[p["id"]][1] = True
    return [1e-3 * dur for dur, keep in out.values() if keep]


def mean(values: list):
    return sum(values) / len(values) if values else None
