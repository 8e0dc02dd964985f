"""The fused query scan's own counters (``repro.core.suco.QueryStats``), as
the server writes them on each ``StepRecord`` and links the record from
every request it answered (``AnnRequest.step``)."""

from __future__ import annotations


def counted_steps(facts: dict) -> list:
    """The distinct steps that answered the window's requests and carry
    counters.  Empty where none does: the dense and streaming paths, and a
    program whose requests link no step."""
    seen: dict[int, object] = {}
    for r in facts["requests"]:
        st = getattr(r, "step", None)
        if st is not None and getattr(st, "chunks", None) is not None:
            seen.setdefault(id(st), st)
    return list(seen.values())
