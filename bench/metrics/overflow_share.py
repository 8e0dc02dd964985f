"""Share of the fused scan's chunks that took the overflow fallback:
``100 * sum(overflow_chunks) / sum(chunks)`` over the distinct steps that
answered the window's requests, counted inside the query executable."""

from bench.counters import counted_steps


def read(run):
    steps = counted_steps(run.facts)
    if not steps:
        return None
    return 100.0 * sum(s.overflow_chunks for s in steps) / sum(s.chunks for s in steps)
