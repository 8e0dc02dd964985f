"""Share of (query row, chunk) pairs whose own survivors overflowed the
fused scan's ``survivor_cap``: ``100 * sum(overflow_rows) / sum(rows *
chunks)`` over the distinct steps that answered the window's requests,
counted inside the query executable over each padded batch.  Set against
``overflow_share`` it says how much of the batch-wide fallback a per-row
fallback would avoid."""

from bench.counters import counted_steps


def read(run):
    steps = counted_steps(run.facts)
    if not steps:
        return None
    return (100.0 * sum(s.overflow_rows for s in steps)
            / sum(s.rows * s.chunks for s in steps))
