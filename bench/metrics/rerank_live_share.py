"""Share of the rows of ``x`` that the fused scan gathered to rerank which
held a live candidate: ``100 * sum(rerank_live) / sum(rerank_rows)`` over
the distinct steps that answered the window's requests, counted inside the
query executable over each padded batch.  The rest of the gather, a dead
survivor slot or a fallback pick past the chunk's end, is bytes moved for
nothing; at d=960 a gathered row is 3,840 B.  None where no step carries
these counters (the dense and streaming paths, a program without them)."""

from bench.counters import counted_steps


def read(run):
    steps = [s for s in counted_steps(run.facts)
             if getattr(s, "rerank_rows", None) is not None]
    rows = sum(s.rerank_rows for s in steps)
    if not rows:
        return None
    return 100.0 * sum(s.rerank_live for s in steps) / rows
