"""Share of the score kernel's former survivor one-hot that it still
builds: ``100 * 128 * sum(slot_windows) / (slot_cap * sum(slot_writes))``
over the distinct steps that answered the window's requests, counted
inside the query executable over each padded batch.  A (row, column block)
pair that writes any survivor slot once built a one-hot over all
``slot_cap`` slots; it now builds one per 128-slot window its survivors
touch.  None where no step carries these counters (the dense and
streaming paths, a program without them)."""

from bench.counters import counted_steps


def read(run):
    steps = [s for s in counted_steps(run.facts)
             if getattr(s, "slot_writes", None) is not None]
    full = sum(s.slot_cap * s.slot_writes for s in steps)
    if not full:
        return None
    return 100.0 * 128 * sum(s.slot_windows for s in steps) / full
