"""``slot_window_share`` reads the score kernel's window counters off the
steps that answered the window's requests, each step once, and reads
nothing where no step carries them."""

from __future__ import annotations

import types

import pytest

from bench import registry


def _step(slot_windows, slot_writes, slot_cap=3328, chunks=49):
    return types.SimpleNamespace(
        overflow_chunks=0, overflow_rows=0, chunks=chunks, rows=64,
        slot_windows=slot_windows, slot_writes=slot_writes, slot_cap=slot_cap)


def _run(steps_of_requests):
    reqs = [types.SimpleNamespace(step=s) for s in steps_of_requests]
    return types.SimpleNamespace(facts={"requests": reqs})


def _read(run):
    return registry.metric_reader("slot_window_share")(run)


def test_share_over_distinct_steps():
    a = _step(1500, 1000)  # 64 requests share this step
    b = _step(30, 10, slot_cap=256)
    share = _read(_run([a] * 64 + [b] * 5))
    assert share == pytest.approx(100 * 128 * 1530 / (3328 * 1000 + 256 * 10))


def test_one_window_per_write_reads_one_window_of_the_cap():
    assert _read(_run([_step(980, 980)])) == pytest.approx(100 / 26)


@pytest.mark.parametrize("steps", [
    [],  # no answered request
    [None, None],  # requests that link no step
    [types.SimpleNamespace(overflow_chunks=4, overflow_rows=250, chunks=49,
                           rows=64)] * 2,  # a program without the counters
    [_step(None, None, None, chunks=None)] * 3,  # the dense path's steps
    [_step(0, 0)],  # no row wrote a slot
])
def test_reads_nothing_without_counters(steps):
    assert _read(_run(steps)) is None


def test_listed_for_the_query_cells():
    spec = registry.benchmark()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == "slot_window_share"]
    assert entry["layer"] == "kernels" and entry["moves"] == "qps"
    for cell in entry["workloads"]:
        assert "qps" in {m["name"] for m in registry.cell(cell).end_to_end}
