"""The overflow metrics read the fused scan's counters off the steps that
answered the window's requests, each step once."""

from __future__ import annotations

import types

import pytest

from bench import registry


def _step(overflow_chunks, overflow_rows, chunks, rows):
    return types.SimpleNamespace(
        overflow_chunks=overflow_chunks, overflow_rows=overflow_rows,
        chunks=chunks, rows=rows)


def _run(steps_of_requests):
    reqs = [types.SimpleNamespace(step=s) for s in steps_of_requests]
    return types.SimpleNamespace(facts={"requests": reqs})


def test_shares_over_distinct_steps():
    a = _step(4, 250, 49, 64)  # 64 requests share this step
    b = _step(2, 3, 49, 8)
    run = _run([a] * 64 + [b] * 5)
    chunks = registry.metric_reader("overflow_share")(run)
    rows = registry.metric_reader("overflow_query_share")(run)
    assert chunks == pytest.approx(100 * 6 / 98)
    assert rows == pytest.approx(100 * 253 / (64 * 49 + 8 * 49))


@pytest.mark.parametrize("steps", [
    [],  # no answered request
    [None, None],  # requests that link no step: a program without counters
    [_step(None, None, None, None)] * 3,  # the dense path's steps
])
def test_no_counters_read_nothing(steps):
    run = _run(steps)
    for name in ("overflow_share", "overflow_query_share"):
        assert registry.metric_reader(name)(run) is None


def test_requests_without_a_step_attribute_read_nothing():
    run = types.SimpleNamespace(facts={"requests": [object(), object()]})
    assert registry.metric_reader("overflow_share")(run) is None
