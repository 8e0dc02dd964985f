"""The gist1m cells: found by name with their metrics, run end to end on
the CPU at a small high-d size, and ``rerank_live_share`` read off the
fused scan's counters."""

from __future__ import annotations

import types

import pytest

from bench import registry
from bench_tiny import TINY, run_cpu, tiny_cell

# halves 15 wide: like gist1m's 60, no multiple of the TPU's 8 sublanes
TINY_HD = dict(TINY, name="tiny_hd", d=240,
               index={"n_subspaces": 8, "sqrt_k": 8, "kmeans_iters": 6})


def test_gist_cells_resolve_with_their_metrics():
    online, rebuild = registry.cell("gist1m.online"), registry.cell("gist1m.rebuild")
    for cell in (online, rebuild):
        assert cell.config["name"] == "gist1m" and cell.config["d"] == 960
        assert cell.config["n"] == 1_000_000 and cell.chips == 1
    assert online.traffic == registry.traffic("closed64")
    assert rebuild.traffic == registry.traffic("rebuild")
    assert {m["name"] for m in online.end_to_end} == {"qps", "p95_ms", "setup_s"}
    assert {m["name"] for m in online.per_layer} == {
        "query_batch_ms", "sc_score_roofline", "device_idle.query",
        "queue_p95_ms", "overflow_share", "overflow_query_share",
        "rerank_live_share"}
    assert {m["name"] for m in rebuild.end_to_end} == {"build_s", "setup_s"}
    assert {m["name"] for m in rebuild.per_layer} == {
        "kmeans_assign_roofline", "device_idle.build"}
    assert "rerank_live_share" in {
        m["name"] for m in registry.cell("sift1m.online").per_layer}


@pytest.mark.parametrize("traced", [False, True])
def test_high_d_query_cell_is_correct(traced):
    cell = tiny_cell("gist1m.online", {
        "loop": "closed", "clients": 8, "k": 10, "max_batch": 8,
        "query_pool": 64, "check_sample": 32,
    }, TINY_HD)
    out = run_cpu(cell, traced=traced)
    assert out["correct"] is True and out["failed"] == 0
    # the tiny corpus takes the dense path: no counters, so traced runs
    # read only the host-clock metric
    want = {"queue_p95_ms"} if traced else {"qps", "p95_ms", "setup_s"}
    assert set(out["metrics"]) == want


@pytest.mark.parametrize("traced", [False, True])
def test_high_d_rebuild_cell_is_correct(traced):
    cell = tiny_cell("gist1m.rebuild", {"loop": "rebuild", "check_sample": 2},
                     TINY_HD)
    out = run_cpu(cell, traced=traced)
    assert out["correct"] is True
    assert set(out["metrics"]) == (set() if traced else {"build_s", "setup_s"})


def _step(rows, live, chunks=49):
    return types.SimpleNamespace(
        overflow_chunks=0, overflow_rows=0, chunks=chunks, rows=64,
        rerank_rows=rows, rerank_live=live)


def _read(steps_of_requests):
    reqs = [types.SimpleNamespace(step=s) for s in steps_of_requests]
    run = types.SimpleNamespace(facts={"requests": reqs})
    return registry.metric_reader("rerank_live_share")(run)


def test_rerank_live_share_over_distinct_steps():
    a, b = _step(1000, 250), _step(600, 600)
    assert _read([a] * 64 + [b] * 3) == pytest.approx(100 * 850 / 1600)


@pytest.mark.parametrize("steps", [
    [],  # no answered request
    [None, None],  # requests that link no step
    [_step(None, None, chunks=None)] * 2,  # the dense path's steps
    # a program whose steps carry the overflow counters but not these
    [types.SimpleNamespace(overflow_chunks=1, overflow_rows=2, chunks=49,
                           rows=64)] * 2,
])
def test_rerank_live_share_reads_nothing_without_counters(steps):
    assert _read(steps) is None
