"""The fused scan's counters (``QueryStats``) and the program's trace names.

The counters are checked against an independent host replay of the
overflow rule over the dense reference scores; the servers' step records
and request links against what the engine reported; the host spans
against a CPU profiler trace."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import (
    EnginePolicy,
    QueryStats,
    SuCoConfig,
    SuCoEngine,
    TileConfig,
    build_index,
    suco_query_fused,
)
from repro.core import subspace as sub
from repro.core.sc_linear import candidate_pool_size
from repro.core.suco import _fused_query, suco_scores
from repro.data import make_dataset
from repro.serve.ann import AnnRequest, AnnServer, AsyncAnnServer
from repro.spans import BUILD_SPANS, PREFIX, SERVE_SPANS

N, D, M, K = 4000, 32, 16, 10
ALPHA, BETA = 0.05, 0.02
CFG = SuCoConfig(n_subspaces=8, sqrt_k=16, kmeans_iters=4, seed=0)


@pytest.fixture(scope="module")
def problem():
    ds = make_dataset("gaussian_mixture", N, D, m=M, k=K, seed=0)
    x = jnp.asarray(ds.x)
    return ds, x, build_index(x, CFG)


def _fused(x, index, q, tiles):
    return _fused_query(
        x, index, q, k=K, alpha=ALPHA, beta=BETA, metric="l2", tiles=tiles,
        score_impl="auto", merge_impl="auto",
    )


def _host_overflow(x, index, q, tiles) -> np.ndarray:
    """``(chunks, rows)`` bool: which rows' survivors overflow the cap in
    which chunk, replayed on the host from the dense scores.  A survivor
    beats the carried pool's minimum, and that minimum is the pool-th
    highest score seen so far (-1 while the pool is cold)."""
    n = x.shape[0]
    scores = np.asarray(suco_scores(index, q, sub.collision_count(n, ALPHA)))
    pool = candidate_pool_size(n, K, BETA)
    bn = min(tiles.block_n, n)
    cap = min(tiles.survivor_cap, bn)
    pool_s = np.full((q.shape[0], pool), -1, np.int64)
    over = []
    for b in range(-(-n // bn)):
        blk = scores[:, b * bn:(b + 1) * bn].astype(np.int64)
        thr = pool_s.min(axis=1, keepdims=True)
        over.append((blk > thr).sum(axis=1) > cap)
        both = np.concatenate([pool_s, blk], axis=1)
        pool_s = -np.sort(-both, axis=1)[:, :pool]
    return np.stack(over)


def _host_rerank(x, index, q, tiles) -> tuple[int, int]:
    """``(rerank_rows, rerank_live)`` replayed on the host from the dense
    scores: a chunk with no overflowing row gathers ``cap`` slots per row,
    live where a column beats the carried pool minimum; a chunk that
    overflows gathers its own top ``min(pool, bn)`` per row, live where the
    pick is one of the chunk's real columns."""
    n, m = x.shape[0], q.shape[0]
    scores = np.asarray(suco_scores(index, q, sub.collision_count(n, ALPHA)))
    pool = candidate_pool_size(n, K, BETA)
    bn = min(tiles.block_n, n)
    cap = min(tiles.survivor_cap, bn)
    pool_s = np.full((m, pool), -1, np.int64)
    rows = live = 0
    for b in range(-(-n // bn)):
        blk = scores[:, b * bn:(b + 1) * bn].astype(np.int64)
        surv = (blk > pool_s.min(axis=1, keepdims=True)).sum(axis=1)
        if (surv > cap).any():
            rows += m * min(pool, bn)
            live += m * min(pool, bn, blk.shape[1])
        else:
            rows += m * cap
            live += int(surv.sum())
        both = np.concatenate([pool_s, blk], axis=1)
        pool_s = -np.sort(-both, axis=1)[:, :pool]
    return rows, live


def _host_slot_windows(x, index, q, tiles) -> tuple[int, int]:
    """``(slot_windows, slot_writes)`` replayed on the host: within each
    chunk a row's survivors (columns beating the carried pool minimum)
    take slots from 0 in ``bn``-wide column blocks; a block that moves the
    row's count from c0 to c1 writes the 128-slot windows of slots
    ``[c0, min(c1, cap))``, with ``cap`` rounded up to 128."""
    n, m = x.shape[0], q.shape[0]
    scores = np.asarray(suco_scores(index, q, sub.collision_count(n, ALPHA)))
    pool = candidate_pool_size(n, K, BETA)
    bn = min(tiles.block_n, n)
    cap = -(-min(tiles.survivor_cap, bn) // 128) * 128
    kb = min(tiles.bn, -(-bn // 128) * 128)
    pool_s = np.full((m, pool), -1, np.int64)
    wins = writes = 0
    for b in range(-(-n // bn)):
        blk = scores[:, b * bn:(b + 1) * bn].astype(np.int64)
        keep = blk > pool_s.min(axis=1, keepdims=True)
        c0 = np.zeros(m, np.int64)
        for k0 in range(0, blk.shape[1], kb):
            c1 = c0 + keep[:, k0:k0 + kb].sum(axis=1)
            hi = np.minimum(c1, cap)
            wrote = hi > c0
            wins += int(np.where(wrote, (hi - 1) // 128 - c0 // 128 + 1, 0).sum())
            writes += int(wrote.sum())
            c0 = c1
        both = np.concatenate([pool_s, blk], axis=1)
        pool_s = -np.sort(-both, axis=1)[:, :pool]
    return wins, writes


@pytest.mark.parametrize("tiles", [
    TileConfig(block_n=512, survivor_cap=64),
    TileConfig(block_n=1000, bn=256, survivor_cap=300),  # several blocks a chunk
    TileConfig(block_n=1000, survivor_cap=1),  # every chunk overflows
])
def test_slot_window_counters_match_host_replay(problem, tiles):
    ds, x, index = problem
    q = jnp.asarray(ds.queries)
    _, stats = _fused(x, index, q, tiles)
    wins, writes = _host_slot_windows(x, index, q, tiles)
    assert int(stats.slot_windows) == wins
    assert int(stats.slot_writes) == writes
    assert 0 < writes <= wins
    assert stats.slot_cap == -(-min(tiles.survivor_cap, tiles.block_n) // 128) * 128


@pytest.mark.parametrize("tiles", [
    TileConfig(block_n=512, survivor_cap=64),
    TileConfig(block_n=333, survivor_cap=40),  # does not divide n
    TileConfig(block_n=1000, survivor_cap=1),  # every chunk overflows
    TileConfig(block_n=256, survivor_cap=200),
])
def test_fused_counters_match_host_recount(problem, tiles):
    ds, x, index = problem
    q = jnp.asarray(ds.queries)
    _, stats = _fused(x, index, q, tiles)
    want = _host_overflow(x, index, q, tiles)
    assert stats.chunks == want.shape[0] and stats.rows == M
    assert int(stats.overflow_chunks) == int(want.any(axis=1).sum())
    assert int(stats.overflow_rows) == int(want.sum())


@pytest.mark.parametrize("tiles", [
    TileConfig(block_n=512, survivor_cap=64),
    TileConfig(block_n=333, survivor_cap=40),  # does not divide n
    TileConfig(block_n=1000, survivor_cap=1),  # every chunk overflows
    TileConfig(block_n=256, survivor_cap=200),
    # the last chunk holds 4 columns: its fallback picks past the chunk's end
    TileConfig(block_n=333, survivor_cap=1),
])
def test_rerank_counters_match_host_replay(problem, tiles):
    ds, x, index = problem
    q = jnp.asarray(ds.queries)
    _, stats = _fused(x, index, q, tiles)
    rows, live = _host_rerank(x, index, q, tiles)
    assert int(stats.rerank_rows) == rows
    assert int(stats.rerank_live) == live
    assert 0 < int(stats.rerank_live) <= int(stats.rerank_rows)


def test_no_overflow_when_cap_covers_the_chunk(problem):
    ds, x, index = problem
    _, stats = _fused(x, index, jnp.asarray(ds.queries),
                      TileConfig(block_n=512, survivor_cap=512))
    assert int(stats.overflow_chunks) == 0 and int(stats.overflow_rows) == 0


def test_cold_pool_overflows_chunk_zero_for_every_row(problem):
    """One chunk over the whole data, so the scan's only chunk is chunk 0:
    against the cold pool (-1) every scored column survives, more than
    the cap, in every row."""
    ds, x, index = problem
    _, stats = _fused(x, index, jnp.asarray(ds.queries),
                      TileConfig(block_n=N, survivor_cap=128))
    assert stats.chunks == 1
    assert int(stats.overflow_chunks) == 1 and int(stats.overflow_rows) == M
    assert _host_overflow(x, index, jnp.asarray(ds.queries),
                          TileConfig(block_n=512, survivor_cap=64))[0].all()


@settings(max_examples=6, deadline=None)
@given(block_n=st.integers(64, 1500), cap=st.integers(1, 400))
def test_overflow_rows_bound_by_chunks(problem, block_n, cap):
    ds, x, index = problem
    _, stats = _fused(x, index, jnp.asarray(ds.queries[:4]),
                      TileConfig(block_n=block_n, survivor_cap=cap))
    oc, orows = int(stats.overflow_chunks), int(stats.overflow_rows)
    assert 0 <= oc <= stats.chunks
    assert oc <= orows <= stats.rows * oc


def test_answers_bit_identical_with_and_without_stats(problem):
    ds, x, index = problem
    q = jnp.asarray(ds.queries)
    tiles = TileConfig(block_n=512, survivor_cap=64)
    res, _ = _fused(x, index, q, tiles)
    plain = suco_query_fused(x, index, q, k=K, alpha=ALPHA, beta=BETA, tiles=tiles)
    engine = SuCoEngine(x, index, EnginePolicy(
        alpha=ALPHA, beta=BETA, mode="fused", tiles=tiles))
    served, stats = engine.query_with_stats(q[:5], K)  # padded to bucket 8
    assert isinstance(stats, QueryStats) and stats.rows == 8
    for got in (plain, engine.query(q[:5], K)):
        for a, b in ((res.ids, got.ids), (res.dists, got.dists),
                     (res.scores, got.scores)):
            np.testing.assert_array_equal(np.asarray(a)[: got.ids.shape[0]],
                                          np.asarray(b))
    np.testing.assert_array_equal(np.asarray(served.ids), np.asarray(res.ids[:5]))
    np.testing.assert_array_equal(np.asarray(served.dists),
                                  np.asarray(res.dists[:5]))


@pytest.mark.parametrize("mode", ["dense", "streaming"])
def test_dense_and_streaming_report_no_stats(problem, mode):
    ds, x, index = problem
    engine = SuCoEngine(x, index, EnginePolicy(alpha=ALPHA, beta=BETA, mode=mode))
    res, stats = engine.query_with_stats(jnp.asarray(ds.queries[:3]), K)
    assert stats is None and res.ids.shape == (3, K)


def _fused_engine(x, index):
    return SuCoEngine(x, index, EnginePolicy(
        alpha=ALPHA, beta=BETA, mode="fused", batch_buckets=(4,),
        tiles=TileConfig(block_n=512, survivor_cap=64)))


@pytest.mark.parametrize("server_cls", [AnnServer, AsyncAnnServer])
def test_step_records_carry_counters_and_requests_link_them(problem, server_cls):
    ds, x, index = problem
    engine = _fused_engine(x, index)
    server = server_cls(engine, max_batch=4)
    server.submit_many([AnnRequest(i, ds.queries[i], k=K) for i in range(10)])
    done = server.run_until_drained()
    assert len(server.steps) == 3
    for rec in server.steps:
        assert rec.chunks == 8 and rec.rows == 4  # ceil(4000 / 512), bucket 4
        assert 1 <= rec.overflow_chunks <= rec.overflow_rows <= 4 * rec.overflow_chunks
        assert 0 < rec.rerank_live <= rec.rerank_rows
        assert 0 < rec.slot_writes <= rec.slot_windows
    for r in done:
        assert r.done and any(r.step is s for s in server.steps)
    assert sorted(r.step.n_requests for r in done) == [2] * 2 + [4] * 8
    # the counters are the executable's own for that batch
    batch0 = [r for r in done if r.step is server.steps[0]]
    _, want = engine.query_with_stats(np.stack([r.query for r in batch0]), K)
    assert server.steps[0].overflow_rows == int(want.overflow_rows)
    assert server.steps[0].overflow_chunks == int(want.overflow_chunks)
    assert server.steps[0].rerank_rows == int(want.rerank_rows)
    assert server.steps[0].rerank_live == int(want.rerank_live)
    assert server.steps[0].slot_windows == int(want.slot_windows)
    assert server.steps[0].slot_writes == int(want.slot_writes)
    assert server.steps[0].slot_cap == want.slot_cap == 128


@pytest.mark.parametrize("server_cls", [AnnServer, AsyncAnnServer])
def test_dense_steps_carry_no_counters(problem, server_cls):
    ds, x, index = problem
    engine = SuCoEngine(x, index, EnginePolicy(alpha=ALPHA, beta=BETA))
    server = server_cls(engine, max_batch=4)
    server.submit_many([AnnRequest(i, ds.queries[i], k=K) for i in range(5)])
    done = server.run_until_drained()
    assert all(r.step is not None and r.step.chunks is None for r in done)
    assert all(s.overflow_chunks is None and s.rows is None for s in server.steps)
    assert all(s.rerank_rows is None and s.rerank_live is None for s in server.steps)
    assert all(s.slot_windows is None and s.slot_writes is None
               and s.slot_cap is None for s in server.steps)


def _trace_event_names(log_dir: str) -> set[str]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return {e.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("server_cls", [AnnServer, AsyncAnnServer])
def test_serve_and_build_spans_land_in_a_profiler_trace(
        problem, tmp_path, server_cls):
    ds, x, index = problem
    engine = _fused_engine(x, index)
    engine.warmup(batch_sizes=(4,), ks=(K,))
    small = SuCoConfig(n_subspaces=4, sqrt_k=8, kmeans_iters=2, seed=1)
    with jax.profiler.trace(str(tmp_path)):
        server = server_cls(engine, max_batch=4)
        server.submit_many([AnnRequest(i, ds.queries[i], k=K) for i in range(4)])
        server.run_until_drained()
        jax.block_until_ready(build_index(x, small).cell_ids)
    names = _trace_event_names(str(tmp_path))
    for span in SERVE_SPANS + BUILD_SPANS:
        assert PREFIX + span in names, span
