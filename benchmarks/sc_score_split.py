"""Split the fused query's score stage on the chip: collision lookup,
prefilter and survivor compaction, in ms per chunk.

Builds a benchmark configuration's corpus and index from ``--seed`` as
``bench/run.py`` does (``bench/configs/<config>.json``), takes the first
batch of the closed loop's query pool, and runs three Pallas kernels over
every chunk of the scan, each in one jitted ``lax.scan`` at the tiles the
engine plans for that batch:

* ``cells``: ``sc_score_cells_kernel``, the collision lookup alone;
* ``prefilter``: ``sc_score_cells_prefilter_kernel``, lookup plus the
  keep mask;
* ``compact``: ``sc_score_cells_prefilter_compact_kernel``, the kernel the
  fused query runs, lookup plus prefix count and slot write.

``compact - prefilter`` is the compaction's cost.  Each chunk sees the
pool minimum the fused scan would carry into it: the ``pool``-th largest
score of the chunks before it (-1 while fewer columns were seen).  The
script also times the whole fused query on the batch and prints a hash of
its answers, so two checkouts can be compared bit for bit.

    python benchmarks/sc_score_split.py --config sift1m --seed 6200000001

from the root of a checkout, on a TPU (elsewhere the kernels run in
interpret mode, which checks the script and times nothing).  Prints one
JSON line.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _timed(fn, *args, reps: int) -> list[float]:
    jax.block_until_ready(fn(*args))  # compile and warm
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return out


def _pool_minima(scores: np.ndarray, limits: list[int], pool: int, smax: int):
    """``(chunks, m)`` pool minimum carried into each chunk."""
    m = scores.shape[1]
    hist = np.zeros((m, smax + 2), np.int64)  # counts of scores 0..smax
    out = []
    for b, lim in enumerate(limits):
        at_least = np.cumsum(hist[:, ::-1], axis=1)[:, ::-1]  # (m, smax + 2)
        full = at_least >= pool
        thr = np.where(full.any(axis=1), smax + 1 - np.argmax(full[:, ::-1], axis=1), -1)
        out.append(thr)
        blk = scores[b, :, :lim]
        for v in range(smax + 1):
            hist[:, v] += (blk == v).sum(axis=1)
    return np.stack(out).astype(np.int32)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sift1m")
    ap.add_argument("--seed", type=int, default=6200000001)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    from bench import corpus
    from bench.loops.closed import kmeans_seed
    from repro.core import subspace as sub
    from repro.core.suco import (
        EnginePolicy, SuCoConfig, SuCoEngine, _pool_size, suco_cell_ranks,
    )
    from repro.core.tuning import autotune_tiles
    from repro.kernels.sc_score import kernel as K
    from repro.kernels.sc_score.ops import _pad_cells_inputs

    with open(os.path.join("bench", "configs", f"{args.config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join("bench", "traffic", "closed64.json")) as f:
        tr = json.load(f)
    x = jax.block_until_ready(corpus.corpus(args.seed, cfg))
    q = jnp.asarray(corpus.queries(args.seed, tr["query_pool"], cfg)[: tr["max_batch"]])
    ix, search = cfg["index"], cfg["search"]
    engine = SuCoEngine.build(
        x,
        SuCoConfig(n_subspaces=ix["n_subspaces"], sqrt_k=ix["sqrt_k"],
                   kmeans_iters=ix["kmeans_iters"], seed=kmeans_seed(args.seed)),
        policy=EnginePolicy(alpha=search["alpha"], beta=search["beta"]),
    )
    index, k = engine.index, tr["k"]
    m, (n, _) = q.shape[0], x.shape
    pool = _pool_size(n, k, search["beta"])
    tiles = engine.tiles_for(m, k) or autotune_tiles(  # the chip's plan elsewhere
        n, x.shape[1], m, pool, n_subspaces=ix["n_subspaces"],
        n_cells=ix["sqrt_k"] ** 2, backend="tpu")
    bc = min(tiles.block_n, n)
    cap = min(tiles.survivor_cap, bc)
    nb = -(-n // bc)
    ranks, cuts = suco_cell_ranks(index, q, sub.collision_count(n, search["alpha"]))
    cells = jnp.pad(index.cell_ids, ((0, 0), (0, nb * bc - n)))
    cells = cells.reshape(cells.shape[0], nb, bc).transpose(1, 0, 2)  # (nb, Ns, bc)
    act, cellp, bm, bn = _pad_cells_inputs(ranks, cuts, cells[0], bm=tiles.bm, bn=tiles.bn)
    cells = jnp.pad(cells, ((0, 0), (0, 0), (0, cellp.shape[1] - bc)))  # bn divides it
    limits = [min(n - b * bc, bc) for b in range(nb)]
    kw = dict(bm=bm, bn=bn, interpret=jax.default_backend() != "tpu")
    lim = jnp.asarray(limits, jnp.int32).reshape(nb, 1, 1)

    @jax.jit
    def all_scores(act, cells):
        return jax.lax.map(
            lambda ce: K.sc_score_cells_kernel(act, ce, **kw), cells)

    scores = np.asarray(all_scores(act, cells))  # (nb, m, bc)
    thrs = jnp.asarray(_pool_minima(scores, limits, pool, ix["n_subspaces"]))[:, :, None]

    def scan(kernel_step):
        @jax.jit
        def run(act, thrs, lim, cells):
            def step(acc, inp):
                return acc + kernel_step(act, *inp), None
            return jax.lax.scan(step, jnp.int32(0), (thrs, lim, cells))[0]
        return run

    stages = {
        "cells": scan(lambda a, t, l, ce: K.sc_score_cells_kernel(a, ce, **kw)[0, 0]),
        "prefilter": scan(lambda a, t, l, ce: sum(
            o[0, 0] for o in K.sc_score_cells_prefilter_kernel(a, t, ce, **kw))),
        "compact": scan(lambda a, t, l, ce: sum(
            o[0, 0] for o in K.sc_score_cells_prefilter_compact_kernel(
                a, t, l, ce, cap=-(-cap // 128) * 128, **kw))),
    }
    result = {"config": args.config, "seed": args.seed, "chunks": nb,
              "tiles": dict(block_n=bc, bm=bm, bn=bn, cap=cap),
              "device": jax.devices()[0].device_kind}
    for name, fn in stages.items():
        t = _timed(fn, act, thrs, lim, cells, reps=args.reps)
        result[f"{name}_ms_per_chunk"] = 1e3 * min(t) / nb
        result[f"{name}_ms_per_chunk_all"] = [1e3 * v / nb for v in t]
    result["compaction_ms_per_chunk"] = (
        result["compact_ms_per_chunk"] - result["prefilter_ms_per_chunk"])
    outs = jax.eval_shape(lambda: K.sc_score_cells_prefilter_compact_kernel(
        act, thrs[0], lim[0], cells[0], cap=-(-cap // 128) * 128, **kw))
    if len(outs) > 4:  # the kernel counts its windows: the pool minima
        # above are the scan's own iff this equals the query's count
        result["split_slot_windows"] = int(scan(lambda a, t, l, ce: jnp.sum(
            K.sc_score_cells_prefilter_compact_kernel(
                a, t, l, ce, cap=-(-cap // 128) * 128, **kw)[4]))(act, thrs, lim, cells))

    def query(q):
        return engine.query_with_stats(q, k)

    t = _timed(query, q, reps=args.reps)
    res, stats = query(q)
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    result["query_batch_ms"] = [1e3 * v for v in t]
    result["answers_sha256"] = hashlib.sha256(ids.tobytes() + dists.tobytes()).hexdigest()[:16]
    result["stats"] = {
        f: int(getattr(stats, f)) for f in (
            "overflow_chunks", "overflow_rows", "rerank_rows", "rerank_live",
            "slot_windows", "slot_writes") if hasattr(stats, f)}  # None: dense
    print(json.dumps(result))


if __name__ == "__main__":
    main()
