"""Pure-jnp oracle for the fused SC-score kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sc_score_cells_ref(
    ranks: jax.Array, cuts: jax.Array, cells: jax.Array
) -> jax.Array:
    """``ranks: (Ns,m,K), cuts: (Ns,m), cells: (Ns,bc) -> (m,bc)`` int32.

    Oracle for the chunked IMI kernel: point j collides with query q in
    subspace i iff the rank of its cell is within the activation cutoff.
    """
    g = jax.vmap(lambda r, c: jnp.take(r, c, axis=-1))(ranks, cells)  # (Ns,m,bc)
    mask = g <= cuts[:, :, None]
    return jnp.sum(mask.astype(jnp.int32), axis=0)


def sc_score_cells_prefilter_ref(
    ranks: jax.Array, cuts: jax.Array, cells: jax.Array, thr: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused score + Pareto-prefilter chunk stage.

    ``thr: (m,)`` is the per-query carried pool minimum; returns the
    chunk scores plus ``keep = scores > thr[:, None]`` — the rows that
    could possibly enter a top pool whose minimum is ``thr`` (everything
    else is pruned before the merge, exactly).
    """
    s = sc_score_cells_ref(ranks, cuts, cells)
    return s, s > thr[:, None]


def sc_score_cells_prefilter_compact_ref(
    ranks: jax.Array,
    cuts: jax.Array,
    cells: jax.Array,
    thr: jax.Array,
    limit: jax.Array,
    keep_cols: jax.Array | None = None,
    *,
    cap: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused score + prefilter + survivor-compaction stage.

    ``thr: (m,)`` is the per-query carried pool minimum and ``limit`` the
    (possibly traced) count of valid chunk columns; columns at or past it
    are masked to the -1 score sentinel and can never survive.
    ``keep_cols`` (optional, ``(bc,) bool``) further restricts the valid
    columns — the live-mutation tombstone mask: a False column is masked
    to -1 exactly like one past ``limit``, so deleted points neither
    survive nor occupy compaction slots nor count toward ``count``.
    Returns ``(scores (m, bc), surv_cols (m, cap), surv_scores (m, cap),
    count (m,))``: the j-th survivor (ascending column order, exactly the
    keep-mask compaction the fused query used to run on the host) sits at
    slot j; empty slots hold column 0 / score -1; ``count`` is the true
    survivor count and may exceed ``cap`` (the caller's overflow signal).
    The compaction is a binary search on the keep-mask's monotone cumsum —
    no sort or scatter touches the ``(m, bc)`` block.
    """
    bc = cells.shape[1]
    s = sc_score_cells_ref(ranks, cuts, cells)
    col = jnp.arange(bc, dtype=jnp.int32)
    ok = col[None, :] < limit
    if keep_cols is not None:
        ok = jnp.logical_and(ok, keep_cols[None, :])
    s = jnp.where(ok, s, -1)
    keep = s > thr[:, None]
    cnt = jnp.cumsum(keep.astype(jnp.int32), axis=1)
    slot = jnp.arange(cap, dtype=jnp.int32)
    surv = jax.vmap(lambda row: jnp.searchsorted(row, slot + 1, side="left"))(cnt)
    surv = jnp.minimum(surv, bc - 1).astype(jnp.int32)
    total = cnt[:, -1]
    live = slot[None, :] < total[:, None]
    surv_cols = jnp.where(live, surv, 0)
    surv_scores = jnp.where(live, jnp.take_along_axis(s, surv, axis=1), -1)
    return s, surv_cols, surv_scores, total


def slot_window_counts(
    keep: jax.Array, *, bn: int, cap: int
) -> tuple[jax.Array, jax.Array]:
    """The compact kernel's slot-write counters, replayed from its keep mask.

    ``keep (m, bc)`` is the prefilter's survivors mask, walked in
    ``bn``-wide column blocks against ``cap`` slots.  In block b a row
    whose count before the block is ``c0`` and after it ``c1`` writes the
    slots ``[c0, min(c1, cap))``: 128-slot windows ``c0 // 128`` through
    ``(min(c1, cap) - 1) // 128``, or none.  Returns
    ``(slot_windows (m,), slot_writes (m,))`` int32, summed over the
    blocks: the windows written and the blocks that wrote any slot.
    """
    m, bc = keep.shape
    nb = -(-bc // bn)
    k = jnp.pad(keep.astype(jnp.int32), ((0, 0), (0, nb * bn - bc)))
    per_block = jnp.sum(k.reshape(m, nb, bn), axis=2)  # (m, nb)
    c1 = jnp.cumsum(per_block, axis=1)
    c0 = c1 - per_block
    last = jnp.minimum(c1, cap) - 1
    wrote = last >= c0
    wins = jnp.where(wrote, last // 128 - c0 // 128 + 1, 0)
    return (
        jnp.sum(wins, axis=1, dtype=jnp.int32),
        jnp.sum(wrote, axis=1, dtype=jnp.int32),
    )


def sc_score_ref(qs: jax.Array, xs: jax.Array, tau: jax.Array) -> jax.Array:
    """``qs: (Ns,m,s), xs: (Ns,n,s), tau: (Ns,m) -> (m,n)`` int32 scores."""
    qf, xf = qs.astype(jnp.float32), xs.astype(jnp.float32)
    d2 = (
        jnp.sum(qf * qf, axis=-1)[:, :, None]
        + jnp.sum(xf * xf, axis=-1)[:, None, :]
        - 2.0 * jnp.einsum("ims,ins->imn", qf, xf, preferred_element_type=jnp.float32)
    )
    d2 = jnp.maximum(d2, 0.0)
    mask = d2 <= tau[:, :, None]
    return jnp.sum(mask.astype(jnp.int32), axis=0)
