"""Fused SC-score accumulation Pallas kernels — the paper's inner loop.

Given per-subspace query/data blocks and per-(subspace, query) collision
thresholds tau, computes

    scores[q, j] = sum_i [ ||q_i - x_ij||^2 <= tau[i, q] ]

in one pass: the distance block is formed on the MXU (norm + matmul
identity), compared against tau in VREGs, and accumulated into an int32
score tile that lives in the output across the subspace grid dimension —
the (Ns, m, n) distance tensor never touches HBM.

The chunked IMI kernels take the per-(subspace, query) activation mask
``act (Ns, m, K)`` (1.0 where the cell sits inside the query's activated
prefix) and a chunk of cell ids ``cells (Ns, bc)``.  Point j collides with
query q in subspace i iff ``act[i, q, cells[i, j]] == 1``; Mosaic has no
in-kernel lane gather, so the lookup is a contraction on the MXU: the 0/1
activation rows times a 0/1 one-hot of the chunk's cell ids, both exact in
bf16 and accumulated in f32 (exactly one term of every sum is non-zero).

Grid = (m/bm, n/bn, Ns); subspace innermost so the output tile revisits.
Every block keeps its last two dims (8, 128)-divisible or equal to the
array's own dims — the Mosaic rule the jaxlint tile-shape check enforces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Cell-id one-hot chunk width: the (KC, bn) one-hot is the collision
# lookup's largest temporary, so K is walked in KC-wide slices.
KC = 512
_LANE = 128
_SUBLANE = 8
# Mosaic's default scoped-VMEM limit (rehearsed on a described v5e: a 20 MiB
# scratch is refused without an explicit limit, 12 MiB compiles).
_SCOPED_VMEM_DEFAULT = 16 << 20


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def cells_vmem_bytes(
    n_sub: int, bm: int, bn: int, k_cells: int, cap: int = 0
) -> int:
    """Upper bound on the VMEM a chunked IMI kernel claims at these tiles
    (``cap > 0``: the compact kernel, with its survivor tiles).

    The double-buffered activation block dominates; the ``(bm, bn)`` tiles
    (score block, slot scratch, collision accumulator, prefix-count
    temporaries) and the slot one-hots add the rest, plus 2 MiB for the
    cell one-hot and Mosaic's own scratch.  Calibrated against the smallest
    ``vmem_limit_bytes`` the compact kernel compiles under for a described
    v5e at K=2560: 2, 2, 9, 7, 9, 10, 3, 14, 12 and 9 MiB at ``(Ns, bm, bn,
    cap)`` = (8, 8, 512, 256), (8, 8, 2048, 256), (8, 64, 2048, 256),
    (8, 64, 512, 256), (8, 64, 2048, 1024), (8, 64, 2048, 2048),
    (8, 8, 2048, 2048), (8, 128, 1024, 256), (16, 64, 1024, 512),
    (8, 64, 512, 3328).  The model is 1.4x to 2.4x above each of them.

    The slot term was calibrated on a slot write that built a ``(cap, bn)``
    one-hot per row; the write now builds ``(128, bn)`` windows, so the
    term overstates it.  It is kept so that :func:`_fit_kernel_vmem`'s
    tile plan (``repro.core.tuning``) stays the one it was.
    """
    act = n_sub * bm * _round_up(k_cells, _LANE) * 4
    tiles = 6 * bm * bn * 4
    slots = _round_up(cap, _LANE) * bn // 2 if cap else 0
    return 2 * act + tiles + slots + (2 << 20)


def _vmem_params(nbytes: int) -> pltpu.CompilerParams | None:
    """Raise the scoped-VMEM limit only when the model needs it."""
    if nbytes <= _SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_round_up(nbytes, 1 << 20))


def _kernel(q_ref, x_ref, tau_ref, out_ref, *, n_sub: int):
    i = pl.program_id(2)  # subspace index (innermost)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    qb = q_ref[0].astype(jnp.float32)  # (bm, s)
    xb = x_ref[0].astype(jnp.float32)  # (bn, s)
    tau = tau_ref[0].astype(jnp.float32)  # (bm, 1)
    qn = jnp.sum(qb * qb, axis=1, keepdims=True)  # (bm, 1)
    xn = jnp.sum(xb * xb, axis=1, keepdims=True).T  # (1, bn)
    cross = jax.lax.dot_general(
        qb, xb, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    d2 = jnp.maximum(qn + xn - 2.0 * cross, 0.0)  # (bm, bn)
    out_ref[...] += (d2 <= tau).astype(jnp.int32)


def _collisions(act_ref, cells_ref, i) -> jax.Array:
    """``(bm, bn)`` int32 0/1: does each chunk point collide in subspace i?

    ``act_ref[i] (bm, Kp)`` holds the queries' 0/1 activation rows and
    ``cells_ref[i] (bn,)`` the chunk's cell ids; the gather
    ``act[q, cells[j]]`` is the contraction of the activation rows with the
    cell ids' one-hot, walked over K in ``KC``-wide slices.
    """
    cells = cells_ref[pl.ds(i, 1), :]  # (1, bn)
    _, bm, kp = act_ref.shape
    bn = cells.shape[1]
    acc = jnp.zeros((bm, bn), jnp.float32)
    for c0 in range(0, kp, KC):
        kc = min(KC, kp - c0)
        rows = act_ref[i, :, c0:c0 + kc].astype(jnp.bfloat16)  # (bm, kc)
        ids = c0 + jax.lax.broadcasted_iota(jnp.int32, (kc, bn), 0)
        onehot = (cells == ids).astype(jnp.bfloat16)  # (kc, bn)
        acc = acc + jax.lax.dot(rows, onehot, preferred_element_type=jnp.float32)
    return acc.astype(jnp.int32)


def _cells_kernel(act_ref, cell_ref, out_ref):
    i = pl.program_id(2)  # subspace index (innermost)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _collisions(act_ref, cell_ref, i)


def _cells_prefilter_kernel(act_ref, thr_ref, cell_ref, score_ref, keep_ref, *, n_sub: int):
    i = pl.program_id(2)  # subspace index (innermost)

    @pl.when(i == 0)
    def _init():
        score_ref[...] = jnp.zeros_like(score_ref)
        keep_ref[...] = jnp.zeros_like(keep_ref)

    score_ref[...] += _collisions(act_ref, cell_ref, i)

    # Pareto prefilter, fused into the last subspace visit: once the score
    # tile is complete, compare it against the carried pool minimum while
    # it is still in VMEM — the survivors mask costs one VPU compare
    # instead of a second pass over the (m, bc) score block.
    @pl.when(i == n_sub - 1)
    def _prefilter():
        keep_ref[...] = (score_ref[...] > thr_ref[...]).astype(jnp.int32)


def _cells_prefilter_compact_kernel(
    act_ref, thr_ref, limit_ref, cell_ref,
    score_ref, svcol_ref, svscore_ref, cnt_ref, win_ref, wrt_ref,
    dest_ref, col_win_ref, score_win_ref,
    *, n_sub: int, bn: int, cap: int,
):
    j = pl.program_id(1)  # column-block index (sequential -> cnt accumulates)
    i = pl.program_id(2)  # subspace index (innermost)

    @pl.when((j == 0) & (i == 0))
    def _init_survivors():
        col_win_ref[...] = jnp.zeros_like(col_win_ref)
        score_win_ref[...] = jnp.full_like(score_win_ref, -1)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        win_ref[...] = jnp.zeros_like(win_ref)
        wrt_ref[...] = jnp.zeros_like(wrt_ref)

    @pl.when(i == 0)
    def _init_scores():
        score_ref[...] = jnp.zeros_like(score_ref)

    score_ref[...] += _collisions(act_ref, cell_ref, i)

    # Survivor compaction, fused into the last subspace visit: while the
    # completed score tile is resident, columns past ``limit`` are masked
    # to the -1 sentinel, the Pareto prefilter picks the survivors, and a
    # running prefix count assigns each survivor its destination slot.
    @pl.when(i == n_sub - 1)
    def _compact():
        bm = score_ref.shape[0]
        col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        s = jnp.where(col < limit_ref[...], score_ref[...], -1)
        score_ref[...] = s
        keep = s > thr_ref[...]  # (bm, bn) vs the (bm, 1) pool minima
        # Inclusive prefix count along the lanes, one 128-lane segment at
        # a time: 0/1 keep rows times an upper-triangular ones matrix on
        # the MXU (exact: segment sums <= 128), plus the running total.
        tri = (
            jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 1)
        ).astype(jnp.bfloat16)
        run = cnt_ref[...]  # (bm, 1) survivors before this block
        for g in range(0, bn, _LANE):
            kg = keep[:, g:g + _LANE]
            incl = run + jax.lax.dot(
                kg.astype(jnp.bfloat16), tri, preferred_element_type=jnp.float32
            ).astype(jnp.int32)
            dest = incl - 1  # slot of each kept column
            dest_ref[:, g:g + _LANE] = jnp.where(kg & (dest < cap), dest, -1)
            run = incl[:, _LANE - 1:]

        # Slot writes, one query row at a time, through 128-slot windows:
        # a row's survivors of this block take the consecutive slots from
        # its count before the block (still in ``cnt_ref``) to its largest
        # destination, so only the windows between them are written — one
        # or two on a warm pool, at most bn/128 + 1.  Per window a
        # (128, bn) one-hot of the row's destinations contracts its
        # (score + 1, column) values into those slots on the MXU.  Every
        # operand is an integer <= 256, exact in bf16 — the column is
        # split into its low and high bytes (block_n <= 2^16) — and each
        # slot is written at most once, so the += against the -1 / 0
        # initialisation is exact.  The windows live in ``(cap/128, bm,
        # 128)`` scratch, indexed on the leading axis: Mosaic refuses a
        # dynamic lane offset into the ``(bm, cap)`` outputs.
        # ``win_ref``/``wrt_ref`` count the windows written and the rows
        # that wrote.
        colrow = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANE, bn), 0)
        slots = jax.lax.broadcasted_iota(jnp.int32, (_LANE, bn), 0)

        def write_row(r, carry):
            d_r = dest_ref[pl.ds(r, 1), :]  # (1, bn), -1 = no write
            last = jnp.max(d_r)

            @pl.when(last >= 0)
            def _write():
                s_r = score_ref[pl.ds(r, 1), :]
                vals = jnp.where(
                    sub == 0, s_r + 1,
                    jnp.where(sub == 1, colrow & 255,
                              jnp.where(sub == 2, colrow >> 8, 0)),
                ).astype(jnp.bfloat16)  # (8, bn)
                first = jnp.max(cnt_ref[pl.ds(r, 1), :]) // _LANE
                stop = last // _LANE + 1

                def write_window(w, c):
                    onehot = (slots == d_r - w * _LANE).astype(jnp.bfloat16)
                    out = jax.lax.dot_general(
                        vals, onehot, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ).astype(jnp.int32)  # (8, 128)
                    score_win_ref[w, pl.ds(r, 1), :] += out[0:1]
                    col_win_ref[w, pl.ds(r, 1), :] += out[1:2] + 256 * out[2:3]
                    return c

                jax.lax.fori_loop(first, stop, write_window, 0)
                win_ref[pl.ds(r, 1), :] += jnp.full((1, 1), stop - first, jnp.int32)
                wrt_ref[pl.ds(r, 1), :] += 1

            return carry

        jax.lax.fori_loop(0, bm, write_row, 0)
        cnt_ref[...] = run

        @pl.when(j == pl.num_programs(1) - 1)
        def _emit_survivors():
            for w in range(cap // _LANE):
                svscore_ref[:, w * _LANE:(w + 1) * _LANE] = score_win_ref[w]
                svcol_ref[:, w * _LANE:(w + 1) * _LANE] = col_win_ref[w]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "cap", "interpret"))
def sc_score_cells_prefilter_compact_kernel(
    act: jax.Array,  # (Ns, m, Kp) f32 0/1 activation rows
    thr: jax.Array,  # (m, 1) carried pool minimum score per query
    limit: jax.Array,  # (1, 1) number of valid (non-padding) columns
    cells: jax.Array,  # (Ns, bc) cell ids of one data chunk
    *,
    bm: int = 8,
    bn: int = 512,
    cap: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Fused chunk stage with in-kernel survivor compaction.

    :func:`sc_score_cells_prefilter_kernel` taken one step further: instead
    of a keep-mask that the host graph still has to cumsum/searchsorted/
    gather, the kernel emits the compacted survivors directly — the fused
    query's score->prune stage becomes *one* kernel launch per chunk.

    Outputs (all int32):

    * ``scores (m, bc)`` — the chunk scores, columns ``>= limit`` masked
      to the -1 sentinel (the caller no longer masks padding itself);
    * ``surv_cols (m, cap)`` — chunk-local column of the j-th survivor in
      ascending-column order (0 for empty slots);
    * ``surv_scores (m, cap)`` — its score (-1 for empty slots);
    * ``count (m, 1)`` — the *true* survivor count, which may exceed
      ``cap`` (overflow slots are dropped; the caller detects
      ``count > cap`` and falls back to an exact full merge);
    * ``slot_windows (m, 1)`` — the 128-slot windows the slot write built
      a one-hot for, over the chunk's column blocks;
    * ``slot_writes (m, 1)`` — the column blocks in which the row wrote
      any slot (each would have cost a ``(cap, bn)`` one-hot whole).

    The survivor tiles revisit across the whole (column-block, subspace)
    grid sweep, so the running count threads destination slots across
    column blocks without any host round trip.  Caller pre-pads
    ``m % bm == bc % bn == 0``, ``cap % 128 == 0``, ``Kp % 128 == 0`` and
    keeps ``bc <= 2^16``.
    """
    n_sub, m, kp = act.shape
    bc = cells.shape[1]
    grid = (m // bm, bc // bn, n_sub)
    return pl.pallas_call(
        functools.partial(
            _cells_prefilter_compact_kernel, n_sub=n_sub, bn=bn, cap=cap
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_sub, bm, kp), lambda i, j, k: (0, i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((n_sub, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, cap), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, cap), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, bc), jnp.int32),
            jax.ShapeDtypeStruct((m, cap), jnp.int32),
            jax.ShapeDtypeStruct((m, cap), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((cap // _LANE, bm, _LANE), jnp.int32),
            pltpu.VMEM((cap // _LANE, bm, _LANE), jnp.int32),
        ],
        compiler_params=_vmem_params(cells_vmem_bytes(n_sub, bm, bn, kp, cap)),
        interpret=interpret,
    )(act, thr, limit, cells)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def sc_score_cells_prefilter_kernel(
    act: jax.Array,  # (Ns, m, Kp) f32 0/1 activation rows
    thr: jax.Array,  # (m, 1) carried pool minimum score per query
    cells: jax.Array,  # (Ns, bc) cell ids of one data chunk
    *,
    bm: int = 8,
    bn: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused chunk stage: SC-scores + Pareto-prefilter survivors mask.

    :func:`sc_score_cells_kernel` with one extra input (the per-query
    carried pool minimum ``thr``) and one extra output: ``keep[q, j] =
    scores[q, j] > thr[q]`` (int32 0/1), emitted on the final subspace
    grid step while the completed score tile is still resident — the
    fused streaming engine's prune decision never re-reads the scores
    from HBM.  Caller pre-pads ``m % bm == bc % bn == 0``; returns
    ``(scores (m, bc) int32, keep (m, bc) int32)``.
    """
    n_sub, m, kp = act.shape
    bc = cells.shape[1]
    grid = (m // bm, bc // bn, n_sub)
    return pl.pallas_call(
        functools.partial(_cells_prefilter_kernel, n_sub=n_sub),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_sub, bm, kp), lambda i, j, k: (0, i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((n_sub, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, bc), jnp.int32),
            jax.ShapeDtypeStruct((m, bc), jnp.int32),
        ],
        compiler_params=_vmem_params(cells_vmem_bytes(n_sub, bm, bn, kp)),
        interpret=interpret,
    )(act, thr, cells)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def sc_score_cells_kernel(
    act: jax.Array,  # (Ns, m, Kp) f32 0/1 activation rows
    cells: jax.Array,  # (Ns, bc) cell ids of one data chunk
    *,
    bm: int = 8,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Chunked IMI entry point: fused lookup-accumulate.

    The SuCo collision test (point j collides with query q in subspace i
    iff its IMI cell sits inside the activated ascending-distance prefix)
    is ``act[i, q, cells[i, j]] == 1`` — the same threshold-accumulate
    structure as :func:`sc_score_kernel` with the MXU distance block
    replaced by the MXU one-hot lookup.  Grid = (m/bm, bc/bn, Ns),
    subspace innermost so the output tile revisits; the (m, n) score
    matrix never exists — callers stream chunks of ``bc`` points and merge
    into a running top pool.

    Caller pre-pads m % bm == bc % bn == 0.  Returns (m, bc) int32.
    """
    n_sub, m, kp = act.shape
    bc = cells.shape[1]
    grid = (m // bm, bc // bn, n_sub)
    return pl.pallas_call(
        _cells_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_sub, bm, kp), lambda i, j, k: (0, i, 0)),
            pl.BlockSpec((n_sub, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, bc), jnp.int32),
        compiler_params=_vmem_params(cells_vmem_bytes(n_sub, bm, bn, kp)),
        interpret=interpret,
    )(act, cells)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def sc_score_kernel(
    qs: jax.Array,  # (Ns, m, s) per-subspace queries (zero-padded s)
    xs: jax.Array,  # (Ns, n, s) per-subspace data
    tau: jax.Array,  # (Ns, m, 1) collision thresholds
    *,
    bm: int = 8,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Caller pre-pads m % bm == n % bn == 0. Returns (m, n) int32 scores."""
    n_sub, m, s = qs.shape
    n = xs.shape[1]
    grid = (m // bm, n // bn, n_sub)
    return pl.pallas_call(
        functools.partial(_kernel, n_sub=n_sub),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, s), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((1, bn, s), lambda i, j, k: (k, j, 0)),
            pl.BlockSpec((1, bm, 1), lambda i, j, k: (k, i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(qs, xs, tau)
