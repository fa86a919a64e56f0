"""Pallas TPU kernels: fused score -> mask -> partial top-N serve leaves.

The serving plane's leaf op used to be two dispatches: a full [B, I]
masked-scoring matmul (``kernels/scoring.py``) materialized to HBM, then
a host-side ``ops.topn_select`` lexsort over all I candidates. These
kernels fuse the pipeline: scores are produced tile-by-tile in VMEM and
merged straight into a [B, top_n] running list, so the [B, I] score
matrix never exists and the sort cost drops from O(I log I) to
O(top_n * I) selection work fused into the matmul pass.

Both kernels preserve the EXACT ``topn_select`` contract — ordering is
(score desc, global id asc on ties), including the convention that
non-candidate entries keep their real ids (empty slots surface as id -1
at -inf, exactly as the unfused path emits them) — so the grid-merge
invariance tests keep pinning one deterministic list.

  * ``fused_topn_pallas``   — factor-model leaf (DISGD / BPR-MF):
    grid (B-tiles, I-tiles), dot_general f32 tile matmul + mask, merge.
  * ``dics_topn_pallas``    — DICS Eq. 6/7 leaf: grid (query blocks,
    cand-tiles); each tile builds its slice of the similarity matrix
    from the co / item_cnt statistics once for a block of 8 queries,
    restricts neighborhoods to each query's rated history, takes the
    top-k_nn neighbor mass, then merges.

Merging is exact: a running top-N merged with each tile's candidates
equals the top-N of the union, because every selection keeps the N
lexicographically-first (score desc, id asc) survivors and consumed /
seed / padding entries are (-inf, INT32_MAX) — strictly after any real
entry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_topn_pallas", "dics_topn_pallas", "QUERY_BLOCK"]

_I32_MAX = jnp.iinfo(jnp.int32).max
# DICS leaf queries per grid step: one sublane tile, the smallest legal
# block for the per-query rated rows.
QUERY_BLOCK = 8


def _merge_topn(run_sc, run_id, cand_sc, cand_id, top_n: int):
    """Merge candidates into a running top-N list, ``topn_select`` order.

    All inputs/outputs are 2-D ([rows, width]); returns ([rows, top_n])
    pairs. Selection per step: max score, then min id among score ties,
    then consume the first position holding that (score, id) pair — so
    duplicated pairs (e.g. several empty slots at (-inf, -1)) are each
    picked once, matching a lexsort over positions.
    """
    sc = jnp.concatenate([run_sc, cand_sc], axis=1)
    ids = jnp.concatenate([run_id, cand_id], axis=1)
    width = sc.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    out_sc, out_id = [], []
    for _ in range(top_n):
        m = jnp.max(sc, axis=1, keepdims=True)
        tie = sc == m
        mid = jnp.min(jnp.where(tie, ids, _I32_MAX), axis=1, keepdims=True)
        pos = tie & (ids == mid)
        first = jnp.min(jnp.where(pos, iota, width), axis=1, keepdims=True)
        hit = iota == first
        out_sc.append(m[:, 0])
        out_id.append(mid[:, 0])
        sc = jnp.where(hit, -jnp.inf, sc)
        ids = jnp.where(hit, _I32_MAX, ids)
    return jnp.stack(out_sc, axis=1), jnp.stack(out_id, axis=1)


def _fused_topn_kernel(u_ref, it_ref, m_ref, id_ref, o_id, o_sc,
                       run_sc, run_id, *, top_n: int, n_i_tiles: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        run_sc[...] = jnp.full(run_sc.shape, -jnp.inf, run_sc.dtype)
        run_id[...] = jnp.full(run_id.shape, _I32_MAX, run_id.dtype)

    scores = jax.lax.dot_general(
        u_ref[...], it_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scores = jnp.where(m_ref[...] != 0, scores, -jnp.inf)
    ids = jnp.broadcast_to(id_ref[...], scores.shape)
    new_sc, new_id = _merge_topn(run_sc[...], run_id[...], scores, ids, top_n)
    run_sc[...] = new_sc
    run_id[...] = new_id

    @pl.when(ci == n_i_tiles - 1)
    def _flush():
        o_sc[...] = run_sc[...]
        o_id[...] = run_id[...]


@functools.partial(
    jax.jit, static_argnames=("top_n", "block_b", "block_i", "interpret"))
def fused_topn_pallas(u_vecs, item_vecs, mask_i8, ids_row, *, top_n: int,
                      block_b: int = 128, block_i: int = 512,
                      interpret: bool = False):
    """Factor-model serve leaf: score + mask + partial top-N, one kernel.

    Args:
      u_vecs: f32[B, k] query vectors (B % block_b == 0, k % 128 == 0).
      item_vecs: f32[I, k] item table (I % block_i == 0).
      mask_i8: i8[B, I] nonzero where the item is a candidate.
      ids_row: i32[1, I] global item ids (padding entries INT32_MAX).

    Returns (ids i32[B, top_n], scores f32[B, top_n]) in serving order.
    """
    b, k = u_vecs.shape
    i = item_vecs.shape[0]
    n_i_tiles = i // block_i
    kernel = functools.partial(
        _fused_topn_kernel, top_n=top_n, n_i_tiles=n_i_tiles)
    out_id, out_sc = pl.pallas_call(
        kernel,
        grid=(b // block_b, n_i_tiles),
        in_specs=[
            pl.BlockSpec((block_b, k), lambda bi, ci: (bi, 0)),
            pl.BlockSpec((block_i, k), lambda bi, ci: (ci, 0)),
            pl.BlockSpec((block_b, block_i), lambda bi, ci: (bi, ci)),
            pl.BlockSpec((1, block_i), lambda bi, ci: (0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, top_n), lambda bi, ci: (bi, 0)),
            pl.BlockSpec((block_b, top_n), lambda bi, ci: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, top_n), jnp.int32),
            jax.ShapeDtypeStruct((b, top_n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, top_n), jnp.float32),
            pltpu.VMEM((block_b, top_n), jnp.int32),
        ],
        interpret=interpret,
    )(u_vecs, item_vecs, mask_i8, ids_row)
    return out_id, out_sc


def _dics_topn_kernel(co_ref, cnt_t_ref, cnt_all_ref, hist_ref, hist_t_ref,
                      known_ref, ids_t_ref, o_id, o_sc, run_sc, run_id, *,
                      top_n: int, k_nn: int, block_p: int, n_p_tiles: int):
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        run_sc[...] = jnp.full(run_sc.shape, -jnp.inf, run_sc.dtype)
        run_id[...] = jnp.full(run_id.shape, _I32_MAX, run_id.dtype)

    co_t = co_ref[...]                       # [block_p, I] candidate rows
    cnt_p = cnt_t_ref[...]                   # [1, block_p]
    cnt_all = cnt_all_ref[...]               # [1, I]
    width = co_t.shape[1]

    # Eq. 6 slice, shared by the query block: sim(p, q) = co / sqrt(cnt_p
    # * cnt_q), 0 where unsupported, and an item is not its own neighbor
    # (diagonal zero — here: global column index == global candidate
    # index).
    denom = jnp.sqrt(cnt_p.reshape(-1, 1) * cnt_all)
    sim = jnp.where(denom > 0, co_t / jnp.maximum(denom, 1e-12), 0.0)
    cols = jax.lax.broadcasted_iota(jnp.int32, sim.shape, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, sim.shape, 0)
    sim = jnp.where(cols == pi * block_p + rows, 0.0, sim)
    hist_all = hist_ref[...].astype(jnp.float32)       # [block_q, I]
    hist_t = hist_t_ref[...].astype(jnp.float32)       # [block_q, block_p]
    known = known_ref[...].astype(jnp.float32)         # [block_q, 1]
    live = ids_t_ref[...] >= 0                         # [1, block_p]

    def pick(x, q):
        """Row ``q`` of a [block_q, n] value, as [1, n]."""
        rows_q = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        return jnp.sum(jnp.where(rows_q == q, x, 0.0), axis=0, keepdims=True)

    def query(q, carry):
        # Eq. 7: neighborhoods restricted to the user's rated history,
        # then top-k_nn neighbor mass per candidate. Iterative
        # max-extract == top_k sum: sims are >= 0, consumed slots go to
        # -1 and are never re-picked while any unconsumed entry remains.
        vals = jnp.where(pick(hist_all, q) != 0, sim, 0.0)
        acc = jnp.zeros((vals.shape[0],), jnp.float32)
        for _ in range(k_nn):
            m = jnp.max(vals, axis=1, keepdims=True)
            first = jnp.min(jnp.where(vals == m, cols, width), axis=1,
                            keepdims=True)
            acc = acc + m[:, 0]
            vals = jnp.where(cols == first, -1.0, vals)

        # Candidate rule, matching dics_partial_topn: live slot, unrated
        # by this user, known user, strictly positive neighbor mass.
        valid = (live[0] & (pick(hist_t, q)[0] == 0)
                 & (pick(known, q)[0, 0] != 0) & (acc > 0))
        scores = jnp.where(valid, acc, -jnp.inf).reshape(1, -1)
        new_sc, new_id = _merge_topn(
            run_sc[pl.ds(q, 1), :], run_id[pl.ds(q, 1), :], scores,
            ids_t_ref[...], top_n)
        run_sc[pl.ds(q, 1), :] = new_sc
        run_id[pl.ds(q, 1), :] = new_id
        return carry

    jax.lax.fori_loop(0, hist_all.shape[0], query, 0)

    @pl.when(pi == n_p_tiles - 1)
    def _flush():
        o_sc[...] = run_sc[...]
        o_id[...] = run_id[...]


@functools.partial(
    jax.jit, static_argnames=("top_n", "k_nn", "block_p", "interpret"))
def dics_topn_pallas(co, item_cnt_row, hist_i8, known_i32, ids_row, *,
                     top_n: int, k_nn: int, block_p: int = 128,
                     interpret: bool = False):
    """DICS serve leaf: Eq. 6/7 scoring + partial top-N, one kernel.

    Each grid step scores one candidate tile for a sublane-aligned block
    of ``QUERY_BLOCK`` queries, so the similarity tile is built once per
    block instead of once per query.

    Args:
      co: f32[I, I] co-rating counts (I % block_p == 0, I % 128 == 0).
      item_cnt_row: f32[1, I] item support counts.
      hist_i8: i8[B, I] per-query rated rows (already known-masked;
        B % QUERY_BLOCK == 0).
      known_i32: i32[B, 1] 1 where the query user is known.
      ids_row: i32[1, I] global item ids (padding entries -1).

    Returns (ids i32[B, top_n], scores f32[B, top_n]) in serving order.
    """
    b = hist_i8.shape[0]
    i = co.shape[0]
    n_p_tiles = i // block_p
    block_q = QUERY_BLOCK
    kernel = functools.partial(
        _dics_topn_kernel, top_n=top_n, k_nn=k_nn, block_p=block_p,
        n_p_tiles=n_p_tiles)
    out_id, out_sc = pl.pallas_call(
        kernel,
        grid=(b // block_q, n_p_tiles),
        in_specs=[
            pl.BlockSpec((block_p, i), lambda bi, pi: (pi, 0)),
            pl.BlockSpec((1, block_p), lambda bi, pi: (0, pi)),
            pl.BlockSpec((1, i), lambda bi, pi: (0, 0)),
            pl.BlockSpec((block_q, i), lambda bi, pi: (bi, 0)),
            pl.BlockSpec((block_q, block_p), lambda bi, pi: (bi, pi)),
            pl.BlockSpec((block_q, 1), lambda bi, pi: (bi, 0)),
            pl.BlockSpec((1, block_p), lambda bi, pi: (0, pi)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, top_n), lambda bi, pi: (bi, 0)),
            pl.BlockSpec((block_q, top_n), lambda bi, pi: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, top_n), jnp.int32),
            jax.ShapeDtypeStruct((b, top_n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, top_n), jnp.float32),
            pltpu.VMEM((block_q, top_n), jnp.int32),
        ],
        # The [block_p, I] similarity tile and its masked copies.
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=int(
            min(max(32 * 2**20, 12 * block_p * i * 4), 100 * 2**20))),
        interpret=interpret,
    )(co, item_cnt_row, item_cnt_row, hist_i8, hist_i8, known_i32, ids_row)
    return out_id, out_sc
