"""Pallas TPU kernel: streaming DICS co-occurrence micro-batch update.

Sibling of ``factor_update.py`` for the neighborhood model: per event it
adds the user's rating history into the co-rating matrix's row and
column of the event's item (Eq. 6 numerator statistics) and maintains
the user's rated row. The grid axis is the worker.

``co`` stays in HBM (``memory_space=ANY``, aliased in place): per event
the kernel DMAs the 8-row tile holding the item's row and the 128-lane
tile holding its column, updates them in VMEM and writes them back —
O(8·I + 128·I) bytes per event however large the worker's split is.
The user rated rows arrive as compact blocks gathered around the kernel
(``ops.dics_update``), as in the factor kernel.

Two reference quirks are replicated deliberately (see
``ref.dics_apply``):

  * collision-eviction clears run UNGUARDED — the reference's
    ``lax.cond`` fires on padding events too, so a padded ``u_id = -1``
    whose derived slot aliases a live row can clear its state; and
  * the diagonal ``co[i, i]`` is double-counted (row add then column
    add both touch it), matching the reference scatter pair.

Parity against the oracle is pinned by ``tests/test_kernel_parity.py``
in TPU interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bookkeeping import NEW_I, NEW_U, VALID

__all__ = ["dics_update_kernel", "dics_update_pallas", "col_chunk"]


def col_chunk(i_cap: int) -> int:
    """Rows per DMA of a 128-lane column tile (bounded VMEM, divides I)."""
    best = 8
    for c in range(8, min(i_cap, 4096) + 1, 8):
        if i_cap % c == 0:
            best = c
    return best


def dics_update_kernel(ua_ref, is_ref, fl_ref, rc_in, co_in, rc, co,
                       row_buf, col_buf, add_buf, sem, *, chunk: int):
    w = pl.program_id(0)
    i_cap = co.shape[1]
    rc[...] = rc_in[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, i_cap), 1)

    def event(e, carry):
        fl = fl_ref[w, e]
        a = ua_ref[w, e]
        s = is_ref[w, e]
        valid = (fl & VALID) != 0
        new_u = (fl & NEW_U) != 0
        new_i = (fl & NEW_I) != 0

        # Eviction clears of the rated rows (unguarded, reference order).
        @pl.when(new_u)
        def _():
            rc[pl.ds(a, 1), :] = jnp.zeros((1, i_cap), rc.dtype)

        @pl.when(new_i)
        def _():
            rc[...] = jnp.where(lanes == s, 0.0, rc[...])

        hist = rc[pl.ds(a, 1), :]          # read after clears, before set

        @pl.when(valid)
        def _():
            rc[pl.ds(a, 1), :] = jnp.where(lanes == s, 1.0, hist)

        @pl.when(valid | new_i)
        def _():
            add = jnp.where(valid, hist, 0.0)
            # Row s: clear on eviction, then add the history.
            r8 = pl.multiple_of((s // 8) * 8, 8)
            cp = pltpu.make_async_copy(
                co.at[w, pl.ds(r8, 8), :], row_buf, sem)
            cp.start()
            cp.wait()
            rows = jax.lax.broadcasted_iota(jnp.int32, row_buf.shape, 0)
            tile = row_buf[...]
            mine = rows == s - r8
            tile = jnp.where(mine & new_i, 0.0, tile)
            row_buf[...] = jnp.where(mine, tile + add, tile)
            cp = pltpu.make_async_copy(
                row_buf, co.at[w, pl.ds(r8, 8), :], sem)
            cp.start()
            cp.wait()

            # Column s: the history laid out along sublanes in lane
            # (s - lb) of a 128-lane tile, via a transposed one-hot
            # product (exact: every operand is 0/1).
            lb = pl.multiple_of((s // 128) * 128, 128)
            hist8 = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (8, i_cap), 0) == 0,
                add, 0.0)
            lane_hot = jnp.where(
                (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) == 0)
                & (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
                   == s - lb), 1.0, 0.0)
            add_buf[...] = jax.lax.dot_general(
                hist8, lane_hot, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            col_lane = jax.lax.broadcasted_iota(
                jnp.int32, col_buf.shape, 1) == s - lb

            def chunk_body(c, carry):
                c0 = pl.multiple_of(c * chunk, chunk)
                src = co.at[w, pl.ds(c0, chunk), pl.ds(lb, 128)]
                cp = pltpu.make_async_copy(src, col_buf, sem)
                cp.start()
                cp.wait()
                rows = c0 + jax.lax.broadcasted_iota(
                    jnp.int32, col_buf.shape, 0)
                # Row s was cleared above, so the column clear skips it
                # and the diagonal keeps the row's add (double count).
                t = col_buf[...]
                t = jnp.where(col_lane & new_i & (rows != s), 0.0, t)
                col_buf[...] = t + add_buf[pl.ds(c0, chunk), :]
                cp = pltpu.make_async_copy(col_buf, src, sem)
                cp.start()
                cp.wait()
                return carry

            jax.lax.fori_loop(0, i_cap // chunk, chunk_body, 0)

        return carry

    jax.lax.fori_loop(0, fl_ref.shape[1], event, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dics_update_pallas(ua, i_slot, flags, rated_rows, co, *,
                       interpret=False):
    """Apply one bucket per worker: ``co`` in place, compact rated rows.

    Leading worker axis ``W`` everywhere: ``ua, i_slot, flags``
    i32[W, E] (scalar-prefetched compact user row, item slot,
    ``bookkeeping`` flag bits); ``rated_rows`` f32[W, Ru, I] (0/1); ``co``
    f32[W, I, I] with ``I % 128 == 0``. Returns ``(rated_rows, co)``,
    aliased to the inputs.
    """
    w, _, i_cap = co.shape
    assert i_cap % 128 == 0, i_cap
    chunk = col_chunk(i_cap)
    rows_spec = pl.BlockSpec((None,) + rated_rows.shape[1:],
                             lambda wi, *_: (wi, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scalars = (ua, i_slot, flags)
    n_s = len(scalars)
    vmem = (4 * rated_rows[0].size * 4 + 4 * (8 * i_cap + chunk * 128)
            + 4 * i_cap * 128)
    return pl.pallas_call(
        functools.partial(dics_update_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_s, grid=(w,),
            in_specs=[rows_spec, any_spec],
            out_specs=[rows_spec, any_spec],
            scratch_shapes=[
                pltpu.VMEM((8, i_cap), jnp.float32),
                pltpu.VMEM((chunk, 128), jnp.float32),
                pltpu.VMEM((i_cap, 128), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=[jax.ShapeDtypeStruct(rated_rows.shape, rated_rows.dtype),
                   jax.ShapeDtypeStruct(co.shape, co.dtype)],
        input_output_aliases={n_s: 0, n_s + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(max(32 * 2**20, 2 * vmem),
                                     100 * 2**20))),
        interpret=interpret,
        name="dics_update",
    )(*scalars, rated_rows, co)
