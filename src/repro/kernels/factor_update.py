"""Pallas TPU kernel: streaming factor-model micro-batch update.

One kernel applies a bucket of events to the rows they touch, in event
order, for every worker of the grid (grid axis = worker). The worker's
tables stay in HBM; around the kernel, ``ops.factor_update`` gathers the
touched rows into compact blocks (one row per distinct slot — events
that share a slot share the row, so their updates chain exactly as in
the sequential reference) and scatters them back in place afterwards.
So the per-call traffic is O(touched rows), never the whole state.

Per event the kernel reads scalar-prefetched compact indices and flags
(``bookkeeping.plan_bucket``: validity, collision eviction of the user
and item slot, the BPR negative's precondition) and does the gather,
(pairwise) SGD step and rated-row maintenance:

  * plain ISGD (DISGD, Alg. 2): ``err = 1 - u.i`` rank-1 update;
  * pairwise BPR: sampled-negative step on ``ln sigmoid(x_ui - x_uj)``,
    skipped unless the negative slot is usable, which includes "not in
    the user's live rated row" — read here from the compact row.

An evicted item's rated column is cleared in the compact rows here and
in the rest of the table by ``ops.factor_update`` (one column scatter
per eviction). Semantics replicate ``ref.factor_apply`` exactly; parity
is pinned by ``tests/test_kernel_parity.py`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bookkeeping import NEG_OK, NEW_I, NEW_U, VALID

__all__ = ["factor_update_kernel", "factor_update_pallas"]


def factor_update_kernel(ua_ref, ia_ref, ja_ref, is_ref, js_ref, fl_ref,
                         iu_ref, ii_ref, uc_in, ic_in, rc_in,
                         uc, ic, rc, *, eta: float, lam: float,
                         pairwise: bool):
    w = pl.program_id(0)
    uc[...] = uc_in[...]
    ic[...] = ic_in[...]
    rc[...] = rc_in[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, rc.shape[1]), 1)

    def event(e, carry):
        fl = fl_ref[w, e]

        @pl.when((fl & VALID) != 0)
        def _():
            a = ua_ref[w, e]
            ia = ia_ref[w, e]
            s = is_ref[w, e]
            new_u = (fl & NEW_U) != 0
            new_i = (fl & NEW_I) != 0

            # Eviction of the item slot clears its rated column in every
            # compact row before the user's row is read.
            @pl.when(new_i)
            def _():
                rc[...] = jnp.where(lanes == s, 0.0, rc[...])

            row = rc[pl.ds(a, 1), :]
            row = jnp.where(new_u, 0.0, row)
            u_vec = jnp.where(new_u, iu_ref[pl.ds(e, 1), :],
                              uc[pl.ds(a, 1), :])
            i_vec = jnp.where(new_i, ii_ref[pl.ds(e, 1), :],
                              ic[pl.ds(ia, 1), :])
            if pairwise:
                ja = ja_ref[w, e]
                js = js_ref[w, e]
                row_j = jnp.max(jnp.where(lanes == js, row, 0.0))
                neg_ok = ((fl & NEG_OK) != 0) & (row_j == 0.0)
                j_vec = ic[pl.ds(ja, 1), :]
                x = jnp.sum(u_vec * i_vec) - jnp.sum(u_vec * j_vec)
                sg = jax.nn.sigmoid(-x)
                u_new = jnp.where(
                    neg_ok, u_vec + eta * (sg * (i_vec - j_vec) - lam * u_vec),
                    u_vec)
                i_new = jnp.where(
                    neg_ok, i_vec + eta * (sg * u_vec - lam * i_vec), i_vec)

                # A usable negative never shares the positive's slot.
                @pl.when(neg_ok)
                def _():
                    ic[pl.ds(ja, 1), :] = (
                        j_vec + eta * (-sg * u_vec - lam * j_vec))
            else:
                err = 1.0 - jnp.sum(u_vec * i_vec)
                u_new = u_vec + eta * (err * i_vec - lam * u_vec)
                i_new = i_vec + eta * (err * u_vec - lam * i_vec)
            uc[pl.ds(a, 1), :] = u_new
            ic[pl.ds(ia, 1), :] = i_new
            rc[pl.ds(a, 1), :] = jnp.where(lanes == s, 1.0, row)

        return carry

    jax.lax.fori_loop(0, fl_ref.shape[1], event, 0)


def _vmem_limit(*blocks) -> int:
    # Each block is double-buffered on the way in and out.
    need = 4 * sum(b.size * b.dtype.itemsize for b in blocks)
    return int(min(max(32 * 2**20, 2 * need), 100 * 2**20))


@functools.partial(
    jax.jit, static_argnames=("eta", "lam", "pairwise", "interpret"))
def factor_update_pallas(ua, ia, ja, i_slot, j_slot, flags, init_u, init_i,
                         u_rows, i_rows, rated_rows, *, eta: float,
                         lam: float, pairwise: bool, interpret=False):
    """Apply one bucket per worker to compact touched-row blocks.

    All arrays carry a leading worker axis ``W``:

      ua, ia, ja, i_slot, j_slot, flags: i32[W, E] scalar-prefetched
        per-event compact user row, compact item rows of the positive
        and of the negative, item slot and negative slot (rated-row
        lanes), and ``bookkeeping`` flag bits;
      init_u / init_i: f32[W, E, k] replica-consistent init vectors;
      u_rows / i_rows: f32[W, Ru, k] / f32[W, Ri, k] compact factor rows;
      rated_rows: f32[W, Ru, I] compact rated rows (0/1).

    Returns the updated ``(u_rows, i_rows, rated_rows)``, aliased to the
    inputs.
    """
    w = u_rows.shape[0]
    kernel = functools.partial(factor_update_kernel, eta=eta, lam=lam,
                               pairwise=pairwise)

    def blk(x):
        return pl.BlockSpec((None,) + x.shape[1:],
                            lambda wi, *_: (wi,) + (0,) * (x.ndim - 1))

    ins = [init_u, init_i, u_rows, i_rows, rated_rows]
    outs = [u_rows, i_rows, rated_rows]
    scalars = (ua, ia, ja, i_slot, j_slot, flags)
    n_s = len(scalars)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_s, grid=(w,),
            in_specs=[blk(x) for x in ins],
            out_specs=[blk(x) for x in outs]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in outs],
        input_output_aliases={n_s + 2: 0, n_s + 3: 1, n_s + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(*ins)),
        interpret=interpret,
        name="factor_update",
    )(*scalars, *ins)
