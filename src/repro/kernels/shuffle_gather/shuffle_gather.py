"""Pallas kernel: permutation row-gather (secure-shuffle apply).

out[r, :] = table[perm[r], :] for an (N, C) table. Each secure-shuffle hop
applies one permutation to every column of the table, three hops per
shuffle — the Resizer's dominant data movement (Table 1: O(N*M) bytes).

TPU adaptation (vs. the CPU pointer-chase in MP-SPDZ): the permutation rides
in scalar-prefetch SMEM (``PrefetchScalarGridSpec``) and is read one scalar
at a time; the source table is staged whole into VMEM, single-buffered (its
block index never changes), and every output row is one dynamic-sublane
row copy ``x_ref[pl.ds(perm[r], 1)]``. Mosaic loads only scalars from SMEM
and has no vector gather across sublanes, so the row loop is the form the
TPU compiler accepts. Output rows are blocked at ``BLOCK_ROWS``. Tables
whose VMEM image exceeds ``ops.VMEM_TABLE_BYTES`` go to the XLA gather.
"""
from __future__ import annotations

import functools

import jax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
UNROLL = 8  # row copies per loop iteration (Mosaic unrolls only fully)
VMEM_LIMIT_BYTES = 48 * 2**20  # scoped VMEM: table cap + output blocks


def _gather_kernel(perm_ref, x_ref, o_ref, *, block_rows: int):
    base = pl.program_id(0) * block_rows

    def copy_rows(g, carry):
        for u in range(UNROLL):
            r = g * UNROLL + u
            o_ref[pl.ds(r, 1), :] = x_ref[pl.ds(perm_ref[base + r], 1), :]
        return carry

    lax.fori_loop(0, block_rows // UNROLL, copy_rows, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def shuffle_gather(
    table: jax.Array,  # (N, C)
    perm: jax.Array,  # (N,) int32
    interpret: bool = True,
    block_rows: int = BLOCK_ROWS,
) -> jax.Array:
    """N % block_rows == 0 and block_rows % UNROLL == 0 (wrapper pads)."""
    n, c = table.shape
    return pl.pallas_call(
        functools.partial(_gather_kernel, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block_rows,),
            in_specs=[
                pl.BlockSpec(  # whole table, fetched once
                    (n, c), lambda i, *_: (0, 0), pipeline_mode=pl.Buffered(1)
                )
            ],
            out_specs=pl.BlockSpec((block_rows, c), lambda i, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, c), table.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(perm, table)
