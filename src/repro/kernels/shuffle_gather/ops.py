"""jit'd wrapper: pads rows to the block size (identity-mapping pad indices so
padded rows gather from themselves) and routes tables whose VMEM image is
too large for the whole-table stage to the XLA gather (launch kind
``shuffle_gather_xla``, so a run shows which route it took)."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode, record_launch
from .ref import shuffle_gather_ref
from .shuffle_gather import BLOCK_ROWS, UNROLL, shuffle_gather

# cap on the table's VMEM image: rows padded to the 8-sublane tile, columns
# to the 128-lane tile (an (N, 1) column costs N * 512 bytes of VMEM)
VMEM_TABLE_BYTES = 32 * 2**20


def vmem_table_bytes(n: int, c: int, itemsize: int) -> int:
    return -(-n // 8) * 8 * (-(-c // 128) * 128) * itemsize


def gather_rows(table, perm, use_kernel: bool = True, block_rows: int = BLOCK_ROWS):
    """table: (N, C); perm: (N,) int32. Returns table[perm]."""
    n, c = table.shape
    if not use_kernel or table.size == 0:
        return shuffle_gather_ref(table, perm)
    block_rows = min(block_rows, max(UNROLL, 1 << (n - 1).bit_length()))
    n_pad = -(-n // block_rows) * block_rows
    if vmem_table_bytes(n_pad, c, table.dtype.itemsize) > VMEM_TABLE_BYTES:
        record_launch("shuffle_gather_xla")
        return shuffle_gather_ref(table, perm)
    interpret = interpret_mode(table.dtype)
    record_launch("shuffle_gather")
    pad = n_pad - n
    if pad:
        table_p = jnp.pad(table, ((0, pad), (0, 0)))
        perm_p = jnp.concatenate(
            [perm.astype(jnp.int32), jnp.arange(n, n + pad, dtype=jnp.int32)]
        )
    else:
        table_p, perm_p = table, perm.astype(jnp.int32)
    out = shuffle_gather(
        table_p, perm_p, interpret=interpret, block_rows=block_rows
    )
    return out[:n]
