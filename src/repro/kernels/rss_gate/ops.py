"""jit'd public wrapper for rss_gate: pads lanes to the block size, flattens
arbitrary trailing shapes, and dispatches to the kernel (compiled on TPU,
interpreted on CPU; see ``repro.kernels.interpret_mode``) or, with
``use_kernel=False``, the jnp reference."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode, record_launch
from .ref import rss_gate_ref
from .rss_gate import BLOCK, rss_gate


def gate(xs, ys, alpha, boolean: bool = True, use_kernel: bool = True, block: int = BLOCK):
    # lanes are flattened below, so broadcast-compatible operands (e.g. a
    # (3,n,2) x against a (3,n,1) y) must be materialized to a common shape
    # first or their flat lane indices misalign
    xs, ys, alpha = jnp.broadcast_arrays(xs, ys, alpha)
    if not use_kernel or xs.size == 0:  # pallas_call cannot slice 0-lane operands
        return rss_gate_ref(xs, ys, alpha, boolean)
    interpret = interpret_mode(xs.dtype)
    record_launch("rss_gate")
    shape = xs.shape
    flat = lambda a: a.reshape(3, -1)
    x, y, al = flat(xs), flat(ys), flat(alpha)
    n = x.shape[1]
    block = min(block, max(128, 1 << (n - 1).bit_length()))
    pad = (-n) % block
    if pad:
        padf = lambda a: jnp.pad(a, ((0, 0), (0, pad)))
        x, y, al = padf(x), padf(y), padf(al)
    out = rss_gate(x, y, al, boolean=boolean, interpret=interpret, block=block)
    return out[:, :n].reshape(shape)
