"""jit'd wrapper for the fused stage swap (pads lanes to the block size)."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode, record_launch
from .bitonic_stage import BLOCK, bitonic_swap
from .ref import bitonic_swap_ref


def stage_swap(mask, own, other, alpha, use_kernel: bool = True, block: int = BLOCK):
    """mask: (3, N); own/other/alpha: (3, C, N). Returns own ^ select-diff."""
    if not use_kernel or own.size == 0:
        return bitonic_swap_ref(mask, own, other, alpha)
    interpret = interpret_mode(own.dtype)
    record_launch("bitonic_stage")
    n = own.shape[2]
    block = min(block, max(128, 1 << (n - 1).bit_length()))
    pad = (-n) % block
    if pad:
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        padc = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad)))
        own_p, other_p, alpha_p = padc(own), padc(other), padc(alpha)
    else:
        own_p, other_p, alpha_p = own, other, alpha
    out = bitonic_swap(
        mask, own_p, other_p, alpha_p, interpret=interpret, block=block
    )
    return out[:, :, :n]
