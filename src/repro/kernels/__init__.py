"""Pallas TPU kernels for the MPC engine's compute hot spots.

Five kernels cover the protocol-local inner loops that dominate the engine's
arithmetic (the *communication* between parties is JAX-level and cannot live
inside a kernel — on a real 3-TPU deployment each kernel body runs per-party
between round boundaries; in this simulation the 3-share axis is local, so the
fused body is exactly the simulation hot loop):

* ``rss_gate``      — cross-term + re-randomization of the 1-round AND / mul
                      gate (every comparison circuit bottoms out here)
* ``ks_prefix``     — an entire Kogge-Stone borrow/carry prefix (all log2 k
                      levels, both independent AND pairs per level) plus the
                      equality AND-fold tree, in ONE launch instead of one
                      ``rss_gate`` launch per level
* ``a2b_fused``     — the full arithmetic->boolean conversion (two chained
                      Kogge-Stone adders, 12 gate rounds) and the fused
                      ``bit2a`` double-multiply, each in ONE launch
* ``shuffle_gather``— permutation row-gather (the secure shuffle's data move)
* ``bitonic_stage`` — fused conditional-swap of one sort stage across all
                      payload columns

Each kernel directory has ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd wrapper with padding), and ``ref.py`` (pure-jnp oracle).
Where a kernel runs is decided by :func:`interpret_mode` alone: compiled by
Mosaic on ``tpu``, the Pallas interpreter on ``cpu`` (the test suite runs
under ``JAX_PLATFORMS=cpu``), and an error on any other platform — there is
no silent fallback. The BlockSpecs are sized for TPU v5e VMEM; every kernel
is compiled for a described ``v5e:2x2`` topology in
``tests/test_tpu_compile.py``.

Switches
--------
The defaults come from :func:`repro.config.current_config` (``use_pallas`` /
``fuse_circuits``, with ``REPRO_USE_PALLAS`` / ``REPRO_FUSE_CIRCUITS`` as the
env fallback parsed in :mod:`repro.config`). ``REPRO_FUSE_CIRCUITS=0`` keeps
kernels on but forces the gate-by-gate circuit path (used by parity tests and
the fused-vs-unfused benchmark). Both can be overridden per-thread with
:func:`override_kernels` / :func:`override_fusion` so tests and benches work
without mutating the environment — the Engine uses exactly these overrides to
apply an explicit ``RuntimeConfig`` for the duration of an execution.

Launch accounting
-----------------
Every ``ops.py`` wrapper records the kernel dispatches it issues from Python
(trace-time accounting: a jit-cached re-execution of an enclosing function is
not re-counted — the engine's protocol layer runs eagerly by default, where
the count equals real dispatches). A bitonic sort's network program traces
its stage body once for all its stages, so ``core.sort`` takes that pass's
launches off the count with :func:`capture_launches` and records one stage's
launches once per stage. ``launch_counts()`` is what
``benchmarks/bench_kernels.py`` uses to demonstrate the fused-kernel launch
reduction.
"""
from __future__ import annotations

import contextlib
import threading
from collections import Counter
from typing import Dict, Iterator, Optional

import jax
import numpy as np

from repro.config import current_config

_STATE = threading.local()


def interpret_mode(dtype) -> bool:
    """``interpret=`` for a ``pallas_call`` over ``dtype`` operands on the
    current default backend: True on ``cpu`` (the Pallas interpreter), False
    on ``tpu`` (compiled by Mosaic). Any other platform raises, and so does a
    64-bit operand on ``tpu`` (the ring-64 build): Mosaic has no 64-bit
    integer vectors, so the error is raised here with its reason instead of
    deep inside the compiler."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform != "tpu":
        raise RuntimeError(
            f"Pallas kernels run compiled on tpu or interpreted on cpu; the "
            f"default backend is {platform!r}"
        )
    if np.dtype(dtype).itemsize == 8:
        raise NotImplementedError(
            f"Pallas kernels on tpu support the 32-bit ring only, got "
            f"{np.dtype(dtype)} operands (ring-64 runs with use_pallas=False)"
        )
    return False


def kernels_enabled() -> bool:
    ov = getattr(_STATE, "kernels", None)
    return current_config().use_pallas if ov is None else ov


def fusion_enabled() -> bool:
    """True when circuits should route through the single-launch fused
    kernels (requires the kernel layer itself to be enabled)."""
    if not kernels_enabled():
        return False
    ov = getattr(_STATE, "fusion", None)
    return current_config().fuse_circuits if ov is None else ov


@contextlib.contextmanager
def override_kernels(enabled: Optional[bool]) -> Iterator[None]:
    """Thread-locally force the kernel layer on/off (None = env default)."""
    prev = getattr(_STATE, "kernels", None)
    _STATE.kernels = enabled
    try:
        yield
    finally:
        _STATE.kernels = prev


@contextlib.contextmanager
def override_fusion(enabled: Optional[bool]) -> Iterator[None]:
    """Thread-locally force circuit fusion on/off (None = env default)."""
    prev = getattr(_STATE, "fusion", None)
    _STATE.fusion = enabled
    try:
        yield
    finally:
        _STATE.fusion = prev


# -----------------------------------------------------------------------------
# Launch accounting
# -----------------------------------------------------------------------------

def _counter() -> Counter:
    if not hasattr(_STATE, "launches"):
        _STATE.launches = Counter()
    return _STATE.launches


def record_launch(kind: str, n: int = 1) -> None:
    sink = getattr(_STATE, "sink", None)
    (_counter() if sink is None else sink)[kind] += n


@contextlib.contextmanager
def capture_launches() -> Iterator[Counter]:
    """Count this thread's launches inside the block into a fresh counter,
    yielded, instead of :func:`launch_counts`."""
    prev = getattr(_STATE, "sink", None)
    _STATE.sink = Counter()
    try:
        yield _STATE.sink
    finally:
        _STATE.sink = prev


def launch_counts() -> Dict[str, int]:
    return dict(_counter())


def total_launches() -> int:
    return sum(_counter().values())


def reset_launch_counts() -> None:
    _counter().clear()
