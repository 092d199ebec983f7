"""jit'd wrappers for the batched wastage kernels.

Callers keep their shapes: the wrappers pad the lane axis to a multiple of
:data:`~repro.kernels.wastage.kernel.LANES` (padded lanes have length 0,
so they never violate and waste nothing) and the time axis to a multiple
of the time block, and slice the outputs back.  The kernels run compiled
unless a caller passes ``interpret=True`` (the CPU tests do).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.wastage.kernel import LANES, oom_probe_call, wastage_call

__all__ = ["wastage_eval", "oom_probe"]


def _padded(starts, peaks, mems, lengths, block_t: int):
    """Kernel operands: lanes padded to ``LANES``, T to the time block."""
    B, T = mems.shape
    bt = min(block_t, T)
    lp = (-B) % LANES
    mems = jnp.pad(jnp.asarray(mems, jnp.float32), ((0, lp), (0, (-T) % bt)))
    starts = jnp.pad(jnp.asarray(starts, jnp.float32), ((0, lp), (0, 0)))
    peaks = jnp.pad(jnp.asarray(peaks, jnp.float32), ((0, lp), (0, 0)))
    lengths = jnp.pad(jnp.asarray(lengths, jnp.int32), (0, lp))[:, None]
    return (starts, peaks, mems, lengths), bt


@functools.partial(jax.jit, static_argnames=("dt", "block_t", "interpret"))
def wastage_eval(starts, peaks, mems, lengths, dt: float = 1.0,
                 block_t: int = 512, interpret: bool = False):
    """Batched successful-attempt wastage in GB·s.

    starts/peaks: (B, k) float; mems: (B, T) float; lengths: (B,) int32.
    """
    B = mems.shape[0]
    args, bt = _padded(starts, peaks, mems, lengths, block_t)
    out = wastage_call(*args, dt=dt, block_t=bt, interpret=interpret)
    return out[:B, 0]


@functools.partial(jax.jit, static_argnames=("dt", "block_t", "interpret"))
def oom_probe(starts, peaks, mems, lengths, dt: float = 1.0,
              block_t: int = 512, interpret: bool = False):
    """Fused single-attempt OOM probe (fleet-engine inner step).

    starts/peaks: (B, k) float; mems: (B, T) float; lengths: (B,) int32.
    Returns ``(viol, w_succ, w_kill)`` — first violating sample index (or
    -1), successful-attempt wastage, and killed-attempt wastage, each (B,).
    """
    B = mems.shape[0]
    args, bt = _padded(starts, peaks, mems, lengths, block_t)
    outs = oom_probe_call(*args, dt=dt, block_t=bt, interpret=interpret)
    return tuple(o[:B, 0] for o in outs)
