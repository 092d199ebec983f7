"""Batched wastage-evaluation Pallas TPU kernels.

The fleet-scale evaluation hot loop of KS+: for thousands of (execution
trace × allocation plan) pairs, integrate ``allocated − used`` over time.
Each grid point evaluates ``LANES`` executions over one time block: the
step-function allocation is rebuilt in VMEM from the ``(LANES, k)``
segment starts/peaks by an unrolled k-step select chain (k ≤ 16), clamped
from below by the trace (successful-attempt contract), masked by
validity, and reduced per lane.

Every block is legal for Mosaic (last two dims a multiple of ``(8, 128)``
or the full array dims): plans ride ``(LANES, k)`` blocks with k the whole
segment axis, traces ``(LANES, block_t)`` blocks, and per-lane scalars
(lengths, outputs, accumulators) ``(LANES, 1)`` columns.  The wrappers in
:mod:`repro.kernels.wastage.ops` pad the lane axis to a multiple of
``LANES``.

Grid: (num_lane_blocks, num_time_blocks); the per-lane accumulators live
in VMEM scratch and are flushed on the last time block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "wastage_kernel", "wastage_call", "oom_probe_kernel",
           "oom_probe_call"]

LANES = 8  # executions per grid point: one f32 sublane tile


def _time_block(tb, shape, block_t: int, dt: float):
    """Absolute sample indices and times of time block ``tb``."""
    local = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    t_idx = tb * block_t + local
    return local, t_idx, t_idx.astype(jnp.float32) * dt


def _alloc_block(starts, peaks, t):
    """Step-function allocation of each lane on a time block.

    ``(L, k)`` plans at ``(L, bt)`` times.  With ascending starts the last
    slot with ``start <= t`` wins, which is
    ``np.searchsorted(side='right') - 1`` clipped to ``[0, k-1]``:
    duplicate starts yield empty intervals, and padded plan slots carry a
    huge sentinel start and never win.
    """
    alloc = jnp.broadcast_to(peaks[:, 0:1], t.shape)
    for k in range(1, starts.shape[1]):
        alloc = jnp.where(starts[:, k:k + 1] <= t, peaks[:, k:k + 1], alloc)
    return alloc


def wastage_kernel(starts_ref, peaks_ref, mem_ref, len_ref, out_ref, acc_scr,
                   *, block_t: int, dt: float):
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    mem = mem_ref[...].astype(jnp.float32)          # (L, block_t)
    _, t_idx, t = _time_block(tb, mem.shape, block_t, dt)
    alloc = _alloc_block(starts_ref[...].astype(jnp.float32),
                         peaks_ref[...].astype(jnp.float32), t)
    alloc = jnp.maximum(alloc, mem)                 # successful attempt
    valid = (t_idx < len_ref[...]).astype(jnp.float32)
    acc_scr[...] += jnp.sum((alloc - mem) * valid, axis=1, keepdims=True)

    @pl.when(tb == pl.num_programs(1) - 1)
    def _flush():
        out_ref[...] = (acc_scr[...] * dt).astype(out_ref.dtype)


def oom_probe_kernel(starts_ref, peaks_ref, mem_ref, len_ref,
                     viol_ref, wsucc_ref, wkill_ref,
                     succ_scr, cum_scr, kill_scr, viol_scr,
                     *, block_t: int, dt: float):
    """One OOM/retry attempt, fused: first violation + both wastage modes.

    Per execution lane emits the first sample index where demand exceeds the
    allocation (-1 if none), the successful-attempt wastage
    (``max(alloc, mem) − mem`` integrated over valid samples) and the
    killed-attempt wastage (all allocation up to and including the kill
    sample).  The fleet engine's retry loop consumes all three, so one kernel
    pass replaces the per-execution ``first_violation`` + ``alloc_series``
    pair of the Python oracle.

    Scratch, one ``(L, 1)`` column each: successful-attempt wastage,
    cumulative allocation, killed-attempt wastage, and the first violation
    index so far (-1 = none).
    """
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        succ_scr[...] = jnp.zeros_like(succ_scr)
        cum_scr[...] = jnp.zeros_like(cum_scr)
        kill_scr[...] = jnp.zeros_like(kill_scr)
        viol_scr[...] = jnp.full_like(viol_scr, -1)

    mem = mem_ref[...].astype(jnp.float32)          # (L, block_t)
    local, t_idx, t = _time_block(tb, mem.shape, block_t, dt)
    alloc = _alloc_block(starts_ref[...].astype(jnp.float32),
                         peaks_ref[...].astype(jnp.float32), t)
    validb = t_idx < len_ref[...]
    av = jnp.where(validb, alloc, 0.0)

    bad = (mem > alloc) & validb
    # first violating sample in the block per lane (block_t = none)
    idx_in = jnp.min(jnp.where(bad, local, block_t), axis=1, keepdims=True)
    # inclusive prefix of allocation up to the in-block kill sample
    upto = jnp.sum(jnp.where(local <= idx_in, av, 0.0), axis=1,
                   keepdims=True)
    fresh = (viol_scr[...] < 0) & (idx_in < block_t)
    viol_scr[...] = jnp.where(fresh, tb * block_t + idx_in, viol_scr[...])
    kill_scr[...] = jnp.where(fresh, cum_scr[...] + upto, kill_scr[...])
    cum_scr[...] += jnp.sum(av, axis=1, keepdims=True)
    succ_scr[...] += jnp.sum(
        jnp.where(validb, jnp.maximum(alloc, mem) - mem, 0.0), axis=1,
        keepdims=True)

    @pl.when(tb == pl.num_programs(1) - 1)
    def _flush():
        viol_ref[...] = viol_scr[...]
        wsucc_ref[...] = (succ_scr[...] * dt).astype(wsucc_ref.dtype)
        wkill_ref[...] = (kill_scr[...] * dt).astype(wkill_ref.dtype)


def _specs(k: int, block_t: int):
    lane_col = pl.BlockSpec((LANES, 1), lambda b, t: (b, 0))
    in_specs = [
        pl.BlockSpec((LANES, k), lambda b, t: (b, 0)),
        pl.BlockSpec((LANES, k), lambda b, t: (b, 0)),
        pl.BlockSpec((LANES, block_t), lambda b, t: (b, t)),
        lane_col,
    ]
    return in_specs, lane_col


def _grid(starts, mems, lengths, block_t: int):
    B, _ = starts.shape
    T = mems.shape[1]
    assert B % LANES == 0, (B, LANES)
    assert T % block_t == 0, (T, block_t)
    assert lengths.shape == (B, 1), lengths.shape
    return B, (B // LANES, T // block_t)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def oom_probe_call(starts, peaks, mems, lengths, *, dt: float,
                   block_t: int = 512, interpret: bool = False):
    """starts/peaks: (B, k); mems: (B, T); lengths: (B, 1) int32, with
    ``B % LANES == 0`` and ``T % block_t == 0``.

    Returns ``(viol, w_succ, w_kill)``, each (B, 1).
    """
    B, grid = _grid(starts, mems, lengths, block_t)
    in_specs, lane_col = _specs(starts.shape[1], block_t)
    kernel = functools.partial(oom_probe_kernel, block_t=block_t, dt=dt)
    col = pltpu.VMEM((LANES, 1), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[lane_col, lane_col, lane_col],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        scratch_shapes=[col, col, col, pltpu.VMEM((LANES, 1), jnp.int32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="oom_probe",
    )(starts, peaks, mems, lengths)


def wastage_call(starts, peaks, mems, lengths, *, dt: float,
                 block_t: int = 512, interpret: bool = False):
    """Shapes as :func:`oom_probe_call`.  Returns (B, 1)."""
    B, grid = _grid(starts, mems, lengths, block_t)
    in_specs, lane_col = _specs(starts.shape[1], block_t)
    kernel = functools.partial(wastage_kernel, block_t=block_t, dt=dt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=lane_col,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((LANES, 1), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="wastage_eval",
    )(starts, peaks, mems, lengths)
