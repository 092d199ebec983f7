"""Batched fleet-scale OOM/retry simulation engine.

This is the vectorized reformulation of :func:`repro.core.wastage.
simulate_execution`: instead of replaying every test execution through a
Python loop (``families × methods × executions × retry-attempts`` numpy
calls — the hot path behind the paper's Figs. 6–8), an entire batch of
(plan, trace) lanes runs the full OOM/retry protocol inside **one jitted
XLA program**:

1. plans are padded to ``(B, K)`` step functions (sentinel starts mark the
   unused slots) and traces to ``(B, T)`` with a validity length,
2. each attempt evaluates every lane at once — first violating sample
   (the simulated OOM killer), successful-attempt wastage and
   killed-attempt wastage come from one fused probe (the extended Pallas
   ``oom_probe`` kernel on TPU, a pure-``jnp`` formulation elsewhere),
3. failed lanes advance through a *vectorized* retry transform — the KS+
   §II-C re-timing rule and every baseline bump rule expressed as pure
   ``jnp`` plan rewrites,
4. a ``jax.lax.while_loop`` iterates attempts until all lanes either
   succeed or are unsatisfiable on the node class (``machine_memory``),
   capped at ``max_attempts``.

:func:`simulate_execution` remains the per-execution oracle; the
differential test in ``tests/test_fleet.py`` pins this engine to it
attempt-for-attempt.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import record_dispatch
from repro.core import envelope as _env
from repro.core.allocation import AllocationPlan
from repro.core.envelope import PackedEnvelopes, RetrySpec
from repro.obs import trace as _obs

__all__ = [
    "RetrySpec",
    "PackedTraces",
    "TraceBucket",
    "FleetBatch",
    "FleetResult",
    "pack_plans",
    "pack_traces",
    "pad_lane_axis",
    "group_lengths",
    "bucket_traces",
    "subset_batch",
    "fleet_eval",
    "first_attempt",
    "packed_predict",
    "concat_packed",
    "resolve_backend",
    "simulate_fleet",
    "simulate_fleet_many",
]

# Sentinel start for padded plan slots (float32 view of the shared
# envelope-layer sentinel): far beyond any sample time, so the slot's
# interval is empty and the last real segment's peak is held forever.
PAD_START = np.float32(_env.PAD_START)


@dataclasses.dataclass(frozen=True)
class PackedTraces:
    """Padded ``(B, T)`` trace batch, shareable across engine calls."""

    mems: np.ndarray      # (B, T) float32
    lengths: np.ndarray   # (B,)  int32


@dataclasses.dataclass(frozen=True)
class TraceBucket:
    """One length bucket of a :class:`FleetBatch` (lanes of similar T).

    Host copies (``mems``/``lengths``) feed failure-compaction; the
    device-resident, lane-padded copies (``dmems``/``dlengths``/``dsummem``)
    are uploaded once and shared by every probe over this bucket — per-call
    host-to-device transfer would otherwise repeat per method.
    """

    idx: np.ndarray       # (b,) lane indices into the original batch
    mems: np.ndarray      # (b, T_bucket) float32, host
    lengths: np.ndarray   # (b,) int32, host
    dmems: object         # (Bp, T_bucket) jnp, lane axis padded to pow2
    dmemsneg: object      # (Bp, T_bucket) jnp, -inf outside the valid span
    dlengths: object      # (Bp,) jnp int32
    dsummem: object       # (Bp,) jnp float32: sum of valid samples per lane


@dataclasses.dataclass(frozen=True)
class FleetBatch:
    """Traces grouped into power-of-two length buckets.

    Padding every trace to the global maximum length wastes most of the
    engine's (memory-bound) work on zeros — short tasks dominate real
    workflows while a few long ones set T.  Bucketing keeps the padded
    element count within ~2× of the real sample count.  Build once with
    :func:`bucket_traces` and share across methods / plan batches.
    """

    n: int
    buckets: tuple  # tuple[TraceBucket, ...]


@dataclasses.dataclass(frozen=True)
class FleetResult:
    """Per-lane outcome of a fleet simulation (mirrors ExecutionResult)."""

    wastage_gbs: np.ndarray  # (B,) float64
    attempts: np.ndarray     # (B,) int — evaluated attempts (>= 1)
    succeeded: np.ndarray    # (B,) bool

    @property
    def retries(self) -> np.ndarray:
        return self.attempts - 1

    @property
    def total_gbs(self) -> float:
        return float(self.wastage_gbs.sum())


def pack_plans(plans: Sequence[AllocationPlan], k: int | None = None):
    """Pad plans to a common segment count.

    Padded slots get ``PAD_START`` starts (never active) and replicate the
    last real peak, so the packed plan evaluates identically to the original.
    Returns ``(starts, peaks, nseg)`` of shapes (B, K), (B, K), (B,).
    """
    K = int(k if k is not None else max(p.n for p in plans))
    B = len(plans)
    ns = {p.n for p in plans}
    if ns == {K}:  # uniform-width fast path (the common per-method case)
        starts = np.stack([p.starts for p in plans]).astype(np.float32)
        peaks = np.stack([p.peaks for p in plans]).astype(np.float32)
        return starts, peaks, np.full((B,), K, np.int32)
    env = PackedEnvelopes.from_plans(plans, K)
    return (env.starts.astype(np.float32), env.peaks.astype(np.float32),
            env.nseg.astype(np.int32))


def packed_predict(method, inputs: Sequence[float], k: int | None = None):
    """Predict plans for a batch of inputs directly in packed form.

    Uses the method's vectorized ``predict_packed`` when it exposes one
    (every built-in method does — per-plan Python prediction costs more
    than the whole batched simulation at fleet scale), falling back to
    per-plan ``predict`` + :func:`pack_plans`.
    """
    fn = getattr(method, "predict_packed", None)
    if fn is None:
        return pack_plans([method.predict(i) for i in inputs], k)
    starts, peaks = fn(np.asarray(inputs, np.float64))
    starts = np.ascontiguousarray(starts, np.float32)
    peaks = np.ascontiguousarray(peaks, np.float32)
    B, K = starts.shape
    nseg = np.full((B,), K, np.int32)
    if k is not None and k > K:
        starts = np.concatenate(
            [starts, np.full((B, k - K), PAD_START, np.float32)], axis=1)
        peaks = np.concatenate(
            [peaks, np.repeat(peaks[:, -1:], k - K, axis=1)], axis=1)
    return starts, peaks, nseg


def concat_packed(parts: Sequence) -> tuple:
    """Concatenate packed plan triples along lanes, padding K to the max."""
    K = max(p[0].shape[1] for p in parts)
    outs, outp, outn = [], [], []
    for starts, peaks, nseg in parts:
        pad = K - starts.shape[1]
        if pad:
            B = starts.shape[0]
            starts = np.concatenate(
                [starts, np.full((B, pad), PAD_START, np.float32)], axis=1)
            peaks = np.concatenate(
                [peaks, np.repeat(peaks[:, -1:], pad, axis=1)], axis=1)
        outs.append(starts)
        outp.append(peaks)
        outn.append(nseg)
    return (np.concatenate(outs), np.concatenate(outp), np.concatenate(outn))


def pack_traces(mems: Sequence[np.ndarray], min_t: int = 128) -> PackedTraces:
    """Pad traces to a power-of-two length (bucketed to bound recompiles)."""
    T = max(max(len(m) for m in mems), min_t)
    T = 1 << (T - 1).bit_length()
    B = len(mems)
    padded = np.zeros((B, T), np.float32)
    lengths = np.zeros((B,), np.int32)
    for i, m in enumerate(mems):
        padded[i, : len(m)] = m
        lengths[i] = len(m)
    return PackedTraces(mems=padded, lengths=lengths)


def _make_bucket(idx: np.ndarray, mems_list, T: int) -> TraceBucket:
    packed = pack_traces(mems_list, min_t=T)
    b = len(idx)
    Bp = _bucket(b)
    pmems = packed.mems
    plen = packed.lengths
    if Bp != b:
        pmems = np.concatenate(
            [pmems, np.zeros((Bp - b, pmems.shape[1]), np.float32)])
        plen = np.concatenate([plen, np.zeros((Bp - b,), np.int32)])
    summem = np.asarray(
        [m.sum(dtype=np.float64) for m in mems_list]
        + [0.0] * (Bp - b), np.float32)
    memsneg = np.where(
        np.arange(pmems.shape[1])[None, :] < plen[:, None], pmems, -np.inf
    ).astype(np.float32)
    return TraceBucket(
        idx=idx, mems=packed.mems, lengths=packed.lengths,
        dmems=jnp.asarray(pmems), dmemsneg=jnp.asarray(memsneg),
        dlengths=jnp.asarray(plen), dsummem=jnp.asarray(summem))


def group_lengths(lengths: Sequence[int], min_t: int = 128,
                  min_lanes: int = 16, max_buckets: int = 4):
    """The bucket policy itself: lane indices grouped by power-of-two
    padded length.  Sparse buckets are merged into the next-longer one
    (below ``min_lanes`` lanes a bucket costs more in per-group overhead
    than its padding saves) and ``max_buckets`` bounds the orchestration
    fan-out.  Returns ``[(T, sorted index array), ...]`` ascending in T —
    shared by :func:`bucket_traces` and the workload generator's
    direct-to-packed-lanes path (:mod:`repro.workloads.generate`), so the
    two always agree on layout.
    """
    by_t: dict = {}
    for i, n in enumerate(lengths):
        T = max(int(n), min_t)
        T = 1 << (T - 1).bit_length()
        by_t.setdefault(T, []).append(i)
    groups = []  # ascending T, merged
    carry: list = []
    for T in sorted(by_t):
        cur = carry + by_t[T]
        if len(cur) < min_lanes and T != max(by_t):
            carry = cur
            continue
        groups.append((T, cur))
        carry = []
    # (the largest-T iteration always appends, so nothing is left in carry)
    while len(groups) > max_buckets:
        # merge the smallest group into the next-longer one
        i = min(range(len(groups) - 1), key=lambda g: len(groups[g][1]))
        T = groups[i + 1][0]
        groups[i + 1] = (T, groups[i][1] + groups[i + 1][1])
        del groups[i]
    return [(T, np.asarray(sorted(ids), np.int64)) for T, ids in groups]


def bucket_traces(mems: Sequence[np.ndarray], min_t: int = 128,
                  min_lanes: int = 16, max_buckets: int = 4) -> FleetBatch:
    """Group traces into power-of-two length buckets (see FleetBatch and
    :func:`group_lengths`, the shared grouping policy)."""
    buckets = []
    for T, idx in group_lengths([len(m) for m in mems], min_t,
                                min_lanes, max_buckets):
        buckets.append(_make_bucket(idx, [mems[i] for i in idx], T))
    return FleetBatch(n=len(mems), buckets=tuple(buckets))


def subset_batch(batch: FleetBatch, lanes) -> FleetBatch:
    """Restrict a :class:`FleetBatch` to a lane subset, keeping bucket widths.

    Every selected lane stays in (a copy of) its original bucket with the
    original padded length ``T``, so all per-lane engine arithmetic —
    probes, span sums, device-side trace reductions — is bit-identical to a
    run over the full batch.  The online replay harness leans on this: its
    round batches must reproduce the offline replay bitwise under
    ``refit="never"``.  ``n`` and the buckets' ``idx`` keep the *original*
    lane numbering, so full-batch plan/result arrays index unchanged.
    """
    want = {int(i) for i in np.asarray(lanes).ravel()}
    buckets = []
    for b in batch.buckets:
        local = np.asarray(
            [p for p, i in enumerate(b.idx) if int(i) in want], np.int64)
        if local.size == 0:
            continue
        nb, T = int(local.size), b.mems.shape[1]
        Bp = _bucket(nb)
        pmems = np.zeros((Bp, T), np.float32)
        pmems[:nb] = b.mems[local]
        plen = np.zeros((Bp,), np.int32)
        plen[:nb] = b.lengths[local]
        # Slice (never recompute) the per-lane trace sums: the originals
        # were reduced from the raw float64 traces, which the float32 host
        # rows kept here cannot reproduce bit-for-bit.
        summem = np.zeros((Bp,), np.float32)
        summem[:nb] = np.asarray(b.dsummem)[local]
        memsneg = np.where(
            np.arange(T)[None, :] < plen[:, None], pmems, -np.inf
        ).astype(np.float32)
        buckets.append(TraceBucket(
            idx=b.idx[local], mems=b.mems[local], lengths=b.lengths[local],
            dmems=jnp.asarray(pmems), dmemsneg=jnp.asarray(memsneg),
            dlengths=jnp.asarray(plen), dsummem=jnp.asarray(summem)))
    return FleetBatch(n=batch.n, buckets=tuple(buckets))


# --------------------------------------------------------------------- probe
def _first_violation_jnp(starts, peaks, memsneg, dt: float):
    """First sample with ``mem > alloc`` per lane, or -1.

    ``alloc(t) = peaks[#{i : starts_i <= t} - 1]`` reproduces the oracle's
    ``searchsorted(side='right') - 1`` segment lookup, duplicate starts and
    sentinel padding included; ``memsneg`` is -inf outside the valid span,
    folding the validity mask into the comparison itself.
    """
    B, T = memsneg.shape
    K = starts.shape[1]
    t = jnp.arange(T, dtype=jnp.float32) * dt
    idx = jnp.sum(starts[:, None, :] <= t[None, :, None], axis=2) - 1
    idx = jnp.clip(idx, 0, K - 1)
    alloc = jnp.take_along_axis(peaks, idx, axis=1)
    bad = memsneg > alloc
    any_v = jnp.any(bad, axis=1)
    vidx = jnp.argmax(bad, axis=1)
    return jnp.where(any_v, vidx, -1).astype(jnp.int32)


def _seg_bounds(starts, dt: float):
    """b_k = first sample index i with ``i*dt >= starts_k`` — exactly.

    ``ceil(start/dt)`` alone can be off by one ulp, so both neighbours are
    checked with the *same* float32 arithmetic the probe's time grid uses
    (``i.astype(f32) * dt``), making the boundaries bit-consistent with the
    per-sample comparisons.
    """
    c = jnp.clip(jnp.ceil(starts / dt), 0.0, 1.0e9)
    c = c - ((c - 1.0) * dt >= starts)
    c = c + (jnp.clip(c, 0.0, 1.0e9) * dt < starts)
    b = jnp.clip(c, 0.0, 2.0e9).astype(jnp.int32)
    # segment 0 is active from t=0 regardless (index clipping semantics)
    return b.at[:, 0].set(0)


def _span_alloc_sum(peaks, bounds, upto):
    """``sum_k peaks_k * |[b_k, b_{k+1}) ∩ [0, upto)|`` — the allocation
    integral over the first ``upto`` samples in O(K) per lane."""
    B, K = peaks.shape
    hi = jnp.concatenate(
        [bounds[:, 1:], jnp.full((B, 1), np.iinfo(np.int32).max, jnp.int32)],
        axis=1)
    lo = jnp.minimum(bounds, upto[:, None])
    hi = jnp.minimum(hi, upto[:, None])
    return jnp.sum(peaks * jnp.maximum(hi - lo, 0).astype(jnp.float32),
                   axis=1)


def _oom_probe_jnp(starts, peaks, mems, memsneg, lengths, summem, dt: float):
    """Full per-attempt probe: ``(viol, w_succ, w_kill, used)``.

    ``w_succ`` is exact only for lanes with ``viol < 0`` (for a successful
    attempt ``max(alloc, mem) == alloc`` everywhere, so the wastage
    integral collapses to segment-span arithmetic minus ``summem``); the
    engine never reads it otherwise.  ``w_kill`` integrates the allocation
    up to and including the kill sample, again in O(K) spans.
    """
    viol = _first_violation_jnp(starts, peaks, memsneg, dt)
    bounds = _seg_bounds(starts, dt)
    w_succ = (_span_alloc_sum(peaks, bounds, lengths) - summem) * dt
    v = jnp.maximum(viol, 0)
    w_kill = jnp.where(
        viol >= 0, _span_alloc_sum(peaks, bounds, v + 1), 0.0) * dt
    used = jnp.take_along_axis(mems, v[:, None], axis=1)[:, 0]
    return viol, w_succ, w_kill, used


@functools.partial(jax.jit, static_argnames=("dt", "backend", "block_t"))
def first_attempt(starts, peaks, mems, lengths, machine_memory, *,
                  dt: float, backend: str = "jnp", block_t: int = 512):
    """Probe attempt #1 for every lane: ``(viol, w_succ)``.

    Standalone-jit convenience around the phase-A probe of
    :func:`simulate_fleet_many` (which amortizes dispatch by batching many
    groups instead).  ``w_succ`` is meaningful where ``viol < 0``.
    """
    capped = jnp.minimum(peaks, machine_memory)
    if backend == "jnp":
        validb = jnp.arange(mems.shape[1])[None, :] < lengths[:, None]
        memsneg = jnp.where(validb, mems, -jnp.inf)
        summem = jnp.sum(jnp.where(validb, mems, 0.0), axis=1)
        viol, w_succ = _probe_first_jnp(
            starts, capped, memsneg, lengths, summem, dt)
    else:
        from repro.kernels.wastage.ops import oom_probe
        viol, w_succ, _ = oom_probe(
            starts, capped, mems, lengths, dt=dt, block_t=block_t,
            interpret=(backend == "pallas-interpret"))
    return viol, w_succ


# --------------------------------------------------------------- retry rules
def _retry_transform(spec: RetrySpec, starts, peaks, nseg, t_fail, used,
                     machine_memory, bump=None):
    """Vectorized ``(plan, t_fail, used) -> plan`` over every lane at once.

    Mirrors :mod:`repro.core.retry` rule for rule; lanes that are not
    retrying are masked out by the caller.  ``bump`` optionally overrides
    the static ``spec.bump`` per lane (a traced ``(B,)`` array — see
    :func:`repro.core.envelope.retry_packed`).
    """
    B, K = starts.shape
    idx = jnp.arange(K)[None, :]
    real = idx < nseg[:, None]

    if spec.kind == "none":
        return starts, peaks
    if spec.kind == "double":
        return starts, jnp.minimum(peaks * 2.0, machine_memory)
    if spec.kind == "max-machine":
        return starts, jnp.full_like(peaks, machine_memory)

    # Failed segment: last real slot with start <= t_fail (searchsorted-right
    # semantics; sentinel-padded slots never count).
    j = jnp.sum((starts <= t_fail[:, None]) & real, axis=1) - 1
    j = jnp.clip(j, 0, nseg - 1)
    peak_j = jnp.take_along_axis(peaks, j[:, None], axis=1)[:, 0]

    if spec.kind == "kseg-selective":
        target = jnp.maximum(peak_j * (1.0 + spec.margin),
                             used * (1.0 + spec.margin))
        return starts, jnp.where(idx == j[:, None], target[:, None], peaks)

    if spec.kind == "kseg-partial":
        target = jnp.maximum(peak_j * (1.0 + spec.margin),
                             used * (1.0 + spec.margin))
        raise_mask = real & (idx >= j[:, None])
        return starts, jnp.where(
            raise_mask, jnp.maximum(peaks, target[:, None]), peaks)

    if spec.kind == "ksplus":
        is_last = j >= nseg - 1
        # --- re-time branch: next segment begins exactly at the failure time,
        # every later one is scaled by the same factor.
        nxt = jnp.take_along_axis(
            starts, jnp.minimum(j + 1, K - 1)[:, None], axis=1)[:, 0]
        factor = jnp.where(nxt > 0, t_fail / jnp.maximum(nxt, 1e-30), 0.0)
        st = jnp.where(real & (idx > (j + 1)[:, None]),
                       starts * factor[:, None], starts)
        st = jnp.where(idx == (j + 1)[:, None], t_fail[:, None], st)
        st = jax.lax.cummax(jnp.maximum(st, 0.0), axis=1)
        st = st.at[:, 0].set(0.0)
        st = jnp.where(real, st, PAD_START)
        # --- last-segment branch: bump the final peak, keep monotone.
        bump_col = spec.bump if bump is None else bump[:, None]
        pk = jnp.where(idx == (nseg - 1)[:, None],
                       peaks * (1.0 + bump_col), peaks)
        pk = jax.lax.cummax(pk, axis=1)
        new_starts = jnp.where(is_last[:, None], starts, st)
        new_peaks = jnp.where(is_last[:, None], pk, peaks)
        return new_starts, new_peaks

    raise ValueError(f"unknown retry kind: {spec.kind!r}")


# -------------------------------------------------------------------- engine
def _engine_loop(starts, peaks, nseg, mems, lengths, machine_memory, *,
                 retry: RetrySpec, dt: float, max_attempts: int,
                 backend: str, block_t: int = 512, bump_lanes=None):
    """Traced body of the retry engine (shared by every jitted entry point).

    ``bump_lanes`` is an optional traced ``(B,)`` per-lane override of the
    ksplus ``retry.bump`` — tuned offsets may assign a different
    last-peak bump per task family within one lane batch.
    """
    B, T = mems.shape
    validb = jnp.arange(T)[None, :] < lengths[:, None]
    # Loop-invariant trace precomputes, amortized over every attempt.
    memsneg = jnp.where(validb, mems, -jnp.inf)
    summem = jnp.sum(jnp.where(validb, mems, 0.0), axis=1)
    peak_demand = jnp.max(memsneg, axis=1)
    unsat = peak_demand > machine_memory  # no allocation can satisfy

    if backend == "jnp":
        def probe(s, p):
            return _oom_probe_jnp(s, p, mems, memsneg, lengths, summem, dt)
    else:
        from repro.kernels.wastage.ops import oom_probe

        def probe(s, p):
            viol, w_succ, w_kill = oom_probe(
                s, p, mems, lengths, dt=dt, block_t=block_t,
                interpret=(backend == "pallas-interpret"))
            used = jnp.take_along_axis(
                mems, jnp.maximum(viol, 0)[:, None], axis=1)[:, 0]
            return viol, w_succ, w_kill, used

    def cond(state):
        it, _, _, active, _, _, _ = state
        return (it < max_attempts) & jnp.any(active)

    def body(state):
        it, sts, pks, active, succ, att, w = state
        capped = jnp.minimum(pks, machine_memory)
        viol, w_succ, w_kill, used = probe(sts, capped)
        failed = viol >= 0
        succ_now = active & ~failed
        w = w + jnp.where(succ_now, w_succ, 0.0) \
              + jnp.where(active & failed, w_kill, 0.0)
        att = att + active.astype(jnp.int32)
        succ = succ | succ_now
        retrying = active & failed & ~unsat
        t_fail = jnp.maximum(viol, 0).astype(jnp.float32) * dt
        nsts, npks = _retry_transform(
            retry, sts, capped, nseg, t_fail, used, machine_memory,
            bump=bump_lanes)
        sts = jnp.where(retrying[:, None], nsts, sts)
        pks = jnp.where(retrying[:, None], npks, capped)
        return (it + 1, sts, pks, retrying, succ, att, w)

    state = (
        jnp.int32(0),
        jnp.asarray(starts, jnp.float32),
        jnp.asarray(peaks, jnp.float32),
        jnp.ones((B,), bool),
        jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.float32),
    )
    _, _, _, _, succeeded, attempts, wastage = jax.lax.while_loop(
        cond, body, state)
    return wastage, attempts, succeeded


@functools.partial(
    jax.jit,
    static_argnames=("retry", "dt", "max_attempts", "backend", "block_t"),
)
def fleet_eval(starts, peaks, nseg, mems, lengths, machine_memory, *,
               retry: RetrySpec, dt: float, max_attempts: int = 25,
               backend: str = "jnp", block_t: int = 512, bump_lanes=None):
    """Run the full OOM/retry protocol for every lane in one XLA program.

    Args:
      starts/peaks: (B, K) packed plans (``pack_plans``).
      nseg:         (B,)  real segment counts.
      mems:         (B, T) padded traces; lengths: (B,) valid counts.
      machine_memory: scalar — node capacity cap (traced, so sweeping it
        does not recompile).
      retry: static :class:`RetrySpec`.
      backend: ``"jnp"`` | ``"pallas"`` | ``"pallas-interpret"``.
      bump_lanes: optional (B,) per-lane ksplus last-peak bump override
        (traced; ``None`` keeps ``retry.bump`` everywhere).

    Returns ``(wastage, attempts, succeeded)``, each (B,).
    """
    return _engine_loop(starts, peaks, nseg, mems, lengths, machine_memory,
                        retry=retry, dt=dt, max_attempts=max_attempts,
                        backend=backend, block_t=block_t,
                        bump_lanes=bump_lanes)


def _probe_first_jnp(starts, peaks, memsneg, lengths, summem, dt: float):
    """Attempt-#1 probe: ``(viol, w_succ)`` with w_succ valid where viol<0.

    The fast path of the fleet: one per-sample pass for the violation scan,
    O(K) span arithmetic for the wastage of the (majority) lanes that
    succeed immediately.
    """
    viol = _first_violation_jnp(starts, peaks, memsneg, dt)
    bounds = _seg_bounds(starts, dt)
    w_succ = (_span_alloc_sum(peaks, bounds, lengths) - summem) * dt
    return viol, w_succ


@functools.partial(jax.jit, static_argnames=("dt", "backend", "block_t"))
def _probe_many(groups, machine_memory, *, dt: float, backend: str = "jnp",
                block_t: int = 512):
    """Attempt #1 for many (plan batch, trace bucket) groups, ONE dispatch.

    ``groups`` is a pytree: a tuple of
    ``(starts, peaks, mems, memsneg, lengths, summem)`` per group.
    Per-call dispatch overhead (~0.5 ms on CPU) dwarfs the per-group
    compute for typical bucket sizes, so every method × length bucket of an
    experiment probes in a single XLA program.
    """
    out = []
    for starts, peaks, mems, memsneg, lengths, summem in groups:
        capped = jnp.minimum(peaks, machine_memory)
        if backend == "jnp":
            viol, w_succ = _probe_first_jnp(
                starts, capped, memsneg, lengths, summem, dt)
        else:
            from repro.kernels.wastage.ops import oom_probe
            viol, w_succ, _ = oom_probe(
                starts, capped, mems, lengths, dt=dt, block_t=block_t,
                interpret=(backend == "pallas-interpret"))
        out.append((viol, w_succ))
    return tuple(out)


@functools.partial(
    jax.jit,
    static_argnames=("specs", "dt", "max_attempts", "backend", "block_t"),
)
def _retry_many(groups, machine_memory, *, specs, dt: float,
                max_attempts: int = 25, backend: str = "jnp",
                block_t: int = 512):
    """Full retry loops for many compacted failure groups, ONE dispatch.

    ``groups`` is a tuple of ``(starts, peaks, nseg, mems, lengths, bump)``
    (``bump`` a per-lane ksplus bump array or ``None``); ``specs`` the
    matching static tuple of :class:`RetrySpec`.
    """
    out = []
    for spec, (starts, peaks, nseg, mems, lengths, bump) in zip(specs,
                                                                groups):
        out.append(_engine_loop(
            starts, peaks, nseg, mems, lengths, machine_memory,
            retry=spec, dt=dt, max_attempts=max_attempts, backend=backend,
            block_t=block_t, bump_lanes=bump))
    return tuple(out)


def _bucket(b: int, lo: int = 8) -> int:
    return max(lo, 1 << (b - 1).bit_length())


def pad_lane_axis(arrs: Sequence[np.ndarray], fills: Sequence,
                  lo: int = 8, fine: bool = False, sub: int = 8) -> tuple:
    """Pad every array's leading (lane) axis to a shared bucket size.

    The compaction trick shared by the fleet retry engine and the fused
    admission engine: gather the active minority into compact rows, then
    pad the lane axis to a bucketed size so the jitted consumers see a
    bounded set of shapes instead of one compile per lane count.
    ``fine=False`` pads to the next power of two (log2-many shapes, up to
    ~2x padding); ``fine=True`` pads to the next multiple of 1/``sub`` of
    the next power of two (``sub`` shapes per octave; the default 8 gives
    <= 25% worst-case padding waste — for the admission engine's deep
    queues, where a 2x pad would double the per-dispatch work).  Callers
    whose lane count wanders across octaves every dispatch can lower
    ``sub`` to trade padding waste for fewer compiled shapes.
    ``fills[i]`` is the pad value for ``arrs[i]``; dtypes are preserved.
    """
    B = int(arrs[0].shape[0])
    Bp = _bucket(B, lo)
    if fine and Bp > lo:
        step = max(Bp // sub, lo)
        Bp = ((B + step - 1) // step) * step
    if Bp == B:
        return tuple(arrs)
    return tuple(
        np.concatenate(
            [a, np.full((Bp - B,) + a.shape[1:], fill, a.dtype)])
        for a, fill in zip(arrs, fills))


def _pad_lanes(starts, peaks, nseg, mems, lengths):
    """Pad the lane axis to a power of two (dummy lanes trivially succeed)."""
    return pad_lane_axis(
        (starts, peaks, nseg, mems, lengths),
        (PAD_START, 1.0, 1, 0.0, 0))


def _as_batch(mems) -> FleetBatch:
    if isinstance(mems, FleetBatch):
        return mems
    if isinstance(mems, PackedTraces):
        B, T = mems.mems.shape
        rows = [mems.mems[i, : mems.lengths[i]] for i in range(B)]
        return FleetBatch(
            n=B, buckets=(_make_bucket(np.arange(B), rows, T),))
    return bucket_traces(mems)


def resolve_backend(backend: str) -> str:
    """The probe backend ``backend="auto"`` stands for: the compiled
    ``oom_probe`` Mosaic kernel on a TPU (a kernel that fails to compile
    raises; there is no fallback), the ``jnp`` formulation elsewhere."""
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def simulate_fleet_many(
    jobs: Sequence,
    mems: Union[FleetBatch, PackedTraces, Sequence[np.ndarray]],
    dt: float = 1.0,
    *,
    machine_memory: float = np.inf,
    max_attempts: int = 25,
    backend: str = "auto",
    k: int | None = None,
) -> List[FleetResult]:
    """Run many plan batches against one shared trace batch.

    ``jobs`` is a sequence of ``(plans, retry_spec)`` pairs — e.g. one per
    prediction method — all evaluated against the same executions.  Each
    job's ``plans`` may be a list of :class:`AllocationPlan` or an already
    packed ``(starts, peaks, nseg)`` triple (see :func:`pack_plans` /
    :func:`packed_predict`); an optional third element is a per-lane
    ``(B,)`` ksplus last-peak-bump array overriding ``retry_spec.bump``
    lane for lane (NaN entries keep the spec's static value) — tuned
    per-task-family offsets ride the lane batch this way.  The
    orchestration is built for a dispatch-bound host:

    * traces are grouped into power-of-two **length buckets** (padding every
      lane to the longest trace would spend most of the memory-bound probe
      on zeros),
    * **one** jitted call probes attempt #1 of every job × bucket — the
      usually-large majority of lanes that succeeds immediately is settled
      by that single dispatch,
    * the failing minority is **compacted** and a second jitted call runs
      the full retry while-loop per job × bucket group (re-evaluating their
      first attempt: a small price, on a small subset, for a state-free
      handoff).

    Per-call overhead (~0.5 ms) therefore amortizes over *all* methods and
    buckets instead of multiplying into them.
    """
    if _obs.enabled:
        with _obs.span("fleet.simulate_many", jobs=len(jobs)):
            return _simulate_fleet_many_impl(
                jobs, mems, dt, machine_memory=machine_memory,
                max_attempts=max_attempts, backend=backend, k=k)
    return _simulate_fleet_many_impl(
        jobs, mems, dt, machine_memory=machine_memory,
        max_attempts=max_attempts, backend=backend, k=k)


def _simulate_fleet_many_impl(
    jobs: Sequence,
    mems: Union[FleetBatch, PackedTraces, Sequence[np.ndarray]],
    dt: float = 1.0,
    *,
    machine_memory: float = np.inf,
    max_attempts: int = 25,
    backend: str = "auto",
    k: int | None = None,
) -> List[FleetResult]:
    batch = _as_batch(mems)
    B = batch.n
    norm = []
    for item in jobs:
        plans, r = item[0], item[1]
        spec = RetrySpec(r) if isinstance(r, str) else r
        bump = item[2] if len(item) > 2 else None
        if bump is not None:
            bump = np.where(np.isnan(np.asarray(bump, np.float64)),
                            spec.bump, bump).astype(np.float32)
        norm.append((plans, spec, bump))
    jobs = norm
    packed_jobs = []  # (starts, peaks, nseg) over ALL lanes, per job
    for plans, _, _ in jobs:
        sp = plans if isinstance(plans, tuple) else pack_plans(plans, k)
        if sp[0].shape[0] != B:
            raise ValueError(f"{sp[0].shape[0]} plans vs {B} traces")
        packed_jobs.append(sp)
    backend = resolve_backend(backend)
    mm = jnp.float32(machine_memory)

    # Phase A: slice each job's packed plans per bucket, probe everything in
    # one dispatch against the buckets' device-resident traces.
    groups = []
    for starts, peaks, nseg in packed_jobs:
        for bucket in batch.buckets:
            bs, bp = starts[bucket.idx], peaks[bucket.idx]
            Bp = bucket.dmems.shape[0]
            if Bp != bs.shape[0]:
                pad = Bp - bs.shape[0]
                bs = np.concatenate(
                    [bs, np.full((pad, bs.shape[1]), PAD_START, np.float32)])
                bp = np.concatenate(
                    [bp, np.ones((pad, bp.shape[1]), np.float32)])
            groups.append(
                (bs, bp, bucket.dmems, bucket.dmemsneg, bucket.dlengths,
                 bucket.dsummem))
    record_dispatch("fleet.probe")
    probes = _probe_many(tuple(groups), mm, dt=float(dt), backend=backend)

    results = [
        FleetResult(wastage_gbs=np.zeros((B,), np.float64),
                    attempts=np.ones((B,), np.int64),
                    succeeded=np.zeros((B,), bool))
        for _ in jobs
    ]

    # Phase B: compact failures per group, run every retry loop at once.
    fail_groups, fail_specs, fail_meta = [], [], []
    gi = 0
    for j, (_, spec, bump) in enumerate(jobs):
        starts, peaks, nseg = packed_jobs[j]
        for bucket in batch.buckets:
            b = len(bucket.idx)
            # lint: allow[host-sync-in-hot-path] one batched readback per bucket group; failures must be compacted on host for phase B
            viol, w_succ = jax.device_get(probes[gi])
            viol = viol[:b]
            w_succ = w_succ[:b].astype(np.float64)
            ok = viol < 0
            res = results[j]
            res.wastage_gbs[bucket.idx[ok]] = w_succ[ok]
            res.succeeded[bucket.idx[ok]] = True
            if not ok.all():
                local = np.nonzero(~ok)[0]
                fail = bucket.idx[local]
                padded = _pad_lanes(
                    starts[fail], peaks[fail], nseg[fail],
                    bucket.mems[local], bucket.lengths[local])
                fbump = None
                if bump is not None:
                    (fbump,) = pad_lane_axis(
                        (bump[fail],), (np.float32(spec.bump),))
                fail_groups.append(padded + (fbump,))
                fail_specs.append(spec)
                fail_meta.append((j, fail, len(fail)))
            gi += 1

    if fail_groups:
        record_dispatch("fleet.retry")
        outs = _retry_many(
            tuple(fail_groups), mm, specs=tuple(fail_specs),
            dt=float(dt), max_attempts=max_attempts, backend=backend)
        for (j, out_idx, nf), out in zip(fail_meta, outs):
            res = results[j]
            # lint: allow[host-sync-in-hot-path] one batched readback per fail group scatters the retry outcomes
            w, att, suc = jax.device_get(out)
            res.wastage_gbs[out_idx] = w[:nf].astype(np.float64)
            res.attempts[out_idx] = att[:nf]
            res.succeeded[out_idx] = suc[:nf]
    return results


def simulate_fleet(
    plans: Sequence[AllocationPlan],
    retry: Union[RetrySpec, str],
    mems: Union[FleetBatch, PackedTraces, Sequence[np.ndarray]],
    dt: float = 1.0,
    *,
    machine_memory: float = np.inf,
    max_attempts: int = 25,
    backend: str = "auto",
    k: int | None = None,
    bump_lanes: np.ndarray | None = None,
) -> FleetResult:
    """Simulate one execution per (plan, trace) lane — the fleet primitive.

    Drop-in batched equivalent of calling
    :func:`repro.core.wastage.simulate_execution` per lane; see
    :func:`simulate_fleet_many` for the orchestration (this is the
    single-job case).  ``bump_lanes`` optionally assigns a per-lane ksplus
    last-peak bump (NaN = keep ``retry``'s static value).
    """
    return simulate_fleet_many(
        [(plans, retry, bump_lanes)], mems, dt,
        machine_memory=machine_memory, max_attempts=max_attempts,
        backend=backend, k=k)[0]
