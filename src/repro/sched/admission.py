"""Shared admission runtime state: one fits matrix, one invalidation protocol.

Both admission paths — :class:`repro.sched.cluster.ClusterSim`'s packed
event loop and :class:`repro.sched.elastic.ElasticPlanner`'s churn-driven
``drain`` — answer the same question at every decision point: *which queued
envelopes fit under which node's residual envelope right now?*  This module
owns that answer as explicit runtime state instead of a per-call
recomputation:

* a **fits matrix** ``(N nodes, B lanes)`` of admission predicates plus a
  per-entry **validity mask** — the single source of truth for "does lane b
  fit node n at the current time",
* one **invalidation protocol** (see :class:`AdmissionState`):

  - advancing ``now`` invalidates everything (residuals are functions of
    absolute time),
  - *placing* a lane on a node invalidates only the node's currently-True
    entries — adding an envelope can only shrink the residual, so False
    entries stay False without recomputation (monotonicity),
  - *releasing* a lane from a node invalidates the node's whole column
    (the residual grew; False entries may flip True),
  - a lane's plan change (retry re-plan) invalidates that lane everywhere,
  - node join/leave adds/drops a row,

* two interchangeable compute backends:

  - ``backend="numpy"`` — the float64 host reference: per-node
    :func:`repro.core.envelope.residual_over` + ``fits_under`` calls,
    exactly the arithmetic the packed ``ClusterSim`` engine inlines,
  - ``backend="fused"`` — ONE jitted XLA dispatch per refresh computing
    every invalid ``(node, lane)`` entry at once on device-resident
    float64 state (``jax.enable_x64(True)`` scopes the 64-bit
    semantics to these calls).  The packed envelope/need/placement-time
    buffers live on the device and are updated in place through donated
    scatter programs, so the per-event hot path is one fused dispatch
    over the already-packed ``(B, K)`` layout — not a Python loop over
    nodes and queued jobs.

Precision contract (see also :mod:`repro.sched.cluster`): both backends
evaluate residuals and admission predicates in float64 with identical
elementwise operations; the only permitted divergence is the summation
order over a node's resident envelopes (numpy reduces linearly, XLA may
tree-reduce), i.e. last-ulp differences ~1e-16 relative.  A decision can
therefore only differ between backends when a lane's need grazes the
residual within one float64 ulp of the 1e-9 admission tolerance — orders
of magnitude below any real trace/plan margin.

Shapes are kept jit-stable by padding the queued-lane and resident-lane
axes to power-of-two buckets (:func:`repro.core.fleet.pad_lane_axis`, the
fleet engine's compaction trick), bounding compilation to log2-many shapes.

The state is *frontier-agnostic*: ``ClusterSim``'s DAG-aware replay adds
every lane up front but only passes *released* lanes (all parents
finished) to :meth:`AdmissionState.columns`, so dependency structure
costs nothing here — unreleased lanes simply never enter a refresh.  The
``workload_replay`` benchmark drives this path with a ≥5k-task DAG.

The join/leave row protocol (:meth:`AdmissionState.add_node` /
:meth:`remove_node`) is what both churn consumers share:
``ElasticPlanner`` drives it for slice membership, and ``ClusterSim``'s
fault path drives it for ``FaultSchedule`` leave/join events —
``remove_node`` returns the dead node's resident lanes *in admission
order*, which is the eviction order every engine pins bitwise.  Node
rows are positional (a leave splices, a join appends); callers keep
their own stable-id ↔ row mapping.  Because the fused dispatch takes
``caps`` and the resident-lane index per call, churn needs no
device-state rebuild: dropping a row just drops it from the next
dispatch's operands, keeping the engine one-dispatch-per-refresh under
faults (``churn_replay`` benchmark).
"""

from __future__ import annotations

import functools
import itertools
from typing import List, Optional, Sequence

import numpy as np

from repro.analysis.contracts import record_dispatch
from repro.core.envelope import fits_column
from repro.obs import trace as _obs

__all__ = ["AdmissionState"]

_KERNEL_CACHE = {}


def _pow4(n: int) -> int:
    """Round ``n`` up to a power of 4 (1, 4, 16, 64, ...).

    Run-axis bucket for the fused kernels: coarser than pow2 on
    purpose — halving the number of distinct compiled shapes costs at
    most 2x padding on an axis these kernels reduce over cheaply.
    """
    b = max(n - 1, 0).bit_length()
    return 1 << (b + (b & 1))


def _fused_kernel(masked: bool):
    """Build (once) the jitted fused fits-columns program.

    Computes, for every requested node and queued lane at once::

        resid[n, q, g] = cap[n] - sum_r alloc_r(now + grid[q, g] - t0[r])
        fits[n, q]     = all_g need[q, g] <= resid[n, q, g] + tol
        minresid[n, q] = min_g resid[n, q, g]

    mirroring ``residual_over`` / ``fits_under`` elementwise in float64.
    ``masked`` (static) selects the anticipating-residual semantics
    (resident envelopes only count inside ``[t0, t0 + dur)``, the cluster
    simulator's rule) vs. the conservative count-forever semantics (the
    elastic planner's rule, ``usage_over`` with ``dur=None``).
    """
    if masked in _KERNEL_CACHE:
        return _KERNEL_CACHE[masked]
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(starts, peaks, admit_t, dur, need, grid,
               caps, run_idx, run_valid, used_now, q_idx, now, tol):
        N, R = run_idx.shape
        K = starts.shape[1]
        G = grid.shape[1]
        flat = run_idx.reshape(-1)
        rs = starts[flat]                        # (N*R, K)
        rp = peaks[flat]
        rt0 = admit_t[flat]                      # (N*R,)
        t = (now + grid[q_idx]).reshape(-1)      # (Q*G,) absolute times
        rel = t[None, :] - rt0[:, None]          # (N*R, Q*G)
        relc = jnp.maximum(rel, 0.0)
        # Step-function evaluation as a K-step select chain: with ascending
        # starts, the last satisfied "starts_k <= t" wins — exactly
        # ``searchsorted(side='right') - 1`` clipped to [0, K-1], without
        # materializing the (lanes, times, K) one-hot tensor.
        alloc = jnp.broadcast_to(rp[:, 0:1], relc.shape)
        for k in range(1, K):
            alloc = jnp.where(rs[:, k:k + 1] <= relc, rp[:, k:k + 1], alloc)
        if masked:
            rdur = dur[flat]
            active = (rel >= 0.0) & (rel < rdur[:, None] + 1e-9)
            alloc = jnp.where(active, alloc, 0.0)
        alloc = jnp.where(run_valid.reshape(-1)[:, None], alloc, 0.0)
        usage = alloc.reshape(N, R, -1).sum(axis=1)          # (N, Q*G)
        resid = _at_now((caps[:, None] - usage).reshape(N, -1, G),
                        caps - used_now)                     # (N, Q, G)
        fits = jnp.all(need[q_idx][None, :, :] <= resid + tol, axis=-1)
        minresid = jnp.min(resid, axis=-1)
        return fits, minresid

    _KERNEL_CACHE[masked] = kernel
    return kernel


def _drain_alloc_chain(rs, rp, relc):
    """Step-function evaluation as a K-step select chain (shared with the
    columns kernel: with ascending starts, the last satisfied
    ``starts_k <= t`` wins) — ``(L, K) x (L, M) -> (L, M)``."""
    import jax.numpy as jnp
    alloc = jnp.broadcast_to(rp[:, 0:1], relc.shape)
    for k in range(1, rs.shape[1]):
        alloc = jnp.where(rs[:, k:k + 1] <= relc, rp[:, k:k + 1], alloc)
    return alloc


def _at_now(resid, resid_now):
    """Residuals ``(N, Q, G)`` with every queued lane's first grid point,
    the instant ``now`` itself (``grid[:, 0] == 0``), taken from the
    host's ``resid_now`` ``(N,)`` where it is not NaN (see
    :meth:`AdmissionState._used_now`)."""
    import jax.numpy as jnp
    host = (jnp.arange(resid.shape[-1]) == 0) & ~jnp.isnan(resid_now)[
        :, None, None]
    return jnp.where(host, resid_now[:, None, None], resid)


# Steps one drain program takes at most: the events of a same-instant run
# are folded into one call, and a longer run is split into consecutive
# calls.  A constant of the program, not a shape bucket: a call's real
# step count is an operand, and a one-event drain is the one-step case.
RUN_STEPS = 16


def _drain_pack(caps, node_valid, run_idx, run_valid, used_now, q_idx,
                q_from, leave_node, leave_slot, old_starts, old_peaks,
                n_steps, now, tol) -> np.ndarray:
    """The unsharded drain's per-call operands as ONE float64 host vector.

    Layout, in operand order: ``caps (npad) | node_valid (npad) |
    run_idx (npad*rmax) | run_valid (npad*rmax) | used_now
    (RUN_STEPS*npad) | q_idx (Q) | q_from (Q) | leave_node (RUN_STEPS) |
    leave_slot (RUN_STEPS) | old_starts (RUN_STEPS*K) | old_peaks
    (RUN_STEPS*K) | n_steps | now | tol``; indices and masks are stored
    as float64, which holds every int32 index and every 0/1 exactly.
    :func:`_drain_unpack` is the inverse the program applies.
    """
    return np.concatenate(
        [caps, node_valid, run_idx.reshape(-1), run_valid.reshape(-1),
         used_now.reshape(-1), q_idx, q_from, leave_node, leave_slot,
         old_starts.reshape(-1), old_peaks.reshape(-1), (n_steps, now, tol)],
        dtype=np.float64)


def _drain_unpack(packed, npad: int, rmax: int, Q: int, K: int):
    """Slice :func:`_drain_pack`'s vector back into the drain operands
    ``(caps, node_valid, run_idx, run_valid, used_now, q_idx, q_from,
    leave_node, leave_slot, old_starts, old_peaks, n_steps, now, tol)`` —
    static slices, an int32 convert for indices, ``!= 0`` for masks.
    Works on a traced array inside the program and on a numpy array."""
    S = RUN_STEPS
    nr = npad * rmax
    o = 0
    parts = []
    for n in (npad, npad, nr, nr, S * npad, Q, Q, S, S, S * K, S * K):
        parts.append(packed[o:o + n])
        o += n
    caps, nv, ri, rv, un, qi, qf, ln, ls, ost, opk = parts
    i32 = "int32"
    return (caps, nv != 0, ri.astype(i32).reshape(npad, rmax),
            (rv != 0).reshape(npad, rmax), un.reshape(S, npad),
            qi.astype(i32), qf.astype(i32), ln.astype(i32), ls.astype(i32),
            ost.reshape(S, K), opk.reshape(S, K), packed[o].astype(i32),
            packed[o + 1], packed[o + 2])


def _drain_kernel(masked: bool, select: str):
    """Build (once) the jitted one-dispatch greedy drain program.

    The program drains a same-instant *run* of events in order — up to
    ``RUN_STEPS`` steps in one dispatch; a one-event drain is its
    one-step case.  Step ``k``:

    1. takes the event's lane off its node (``leave_node[k]``, slot
       ``leave_slot[k]`` of the pre-run resident table; an out-of-range
       node is no leave) and recomputes that node's base residual from
       the residents left — the same masked sum as every other row, not
       the released envelope added back;
    2. makes visible the queue lanes with ``q_from <= k`` (the pre-run
       queue has 0, the lanes a step appends — DAG children, a re-queued
       retry — that step);
    3. runs the greedy drain below to exhaustion.

    A resident that leaves at step ``k`` with a staged re-plan holds the
    re-plan in the device buffers already (its queue lane reads it from
    step ``k`` on); as a resident it is evaluated with ``old_starts`` /
    ``old_peaks``, the envelope it was admitted with.  The lanes placed
    in earlier steps of the call count through the carried ``placed``
    usage (their admission time is ``now`` exactly), and at grid point 0
    through the host's ``used_now[k]`` plus their allocation at ``rel =
    0``, which is exact on any float64.

    The greedy drain of one step is a ``lax.while_loop`` whose carry
    holds the residual tensor ``resid[n, q, g]`` and the packed placement
    list.  Each iteration:

    1. recomputes ``fits[n, q]`` from the carried residuals (the in-loop
       equivalent of refreshing every invalidated fits entry),
    2. places a maximal *order-preserving independent prefix* of the
       queue in one step — the batched top-k fast path.  Residual
       monotonicity (placements only shrink residuals) proves the picks
       independent: walking lanes in queue order, every fitting lane
       whose fitting-node set is disjoint from the nodes already used
       *this iteration* would be chosen identically by the sequential
       greedy, because none of the entries its decision reads have
       changed.  The prefix stops at the first fitting lane whose fit
       set intersects a used node — its decision could differ after the
       update, so it is re-evaluated next iteration,
    3. scatter-subtracts each placed lane's windowed envelope from its
       node's residual rows and clears the lane's active bit,

    until no visible queued lane fits.  The placed lanes' admission
    times are scatter-written into the donated ``admit_t`` buffer in the
    same dispatch, so the host does zero follow-up device work per drain.

    Callers shrink the lane axis before dispatching: residual
    monotonicity means a lane that does not fit any node on the *base*
    residuals can never place within the drain, so
    :meth:`AdmissionState.drain` restricts a wide queue's dispatch to the
    lanes the (incrementally refreshed) fits cache marks as fitting
    somewhere.  The restriction is exact, not approximate: unfit lanes
    contribute nothing to the independent-prefix bookkeeping (their
    ``onehot``/``conflict`` entries are identically False), so the placed
    set and order are bitwise those of the full-queue program.

    ``select`` (static) picks the node rule: ``"first"`` — first fitting
    node in row order (the ClusterSim greedy; device ``argmax`` over the
    boolean column, identical tie-break to ``np.argmax``) — or
    ``"headroom"`` — most post-placement head-room ``minresid - peak``,
    first on ties (the ElasticPlanner rule).

    Signature: the six device-resident lane buffers (``admit_t``
    donated) and the packed per-call operands of :func:`_drain_pack`,
    with the padded node, run and queue widths ``(npad, rmax, Q)`` as
    static arguments — the bucket triple the shapes already keyed, so
    the compiled-program count is unchanged.  Returns one int32 vector
    ``out_lane (Q) | out_node (Q) | out_step (Q) | count`` and the
    updated ``admit_t``: one buffer in, one buffer back.
    """
    key = ("drain", masked, select)
    if key in _KERNEL_CACHE:
        return _KERNEL_CACHE[key]
    import jax
    import jax.numpy as jnp
    from jax import lax

    def drain(starts, peaks, admit_t, dur, need, grid,
              caps, node_valid, run_idx, run_valid, used_now,
              q_idx, q_from, leave_node, leave_slot, old_starts, old_peaks,
              n_steps, now, tol):
        N, R = run_idx.shape
        Q = q_idx.shape[0]
        G = grid.shape[1]
        B = starts.shape[0]
        S = leave_node.shape[0]
        # Base residuals from the pre-run residents — elementwise the
        # same float64 program as the columns kernel.  A leaving
        # resident's slot carries the envelope it was admitted with.
        flat = run_idx.reshape(-1)
        left = jnp.where(leave_node < N, leave_node * R + leave_slot, N * R)
        rs = starts[flat].at[left].set(old_starts, mode="drop")
        rp = peaks[flat].at[left].set(old_peaks, mode="drop")
        rt0 = admit_t[flat]
        tabs = (now + grid[q_idx]).reshape(-1)        # (Q*G,) absolute
        rel = tabs[None, :] - rt0[:, None]
        alloc = _drain_alloc_chain(rs, rp, jnp.maximum(rel, 0.0))
        if masked:
            rdur = dur[flat]
            active0 = (rel >= 0.0) & (rel < rdur[:, None] + 1e-9)
            alloc = jnp.where(active0, alloc, 0.0)
        alloc = jnp.where(run_valid.reshape(-1)[:, None], alloc, 0.0)
        alloc = alloc.reshape(N, R, -1)
        # The step from which each resident slot is gone (S: stays).
        gone = jnp.full((N * R,), S, jnp.int32).at[left].set(
            jnp.arange(S, dtype=jnp.int32), mode="drop").reshape(N, R)
        need_q = need[q_idx]                          # (Q, G)
        if select == "headroom":
            peak_q = jnp.max(peaks[q_idx], axis=1)    # (Q,)
        # A lane placed inside this drain has admit_t == now *exactly*,
        # so its contribution at grid point (q, g) is evaluated at
        # rel = (now + grid[q, g]) - now — kept in this form (not
        # simplified to grid[q, g]) so the arithmetic matches what the
        # columns kernel computes for that resident afterwards, bitwise.
        prel = tabs - now
        prelc = jnp.maximum(prel, 0.0)
        nrange = jnp.arange(N, dtype=jnp.int32)
        qrange = jnp.arange(Q, dtype=jnp.int32)

        def greedy(k, resid, active, placed, out_lane, out_node, out_step,
                   count):
            def cond(st):
                return ~st[-1]

            def body(st):
                resid, active, placed, out_lane, out_node, out_step, \
                    count, _ = st
                fits = jnp.all(need_q[None, :, :] <= resid + tol, axis=-1)
                fits = fits & node_valid[:, None] & active[None, :]
                anyfit = fits.any(axis=0)                 # (Q,)
                done = ~anyfit.any()
                if select == "first":
                    node_q = jnp.argmax(fits, axis=0).astype(jnp.int32)
                else:
                    head = resid.min(axis=-1) - peak_q[None, :]
                    node_q = jnp.argmax(
                        jnp.where(fits, head, -jnp.inf), axis=0
                    ).astype(jnp.int32)
                # Order-preserving independent prefix: optimistically
                # every fitting lane before the first whose fit set
                # touches an already-used node.  Before that first
                # conflict the optimistic used-set equals the sequential
                # one, so the cut point (and every placement before it)
                # is exact.
                onehot = (nrange[:, None] == node_q[None, :]) \
                    & anyfit[None, :]
                before = (jnp.cumsum(onehot, axis=1, dtype=jnp.int32)
                          - onehot.astype(jnp.int32)) > 0
                conflict = anyfit & (fits & before).any(axis=0)
                first_conf = jnp.where(
                    conflict.any(), jnp.argmax(conflict).astype(jnp.int32),
                    jnp.int32(Q))
                place = anyfit & (qrange < first_conf) & ~done
                pos = count + jnp.cumsum(place, dtype=jnp.int32) - 1
                slot = jnp.where(place, pos, Q)
                out_lane = out_lane.at[slot].set(q_idx, mode="drop")
                out_node = out_node.at[slot].set(node_q, mode="drop")
                out_step = out_step.at[slot].set(k, mode="drop")
                count = count + place.sum(dtype=jnp.int32)
                # Scatter-subtract the placed envelopes: at most one lane
                # per node per iteration by construction (a second lane
                # fitting a used node is past the conflict cut), so a
                # node -> queue-col scatter is collision-free.
                col = jnp.full((N,), Q, jnp.int32).at[
                    jnp.where(place, node_q, N)].set(qrange, mode="drop")
                hasl = col < Q
                gl = q_idx[jnp.where(hasl, col, 0)]
                pal = _drain_alloc_chain(
                    starts[gl], peaks[gl],
                    jnp.broadcast_to(prelc[None, :], (N, prelc.shape[0])))
                if masked:
                    pact = (prel[None, :] >= 0.0) \
                        & (prel[None, :] < dur[gl][:, None] + 1e-9)
                    pal = jnp.where(pact, pal, 0.0)
                pal = jnp.where(hasl[:, None], pal, 0.0)
                resid = resid - pal.reshape(N, Q, G)
                placed = placed + pal
                active = active & ~place
                return (resid, active, placed, out_lane, out_node,
                        out_step, count, done)

            return lax.while_loop(
                cond, body, (resid, active, placed, out_lane, out_node,
                             out_step, count, jnp.bool_(False)))[1:7]

        def step(k, st):
            usage, waiting, placed, out_lane, out_node, out_step, count = st
            # Step k's leave: recompute the node's row from its residents
            # left (an out-of-range node is no leave, and drops).
            n = leave_node[k]
            row = jnp.where((gone[jnp.minimum(n, N - 1)] > k)[:, None],
                            alloc[jnp.minimum(n, N - 1)], 0.0).sum(axis=0)
            usage = usage.at[n].set(row, mode="drop")
            resid = _at_now(
                (caps[:, None] - (usage + placed)).reshape(N, Q, G),
                caps - (used_now[k] + placed[:, 0]))
            visible = waiting & (q_from <= k)
            active, placed, out_lane, out_node, out_step, count = greedy(
                k, resid, visible, placed, out_lane, out_node, out_step,
                count)
            waiting = waiting & ~(visible & ~active)
            return (usage, waiting, placed, out_lane, out_node, out_step,
                    count)

        init = (alloc.sum(axis=1), q_from < S,
                jnp.zeros((N, Q * G), alloc.dtype),
                jnp.full((Q,), B, jnp.int32), jnp.zeros((Q,), jnp.int32),
                jnp.zeros((Q,), jnp.int32), jnp.int32(0))
        out = lax.fori_loop(0, n_steps, step, init)
        out_lane, out_node, out_step, count = out[3:]
        # Same-dispatch admit-time scatter: unused slots keep the
        # out-of-range fill B and drop.
        admit_new = admit_t.at[out_lane].set(now, mode="drop")
        return out_lane, out_node, out_step, count, admit_new

    @functools.partial(jax.jit, donate_argnums=(2,),
                       static_argnames=("npad", "rmax", "Q"))
    def kernel(starts, peaks, admit_t, dur, need, grid, packed, *,
               npad: int, rmax: int, Q: int):
        *out, admit_new = drain(
            starts, peaks, admit_t, dur, need, grid,
            *_drain_unpack(packed, npad, rmax, Q, starts.shape[1]))
        out_lane, out_node, out_step, count = out
        return (jnp.concatenate([out_lane, out_node, out_step, count[None]]),
                admit_new)

    _KERNEL_CACHE[key] = kernel
    return kernel


def _drain_kernel_sharded(masked: bool, select: str, shard: int):
    """Node-sharded drain: ``shard_map`` over the node axis of the fits
    matrix — nodes sharded, queued lanes replicated.

    Each shard carries its local residual block ``(N/shard, Q, G)``; per
    iteration the global "first fitting (queue-order, node-order) pair"
    is found with two collectives: a vectorized ``psum`` OR-reduction
    over the node axis for per-lane any-fit, then a ``pmin`` min-index
    reduction for the winning node.  For ``select="headroom"`` each shard
    contributes its (best head-room, lowest index attaining it) pair
    through an ``all_gather`` and every shard reduces the gathered pairs
    identically — first-on-ties, matching ``np.argmax``.  (The TPU
    lowers float64 all-reduces only for sums, so the head-room maximum
    cannot be a ``pmax``.)  The owning shard
    scatter-subtracts the placed envelope from its local block; the
    packed placement list is replicated.  One placement per iteration —
    selection is globally ordered, so the single-device batched-prefix
    fast path is not needed for correctness, and placements match the
    unsharded program bitwise (per-node arithmetic is identical; only
    node *selection* is distributed, and it reduces over exact indices).
    """
    key = ("drain_sharded", masked, select, shard)
    if key in _KERNEL_CACHE:
        return _KERNEL_CACHE[key]
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:shard]), ("nodes",))

    def core(starts, peaks, admit_t, dur, need, grid, caps, node_valid,
             run_idx, run_valid, used_now, q_idx, q_valid, now, tol):
        Nl, R = run_idx.shape
        Q = q_idx.shape[0]
        G = grid.shape[1]
        B = starts.shape[0]
        off = lax.axis_index("nodes").astype(jnp.int32) * Nl
        flat = run_idx.reshape(-1)
        rs = starts[flat]
        rp = peaks[flat]
        rt0 = admit_t[flat]
        tabs = (now + grid[q_idx]).reshape(-1)
        rel = tabs[None, :] - rt0[:, None]
        alloc = _drain_alloc_chain(rs, rp, jnp.maximum(rel, 0.0))
        if masked:
            rdur = dur[flat]
            active0 = (rel >= 0.0) & (rel < rdur[:, None] + 1e-9)
            alloc = jnp.where(active0, alloc, 0.0)
        alloc = jnp.where(run_valid.reshape(-1)[:, None], alloc, 0.0)
        usage = alloc.reshape(Nl, R, -1).sum(axis=1)
        resid0 = _at_now((caps[:, None] - usage).reshape(Nl, Q, G),
                         caps - used_now)
        need_q = need[q_idx]
        if select == "headroom":
            peak_q = jnp.max(peaks[q_idx], axis=1)
        prel = tabs - now
        prelc = jnp.maximum(prel, 0.0)
        big = jnp.int32(Nl * shard)
        gidx = off + jnp.arange(Nl, dtype=jnp.int32)

        def cond(st):
            return ~st[5]

        def body(st):
            resid, active, out_lane, out_node, count, _ = st
            fits = jnp.all(need_q[None, :, :] <= resid + tol, axis=-1)
            fits = fits & node_valid[:, None] & active[None, :]
            anyfit = lax.psum(fits.any(axis=0).astype(jnp.int32),
                              "nodes") > 0
            done = ~anyfit.any()
            qsel = jnp.argmax(anyfit).astype(jnp.int32)
            colf = fits[:, qsel]
            if select == "first":
                nsel = lax.pmin(jnp.where(colf, gidx, big).min(), "nodes")
            else:
                minres = resid[:, qsel, :].min(axis=-1)
                head = jnp.where(colf, minres - peak_q[qsel], -jnp.inf)
                lbest = head.max()
                lidx = jnp.where(colf & (head == lbest), gidx, big).min()
                heads = lax.all_gather(lbest, "nodes")     # (shard,)
                idxs = lax.all_gather(lidx, "nodes")
                nsel = jnp.where(heads == heads.max(), idxs, big).min()
            place = ~done
            slot = jnp.where(place, count, Q)
            out_lane = out_lane.at[slot].set(q_idx[qsel], mode="drop")
            out_node = out_node.at[slot].set(nsel, mode="drop")
            gl = q_idx[qsel]
            pal = _drain_alloc_chain(starts[gl][None], peaks[gl][None],
                                     prelc[None, :])
            if masked:
                pact = (prel >= 0.0) & (prel < dur[gl] + 1e-9)
                pal = jnp.where(pact[None, :], pal, 0.0)
            lrow = nsel - off
            own = place & (lrow >= 0) & (lrow < Nl)
            resid = resid.at[jnp.where(own, lrow, Nl)].add(
                -pal.reshape(Q, G), mode="drop")
            active = active.at[jnp.where(place, qsel, Q)].set(
                False, mode="drop")
            count = count + place.astype(jnp.int32)
            return (resid, active, out_lane, out_node, count, done)

        init = (resid0, q_valid, jnp.full((Q,), B, jnp.int32),
                jnp.zeros((Q,), jnp.int32), jnp.int32(0), jnp.bool_(False))
        _, _, out_lane, out_node, count, _ = lax.while_loop(
            cond, body, init)
        return out_lane, out_node, count

    smapped = jax.shard_map(
        core, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P("nodes"), P("nodes"),
                  P("nodes"), P("nodes"), P("nodes"), P(), P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def kernel(starts, peaks, admit_t, dur, need, grid, caps, node_valid,
               run_idx, run_valid, used_now, q_idx, q_valid, now, tol):
        out_lane, out_node, count = smapped(
            starts, peaks, admit_t, dur, need, grid, caps, node_valid,
            run_idx, run_valid, used_now, q_idx, q_valid, now, tol)
        admit_new = admit_t.at[out_lane].set(now, mode="drop")
        return out_lane, out_node, count, admit_new

    _KERNEL_CACHE[key] = kernel
    return kernel


def _scatter_rows_fn():
    """Donated-buffer row scatter: the in-place device update primitive."""
    if "scatter" in _KERNEL_CACHE:
        return _KERNEL_CACHE["scatter"]
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(buf, rows, vals):
        return buf.at[rows].set(vals)

    _KERNEL_CACHE["scatter"] = scatter
    return scatter


class AdmissionState:
    """Fits matrix + invalidation protocol over packed ``(B, K)`` envelopes.

    Lanes (queued/resident jobs) carry a packed envelope, a relative
    admission grid with its precomputed ``need`` evaluation, a placement
    time and an active-window duration; nodes carry a capacity and the
    list of resident lanes.  ``columns()`` refreshes every invalid
    ``(node, lane)`` entry for the requested lanes — one fused dispatch on
    the jitted backend — and returns the fits matrix slice; ``place`` /
    ``release`` / ``update_lane`` / ``add_node`` / ``remove_node`` keep
    the validity mask honest (the churn test drives exactly this contract).

    ``use_dur=False`` selects the elastic planner's conservative
    count-forever residual (``usage_over`` with ``dur=None``).
    """

    # Max candidate lanes per drain dispatch.  Deep backlogs routinely
    # have hundreds of lanes that *fit somewhere* while capacity admits
    # only a few — capping the dispatch keeps the while-loop program's
    # queue axis (and its padded pow2 bucket) small; the exact
    # continuation loop in :meth:`drain` re-dispatches in the rare case
    # more than DRAIN_CAP lanes were simultaneously placeable.  Queues
    # at or below the cap skip the candidate pre-filter and go straight
    # into the program: one dispatch per drain, no refresh round-trip.
    DRAIN_CAP = 256

    def __init__(self, caps: Sequence[float], K: int, G: int,
                 backend: str = "fused", use_dur: bool = True,
                 tol: float = 1e-9, shard: Optional[int] = None):
        if backend not in ("fused", "numpy"):
            raise ValueError(f"unknown admission backend: {backend!r}")
        if shard is not None:
            if backend != "fused":
                raise ValueError("shard= requires backend='fused'")
            shard = int(shard)
            if shard < 1:
                raise ValueError(f"shard must be >= 1, got {shard}")
            import jax
            have = len(jax.devices())
            if have < shard:
                raise ValueError(
                    f"shard={shard} needs {shard} devices but only {have} "
                    f"are visible — set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={shard} "
                    f"before jax initializes its backend")
        self.shard = shard
        self.stats = {"drains": 0, "drain_steps": 0, "drain_dispatches": 0}
        self.backend = backend
        self.use_dur = bool(use_dur)
        self.tol = float(tol)
        self.K = int(K)
        self.G = int(G)
        self.caps = np.asarray(caps, np.float64).copy()
        N = len(self.caps)
        self.running: List[List[int]] = [[] for _ in range(N)]
        # Lane state (grows via add_lanes).
        self.starts = np.zeros((0, self.K), np.float64)
        self.peaks = np.zeros((0, self.K), np.float64)
        self.need = np.zeros((0, self.G), np.float64)
        self.grid = np.zeros((0, self.G), np.float64)
        self.admit_t = np.zeros((0,), np.float64)
        self.dur = np.zeros((0,), np.float64)
        # The shared runtime state: fits matrix + validity mask.
        self.fits = np.zeros((N, 0), bool)
        self.minresid = np.zeros((N, 0), np.float64)
        self.valid = np.zeros((N, 0), bool)
        self._now: Optional[float] = None
        # Every admission instant so far a whole number: the device's
        # own float64 is then exact at ``now`` (see :meth:`_used_now`).
        self._whole_times = True
        self._dirty_dev = True  # device mirrors need a (re)upload

    # ------------------------------------------------------------- lane mgmt
    @property
    def B(self) -> int:
        return int(self.starts.shape[0])

    @property
    def N(self) -> int:
        return int(self.caps.shape[0])

    def ensure_k(self, k: int):
        """Grow the packed segment axis (rare: a new lane with more
        segments than any seen).  Padding follows the PackedEnvelopes
        convention — sentinel starts, replicated last peak — so existing
        lanes evaluate identically."""
        if k <= self.K:
            return
        from repro.core.envelope import PAD_START
        pad = k - self.K
        B = self.B
        self.starts = np.concatenate(
            [self.starts, np.full((B, pad), PAD_START)], axis=1)
        last = (self.peaks[:, -1:] if self.K else np.zeros((B, 1)))
        self.peaks = np.concatenate(
            [self.peaks, np.repeat(last, pad, axis=1)], axis=1)
        self.K = k
        self._dirty_dev = True

    def add_lanes(self, starts, peaks, need, grid,
                  dur=None) -> np.ndarray:
        """Append lanes; returns their indices.  New entries are invalid.
        Each lane's ``grid`` (its admission horizon, relative) starts at
        0: the device programs take that point from the host
        (:meth:`_used_now`)."""
        starts = np.asarray(starts, np.float64).reshape(-1, self.K)
        n = starts.shape[0]
        grid = np.asarray(grid, np.float64).reshape(n, self.G)
        if np.any(grid[:, 0] != 0.0):
            raise ValueError("admission grids must start at 0 (the "
                             "admission instant)")
        self.starts = np.concatenate([self.starts, starts])
        self.peaks = np.concatenate(
            [self.peaks, np.asarray(peaks, np.float64).reshape(n, self.K)])
        self.need = np.concatenate(
            [self.need, np.asarray(need, np.float64).reshape(n, self.G)])
        self.grid = np.concatenate(
            [self.grid, np.asarray(grid, np.float64).reshape(n, self.G)])
        self.admit_t = np.concatenate([self.admit_t, np.zeros(n)])
        self.dur = np.concatenate(
            [self.dur,
             np.full(n, np.inf) if dur is None
             else np.asarray(dur, np.float64).reshape(n)])
        pad = np.zeros((self.N, n), bool)
        self.fits = np.concatenate([self.fits, pad], axis=1)
        self.valid = np.concatenate([self.valid, pad.copy()], axis=1)
        self.minresid = np.concatenate(
            [self.minresid, np.zeros((self.N, n))], axis=1)
        self._dirty_dev = True
        return np.arange(self.B - n, self.B)

    def update_lane(self, lane: int, starts, peaks, need):
        """Re-plan a lane; its column is invalid on every node.

        If the lane is currently *resident* somewhere (a live re-size
        rather than a queued retry), that node's residual changed for
        every queued lane — its whole row is invalidated too.
        """
        self.starts[lane] = starts
        self.peaks[lane] = peaks
        self.need[lane] = need
        self.valid[:, lane] = False
        for ni, run in enumerate(self.running):
            if lane in run:
                self.valid[ni] = False
        self._push_lane(lane)

    # ------------------------------------------------------------- node mgmt
    def add_node(self, cap: float) -> int:
        self.caps = np.concatenate([self.caps, [float(cap)]])
        self.running.append([])
        B = self.B
        self.fits = np.concatenate([self.fits, np.zeros((1, B), bool)])
        self.valid = np.concatenate([self.valid, np.zeros((1, B), bool)])
        self.minresid = np.concatenate([self.minresid, np.zeros((1, B))])
        return self.N - 1

    def remove_node(self, ni: int) -> List[int]:
        """Drop a node row; returns the lanes that were resident on it."""
        evicted = self.running[ni]
        self.caps = np.delete(self.caps, ni)
        del self.running[ni]
        self.fits = np.delete(self.fits, ni, axis=0)
        self.valid = np.delete(self.valid, ni, axis=0)
        self.minresid = np.delete(self.minresid, ni, axis=0)
        return evicted

    # ----------------------------------------------------------- invalidation
    def sync_now(self, now: float):
        """Advance the clock; residuals are time functions, so a new ``now``
        invalidates every cached entry."""
        if self._now is None or now != self._now:
            self.valid[:] = False
            self._now = float(now)
            self._whole_times &= self._now.is_integer()

    def place(self, ni: int, lane: int, now: float):
        """Resident set grows: only the node's True entries can change
        (residual shrank monotonically), so False entries stay valid."""
        self.running[ni].append(lane)
        self.admit_t[lane] = now
        self._whole_times &= float(now).is_integer()
        self.valid[ni] &= ~self.fits[ni]
        self._push_admit(lane)

    def release(self, ni: int, lane: int):
        """Resident set shrinks: the residual grew, False entries may flip
        True — the node's whole column is invalid."""
        self.running[ni].remove(lane)
        self.valid[ni] = False

    def is_valid(self, ni: int, lane: int) -> bool:
        return bool(self.valid[ni, lane])

    # ---------------------------------------------------------------- refresh
    def columns(self, now: float, lanes: Sequence[int],
                sub: int = 8) -> np.ndarray:
        """Fits matrix slice ``(N, len(lanes))``, refreshed where invalid.

        One fused dispatch per call on the jitted backend: every invalid
        ``(node, lane)`` entry across all nodes is recomputed at once.
        ``sub`` sets the lane-bucket subdivision (see
        :func:`repro.core.fleet.pad_lane_axis`).
        """
        self.sync_now(now)
        lanes = np.asarray(lanes, np.int64)
        stale = ~self.valid[:, lanes]
        if stale.any():
            todo = lanes[stale.any(axis=0)]
            nodes = np.nonzero(stale.any(axis=1))[0]
            if self.backend == "numpy":
                self._refresh_numpy(nodes, todo)
            else:
                self._refresh_fused(nodes, todo, sub)
            self.valid[np.ix_(nodes, todo)] = True
        return self.fits[:, lanes]

    def _refresh_numpy(self, nodes: np.ndarray, lanes: np.ndarray):
        """Float64 host reference: per-node :func:`fits_column` — the
        exact arithmetic of the packed ClusterSim engine."""
        grid_abs = self._now + self.grid[lanes]
        for ni in nodes:
            run = self.running[ni]
            ok, resid = fits_column(
                self.caps[ni], self.starts[run], self.peaks[run],
                self.admit_t[run], self.need[lanes], grid_abs,
                dur=self.dur[run] if self.use_dur else None, tol=self.tol)
            self.fits[ni, lanes] = ok
            self.minresid[ni, lanes] = resid.min(axis=-1)

    # ------------------------------------------------------------ fused path
    def _dev_sync(self):
        """(Re)upload the packed lane state to the device (bulk path; the
        incremental paths go through donated scatters).

        Contract: after the initial upload this must never fire again on
        node join/leave — churn only changes the *operands* of the next
        dispatch, never the device-resident lane state
        (``tests/test_contracts.py`` pins the tag at one per replay).
        """
        import jax.numpy as jnp
        record_dispatch("admission.dev_sync")
        self._dstarts = jnp.asarray(self.starts)
        self._dpeaks = jnp.asarray(self.peaks)
        self._dneed = jnp.asarray(self.need)
        self._dgrid = jnp.asarray(self.grid)
        self._dadmit = jnp.asarray(self.admit_t)
        self._ddur = jnp.asarray(self.dur)
        self._dirty_dev = False

    def _push_lane(self, lane: int):
        if self.backend == "numpy" or self._dirty_dev:
            return
        self._push_lanes(np.asarray([lane]))

    def _push_lanes(self, lanes: np.ndarray):
        """In-place device update of re-planned lanes (donated buffers)."""
        if self.backend == "numpy" or self._dirty_dev:
            return
        import jax
        scatter = _scatter_rows_fn()
        record_dispatch("admission.scatter", 3)
        with jax.enable_x64(True):
            import jax.numpy as jnp
            rows = jnp.asarray(np.asarray(lanes, np.int32))
            self._dstarts = scatter(self._dstarts, rows,
                                    jnp.asarray(self.starts[lanes]))
            self._dpeaks = scatter(self._dpeaks, rows,
                                   jnp.asarray(self.peaks[lanes]))
            self._dneed = scatter(self._dneed, rows,
                                  jnp.asarray(self.need[lanes]))

    def _push_admit(self, lane: int):
        if self.backend == "numpy" or self._dirty_dev:
            return
        import jax
        scatter = _scatter_rows_fn()
        record_dispatch("admission.scatter")
        with jax.enable_x64(True):
            import jax.numpy as jnp
            self._dadmit = scatter(
                self._dadmit, jnp.asarray(np.asarray([lane], np.int32)),
                jnp.asarray(self.admit_t[lane:lane + 1]))

    def _used_now(self, runs: Sequence[List[int]], now: float,
                  n: int, gone: Optional[np.ndarray] = None) -> np.ndarray:
        """Each node's allocation in use at the instant ``now``: the sum
        over its residents ``runs[i]`` of their envelopes at ``now - t0``
        (with ``use_dur``, only inside ``[t0, t0 + dur)``), as ``(n,)``
        with zeros past ``len(runs)`` — the device programs' arithmetic,
        done here in the host's IEEE float64.  With ``gone`` (per
        resident, in the order of ``runs``: the step of a run from which
        it has left) it is ``(RUN_STEPS, n)``, row ``k`` without the
        residents gone by step ``k`` — each row summed in the same order
        as the ``(n,)`` form over the residents left.

        The programs evaluate residents at ``now + grid`` on the device.
        At the first grid point, ``now`` itself, ``now - t0`` often lies
        exactly on a segment start: KS+ re-timed plans start segments at
        whole samples, and events fall whole samples after admissions.
        A TPU's float64 is emulated in about 49 bits, not IEEE: once
        ``now`` and ``t0`` carry fractions (a node leaves at any instant
        and its evicted tasks are re-admitted then), it can round such a
        tie to the other segment than the host loops and the legacy
        engine do, and admit differently.  Every other grid point lies
        off the starts by far more than that rounding.  While every
        instant is a whole number, ``now - t0`` is exact on the device
        too, and this returns NaN: the programs keep their own value."""
        if self._whole_times:
            return np.full(n if gone is None else (RUN_STEPS, n), np.nan)
        lens = [len(run) for run in runs]
        lanes = np.fromiter(itertools.chain.from_iterable(runs), np.int64,
                            sum(lens))
        rel = now - self.admit_t[lanes]
        relc = np.maximum(rel, 0.0)
        starts, peaks = self.starts[lanes], self.peaks[lanes]
        alloc = peaks[:, 0]
        for k in range(1, self.K):
            alloc = np.where(starts[:, k] <= relc, peaks[:, k], alloc)
        if self.use_dur:
            alloc = np.where((rel >= 0.0) & (rel < self.dur[lanes] + 1e-9),
                             alloc, 0.0)
        node = np.repeat(np.arange(len(runs)), lens)
        if gone is None:
            return np.bincount(node, weights=alloc, minlength=n)
        k = np.arange(RUN_STEPS)[:, None]
        return np.bincount(
            (k * n + node[None, :]).reshape(-1),
            weights=np.where(gone[None, :] > k, alloc[None, :],
                             0.0).reshape(-1),
            minlength=RUN_STEPS * n).reshape(RUN_STEPS, n)

    def _refresh_fused(self, nodes: np.ndarray, lanes: np.ndarray,
                       sub: int = 8):
        """One fused XLA dispatch for every invalid (node, lane) entry.

        Only the stale node rows enter the dispatch — after a placement,
        that is a single node over the previously-True lanes, not the
        whole matrix.
        """
        import jax
        import jax.numpy as jnp

        from repro.core.fleet import pad_lane_axis

        kernel = _fused_kernel(self.use_dur)
        # Only wide (execution-bound) refreshes reach this kernel — the
        # narrow compile-bound ones route to the host oracle in
        # :meth:`columns` — so shapes stay exact: stale rows only, run
        # axis padded pow2.  The queue axis is already coarse by the
        # time a refresh is wide (pow2 buckets at >256 lanes), so the
        # compiled-shape count stays small without extra padding.
        sel = [self.running[ni] for ni in nodes]
        rmax = max(max((len(r) for r in sel), default=0), 1)
        rmax = 1 << (rmax - 1).bit_length()
        run_idx = np.zeros((len(nodes), rmax), np.int32)
        run_valid = np.zeros((len(nodes), rmax), bool)
        for i, run in enumerate(sel):
            run_idx[i, :len(run)] = run
            run_valid[i, :len(run)] = True
        (q_idx,) = pad_lane_axis(
            (np.asarray(lanes, np.int32),), (0,), lo=8, fine=True, sub=sub)
        nq = len(lanes)
        record_dispatch("admission.columns")
        with jax.enable_x64(True):
            if self._dirty_dev:
                self._dev_sync()
            # lint: allow[recompile-hazard] stale-row refreshes are execution-bound by design (see comment above): rows stay exact, only the run axis is padded
            fits, minresid = kernel(
                self._dstarts, self._dpeaks, self._dadmit, self._ddur,
                self._dneed, self._dgrid,
                jnp.asarray(self.caps[nodes]), jnp.asarray(run_idx),
                jnp.asarray(run_valid),
                jnp.asarray(self._used_now(sel, self._now, len(nodes))),
                jnp.asarray(q_idx),
                jnp.float64(self._now), jnp.float64(self.tol))
        # lint: allow[host-sync-in-hot-path] one batched readback materializes the host fits cache the drain pre-filter reads
        fits_h, minresid_h = jax.device_get((fits, minresid))
        self.fits[np.ix_(nodes, lanes)] = fits_h[:, :nq]
        self.minresid[np.ix_(nodes, lanes)] = minresid_h[:, :nq]

    # ------------------------------------------------------------------ drain
    def drain(self, now: float, lanes: Sequence[int],
              select: str = "first") -> List[tuple]:
        """Greedy drain at ``now`` over ``lanes`` (queue order): place
        lanes until none fits, returning ``[(lane, node_row), ...]`` in
        decision order.

        On the fused backend this is ONE device dispatch for the whole
        drain — the one-step case of the jitted run program of
        :func:`_drain_kernel` (node-sharded via
        :func:`_drain_kernel_sharded` when the state was built with
        ``shard=``), including the donated-buffer admit-time scatter for
        every placement.  On the numpy backend it is the host reference
        loop over :meth:`columns` — the oracle the device program is
        differentially pinned against.

        ``select="first"`` is the ClusterSim rule (first fitting node in
        row order); ``select="headroom"`` is the ElasticPlanner rule
        (most post-placement head-room, first on ties).  Decision
        equivalence with the sequential greedy holds because placements
        only shrink residuals: an unfit lane can never become fit within
        one drain, and a fitting lane whose fitting-node set is disjoint
        from the drain's earlier placements reads only unchanged state.

        Queue routing (fused, unsharded): a queue of at most
        ``DRAIN_CAP`` lanes — a DAG dependency frontier, an elastic
        re-admission batch — goes straight into the program, whole:
        exactly one dispatch per drain, no refresh round-trip, and the
        per-dispatch cost is bounded by the cap's pow2 bucket.  A wider
        backlog first runs the candidate pre-filter: base-residual fits
        of the whole queue from :meth:`columns` — the incremental,
        validity-cached refresh, which within a same-``now`` event batch
        recomputes only the released node's row instead of the full
        matrix — and the program dispatches over *just the lanes that
        fit somewhere*.  The restriction is exact by residual
        monotonicity (placements only shrink residuals, so a lane unfit
        on the base residuals can never place within the drain), and it
        collapses the dispatch's queue axis from the whole backlog to
        the handful of contenders: event-dense flat replays, where most
        drains place nothing or one lane out of hundreds queued, run at
        stale-row refresh cost instead of full-program cost.  The
        sharded program keeps the full queue — its point is scaling the
        (nodes x queue) matrix itself, and its fits stay inside the
        ``shard_map``.
        """
        if _obs.enabled:
            q = int(np.asarray(lanes).size)
            with _obs.span("admission.drain", backend=self.backend,
                           q=q, steps=1) as sp:
                out = self._drain(now, lanes, select)
                sp.add(placed=len(out))
            return out
        return self._drain(now, lanes, select)

    def drain_run(self, now: float, lanes: Sequence[int],
                  events: Sequence[tuple],
                  select: str = "first") -> List[List[tuple]]:
        """Greedy drains of a same-instant run of events, in order;
        returns each event's placements ``[(lane, node_row), ...]`` in
        decision order.

        ``events`` holds ``(node_row, lane, joins, replan)`` per event:
        ``lane`` leaves ``node_row``, its staged re-plan ``replan`` —
        ``(starts, peaks, need)``, or None — takes effect as it leaves
        (:meth:`update_lane`), and ``joins`` join the back of the queue
        in order, after ``lanes`` and the earlier events' joins.  Then
        the event's drain runs over the queue left.

        Placement for placement, this is :meth:`release`,
        :meth:`update_lane` and :meth:`drain` event by event, skipping
        the drain of an event whose queue is empty.  On the fused
        backend, unsharded, a run whose queue stays within ``DRAIN_CAP``
        is one dispatch per ``RUN_STEPS`` events — one ``admission.drain``
        span with ``steps``, the events it folds — and the host's only
        per-event work is the operands.  A wider queue, the numpy
        backend and the sharded program go event by event.
        """
        if select not in ("first", "headroom"):
            raise ValueError(f"unknown drain select rule: {select!r}")
        self.sync_now(now)
        queue = [int(x) for x in np.asarray(lanes, np.int64).reshape(-1)]
        out: List[List[tuple]] = []
        for c0 in range(0, len(events), RUN_STEPS):
            chunk = events[c0:c0 + RUN_STEPS]
            joined = [int(ji) for ev in chunk for ji in ev[2]]
            nq = len(queue) + len(joined)
            if not (self.backend == "fused" and not self.shard and self.N
                    and 0 < nq <= self.DRAIN_CAP):
                for ni, lane, joins, replan in chunk:
                    self._leave(ni, lane, replan)
                    queue.extend(int(ji) for ji in joins)
                    got = self.drain(now, queue, select) if (
                        queue and self.N) else []
                    out.append(got)
                    if got:
                        done = {ji for ji, _ in got}
                        queue = [ji for ji in queue if ji not in done]
                continue
            self.stats["drains"] += 1
            self.stats["drain_steps"] += len(chunk)
            if _obs.enabled:
                with _obs.span("admission.drain", backend=self.backend,
                               q=nq, steps=len(chunk)) as sp:
                    placed = self._drain_fused(now, queue, select, chunk)
                    sp.add(placed=sum(len(p) for p in placed))
            else:
                placed = self._drain_fused(now, queue, select, chunk)
            out.extend(placed)
            done = {ji for p in placed for ji, _ in p}
            queue = [ji for ji in queue + joined if ji not in done]
        return out

    def _leave(self, ni: Optional[int], lane: Optional[int], replan):
        """A run event's lane leaves its node row, re-planned if staged."""
        if ni is None:
            return
        self.release(ni, lane)
        if replan is not None:
            self.update_lane(lane, *replan)

    def _drain(self, now: float, lanes: Sequence[int],
               select: str) -> List[tuple]:
        if select not in ("first", "headroom"):
            raise ValueError(f"unknown drain select rule: {select!r}")
        self.sync_now(now)
        self.stats["drains"] += 1
        self.stats["drain_steps"] += 1
        lanes = [int(x) for x in np.asarray(lanes, np.int64).reshape(-1)]
        if not lanes or self.N == 0:
            return []
        if self.backend == "numpy":
            return self._drain_host(now, lanes, select)
        if self.shard:
            return self._drain_fused(now, lanes, select)[0]
        placed_all: List[tuple] = []
        remaining = lanes
        while True:
            if len(remaining) <= self.DRAIN_CAP:
                # Narrow queue: the whole thing is the dispatch.
                placed_all.extend(
                    self._drain_fused(now, remaining, select)[0])
                break
            idx = np.nonzero(
                self.columns(now, remaining).any(axis=0))[0]
            if idx.size == 0:
                break
            cand = [remaining[i] for i in idx[:self.DRAIN_CAP]]
            placed = self._drain_fused(now, cand, select)[0]
            placed_all.extend(placed)
            if idx.size <= self.DRAIN_CAP or not placed:
                # A single chunk held every candidate — the kernel's own
                # termination condition verified exhaustion — or the
                # kernel disagreed with the cache inside the float64
                # grazing band (precision contract) and made no progress.
                break
            got = {ji for ji, _ in placed}
            remaining = [ji for ji in remaining if ji not in got]
        return placed_all

    def _drain_host(self, now: float, lanes: List[int],
                    select: str) -> List[tuple]:
        """Host reference drain: the exact per-placement columns/argmax
        loop the engines ran before the device program existed."""
        placed: List[tuple] = []
        if select == "first":
            remaining = list(lanes)
            while remaining:
                M = self.columns(now, remaining)
                anyfit = M.any(axis=0)
                if not anyfit.any():
                    break
                col = int(np.argmax(anyfit))
                ni = int(np.argmax(M[:, col]))
                lane = remaining.pop(col)
                self.place(ni, lane, now)
                placed.append((lane, ni))
        else:
            for lane in lanes:
                col = self.columns(now, [lane])[:, 0]
                if not col.any():
                    continue
                head = self.minresid[:, lane] - float(self.peaks[lane].max())
                ni = int(np.argmax(np.where(col, head, -np.inf)))
                self.place(ni, lane, now)
                placed.append((lane, ni))
        return placed

    def _drain_fused(self, now: float, lanes: List[int], select: str,
                     events: Sequence[tuple] = ((None, None, (), None),)
                     ) -> List[List[tuple]]:
        """One-dispatch device drain (see :func:`_drain_kernel`) of the
        run ``events`` (:meth:`drain_run`'s form, at most ``RUN_STEPS``;
        the default is one step that nothing leaves) over the queue
        ``lanes``; returns each event's placements.  The sharded program
        takes the default alone.

        The node axis is padded to a power of two (and to a multiple of
        the shard count when sharding) with ``-1e30`` capacities and a
        validity mask, the queue axis through the coarse pow2 buckets of
        :func:`repro.core.fleet.pad_lane_axis` — compilation stays
        bounded to log2-many shapes, which matters: the while-loop
        program is the most expensive compile in the repo, and the DAG
        replay's queue (the dependency frontier) wanders over two orders
        of magnitude.

        The program recomputes base residuals from ``running``/``caps``
        inside the dispatch, so node churn between drains needs no
        device-side rebuild; the placed nodes' cached True entries are
        invalidated afterwards (monotonic rule) so the next refresh
        recomputes exactly what a placement can have changed.

        Unsharded, the drain crosses the host–device boundary once each
        way: :func:`_drain_pack` writes the per-call operands (``caps``
        with its ``-1e30`` pad, ``node_valid``, the pre-run residents
        ``run_idx``/``run_valid`` flattened, each node's allocation in
        use at ``now`` per step (:meth:`_used_now`), the queue and the
        step each lane joins it, each step's leaving (node row, resident
        slot) with the envelope the lane was admitted with, the step
        count, ``now``, ``tol``) into one float64 vector, one
        ``device_put`` uploads it, the program slices it apart under the
        static triple ``(npad, rmax, Q)`` — npad pow2, rmax
        :func:`_pow4`, Q from ``pad_lane_axis`` — and hands back one
        int32 vector ``out_lane | out_node | out_step | count``.  The
        events' leaves and re-plans reach the host state (and a re-plan
        the device buffers) before the upload.  The sharded program
        keeps one operand per ``shard_map`` input spec (its node-axis
        operands are sharded, a replicated buffer would force a
        reshard), so it uploads them one by one.  Every host-to-device
        buffer counts one ``admission.drain.upload`` dispatch tag.

        Traced, three spans tile the device round trip:
        ``admission.drain.operands`` (host build and one upload, inside
        the x64 scope), ``admission.drain.launch`` (the asynchronous
        kernel enqueue) and ``admission.drain.readback`` (leaving the x64
        scope and the one ``device_get``).
        """
        import jax
        import jax.numpy as jnp

        from repro.core.fleet import pad_lane_axis

        obs_enabled = _obs.enabled
        if obs_enabled:
            phase = _obs.span("admission.drain.operands").__enter__()
        N = self.N
        npad = 1 << max(N - 1, 0).bit_length()
        if self.shard:
            npad = max(npad, self.shard)
            npad = -(-npad // self.shard) * self.shard
        lens = [len(r) for r in self.running]
        rmax = _pow4(max(max(lens, default=0), 1))
        run_idx = np.zeros((npad, rmax), np.int32)
        run_valid = np.zeros((npad, rmax), bool)
        for i, run in enumerate(self.running):
            run_idx[i, :len(run)] = run
            run_valid[i, :len(run)] = True
        caps = np.full((npad,), -1e30)
        caps[:N] = self.caps
        node_valid = np.zeros((npad,), bool)
        node_valid[:N] = True
        if self.shard:
            q_idx, q_valid = pad_lane_axis(
                (np.asarray(lanes, np.int32), np.ones(len(lanes), bool)),
                (0, False), lo=8)
            operands = (caps, node_valid, run_idx, run_valid,
                        self._used_now(self.running, now, npad), q_idx,
                        q_valid)
        else:
            S = RUN_STEPS
            leave_node = np.full(S, npad)
            leave_slot = np.zeros(S)
            old_starts = np.zeros((S, self.K))
            old_peaks = np.zeros((S, self.K))
            gone = np.full(sum(lens), S)    # per resident, node-major
            first = np.cumsum([0] + lens[:-1])
            queue, q_from = list(lanes), [0] * len(lanes)
            for k, (ni, lane, joins, _) in enumerate(events):
                if ni is not None:
                    slot = self.running[ni].index(lane)
                    leave_node[k], leave_slot[k] = ni, slot
                    old_starts[k] = self.starts[lane]
                    old_peaks[k] = self.peaks[lane]
                    gone[first[ni] + slot] = k
                queue.extend(joins)
                q_from.extend([k] * len(joins))
            q_idx, q_from = pad_lane_axis(
                (np.asarray(queue, np.int32), np.asarray(q_from, np.int32)),
                (0, S), lo=8)
            used_now = self._used_now(self.running, now, npad, gone)
            for ni, lane, _, replan in events:
                self._leave(ni, lane, replan)
        Q = int(q_idx.shape[0])
        with jax.enable_x64(True):
            if self._dirty_dev:
                self._dev_sync()
            if self.shard:
                kernel = _drain_kernel_sharded(self.use_dur, select,
                                               self.shard)
                operands = tuple(jnp.asarray(x) for x in operands) + (
                    jnp.float64(now), jnp.float64(self.tol))
                record_dispatch("admission.drain.upload", len(operands))
            else:
                kernel = _drain_kernel(self.use_dur, select)
                packed = jax.device_put(_drain_pack(
                    caps, node_valid, run_idx, run_valid, used_now, q_idx,
                    q_from, leave_node, leave_slot, old_starts, old_peaks,
                    len(events), now, self.tol))
                record_dispatch("admission.drain.upload")
            if obs_enabled:
                phase.__exit__(None, None, None)
                phase = _obs.span("admission.drain.launch").__enter__()
            if self.shard:
                *out, self._dadmit = kernel(
                    self._dstarts, self._dpeaks, self._dadmit, self._ddur,
                    self._dneed, self._dgrid, *operands)
            else:
                out, self._dadmit = kernel(
                    self._dstarts, self._dpeaks, self._dadmit, self._ddur,
                    self._dneed, self._dgrid, packed,
                    npad=npad, rmax=rmax, Q=Q)
            if obs_enabled:
                phase.__exit__(None, None, None)
            # Outside the sub-spans: the tag lands on ``admission.drain``.
            self.stats["drain_dispatches"] += 1
            record_dispatch("admission.drain")
            if obs_enabled:
                phase = _obs.span("admission.drain.readback").__enter__()
        # The drain's placement decisions must reach the host loop below,
        # so one transfer is irreducible — but it is ONE batched
        # device_get, of one packed vector on the unsharded path.
        # lint: allow[host-sync-in-hot-path] single batched readback per drain; decisions feed host bookkeeping
        out = jax.device_get(out)
        if obs_enabled:
            phase.__exit__(None, None, None)
        if self.shard:
            out_lane, out_node, n = out
            out_step = np.zeros(n, np.int32)
        else:
            out_lane, out_node, out_step, n = (
                out[:Q], out[Q:2 * Q], out[2 * Q:3 * Q], out[3 * Q])
        placed: List[List[tuple]] = [[] for _ in events]
        for lane, ni, k in zip(out_lane[:n].tolist(), out_node[:n].tolist(),
                               out_step[:n].tolist()):
            # Host bookkeeping per placement; the device-side admit_t
            # scatter already happened inside the drain dispatch.
            self.running[ni].append(lane)
            self.admit_t[lane] = now
            if self.shard:
                self.valid[ni, :] = False
            else:
                # Monotonic rule (same as place()): the placement only
                # shrank node ni's residual, so the pre-filter's cached
                # False entries stay valid; only the Trues must be
                # recomputed on the next refresh.
                self.valid[ni] &= ~self.fits[ni]
            placed[k].append((lane, ni))
        return placed
