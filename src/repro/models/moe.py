"""Top-k Mixture-of-Experts with sort-based token dispatch.

Dispatch is O(T·k log T·k) sort + gathers — *not* the GShard one-hot einsum,
whose dispatch FLOPs (T·E·C·d) would dwarf the expert compute itself at our
shapes.  Tokens are routed to a capacity-bounded per-expert buffer
``(E, C, d)``; the batched expert matmuls are plain einsums so the lowered
FLOPs equal the *active* parameter count (top-k experts per token), which is
what the 6·N_active·D roofline accounting expects.

Expert weights carry the ``expert`` logical axis and are sharded over the
``model`` mesh axis (expert parallelism); GSPMD turns the data→expert
scatter/gather into all-to-alls on the token buffer.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.launch.partitioning import (
    current_batch_axes,
    current_batch_shards,
    current_mesh,
    logical_constraint,
)

__all__ = ["moe_block", "moe_block_local", "moe_capacity"]


def _local_dispatch(xl: jnp.ndarray, router_w, topk: int, C: int):
    """Per-device token routing (plain local ops; used under shard_map).

    xl: (Tl, d) local tokens.  Returns (buf (E,C,d), slot, rows, gate, keep,
    probs) — everything the combine step and aux losses need.
    """
    Tl, d = xl.shape
    E = router_w.shape[1]
    logits = jnp.einsum("td,de->te", xl, router_w.astype(xl.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, topk)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    flat_e = expert_idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    rows = order // topk
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(Tl * topk, dtype=jnp.int32) - starts[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + jnp.minimum(rank, C - 1), E * C)
    gathered = jnp.take(xl, rows, axis=0)
    buf = jnp.zeros((E * C, d), xl.dtype).at[slot].set(gathered, mode="drop")
    gate_sorted = gate_vals.reshape(-1)[order]
    return (buf.reshape(E, C, d), slot, rows, gate_sorted, keep,
            probs, counts)


def _local_combine(out_buf, slot, rows, gate_sorted, keep, Tl: int):
    """Per-device combine: scatter expert outputs back to local tokens."""
    E_C, d = out_buf.reshape(-1, out_buf.shape[-1]).shape
    out_flat = out_buf.reshape(E_C, d)
    picked = jnp.take(out_flat, jnp.minimum(slot, E_C - 1), axis=0)
    contrib = picked * (gate_sorted * keep).astype(out_flat.dtype)[:, None]
    return jnp.zeros((Tl, d), out_flat.dtype).at[rows].add(contrib)


def moe_capacity(num_tokens: int, n_experts: int, topk: int,
                 capacity_factor: float) -> int:
    c = int(num_tokens * topk / n_experts * capacity_factor)
    return max(-(-c // 8) * 8, 8)  # round up to 8 for tiling


def moe_block(
    x: jnp.ndarray,             # (B, S, d)
    router_w: jnp.ndarray,      # (d, E)
    w_gate: jnp.ndarray,        # (E, d, ff)
    w_up: jnp.ndarray,          # (E, d, ff)
    w_down: jnp.ndarray,        # (E, ff, d)
    *,
    topk: int,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (output (B,S,d), aux dict with load-balance loss terms)."""
    B, S, d = x.shape
    E = router_w.shape[1]
    T = B * S
    C = moe_capacity(T, E, topk, capacity_factor)
    xf = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xf, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)          # (T, E) f32
    gate_vals, expert_idx = jax.lax.top_k(probs, topk)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch -------------------------------------------
    flat_e = expert_idx.reshape(-1)                  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)         # (T*k,)
    sorted_e = flat_e[order]
    tok_of = order // topk                           # source token per slot
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts             # (E,)
    rank = jnp.arange(T * topk, dtype=jnp.int32) - starts[sorted_e]
    keep = rank < C                                  # capacity dropping
    slot = sorted_e * C + jnp.minimum(rank, C - 1)
    slot = jnp.where(keep, slot, E * C)              # OOB -> dropped

    gathered = jnp.take(xf, tok_of, axis=0)          # (T*k, d)
    buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(gathered, mode="drop")
    buf = buf.reshape(E, C, d)
    buf = logical_constraint(buf, "expert", None, None)

    # ---- expert computation (active FLOPs only) ------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(x.dtype))
    act = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", act, w_down.astype(x.dtype))
    out_flat = out_buf.reshape(E * C, d)

    # ---- combine back ---------------------------------------------------
    picked = jnp.take(out_flat, jnp.minimum(slot, E * C - 1), axis=0)
    gate_sorted = gate_vals.reshape(-1)[order]
    contrib = picked * (gate_sorted * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((T, d), x.dtype).at[tok_of].add(contrib)

    # Switch-style load-balance aux loss (computed in f32).
    frac_tokens = jnp.mean(
        (jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0)) / (T * topk))
    me = jnp.mean(probs, axis=0)                     # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * topk)
    aux_loss = E * jnp.sum(me * ce)
    dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) / (T * topk)
    aux = dict(moe_aux_loss=aux_loss, moe_dropped_frac=dropped,
               moe_frac_tokens=frac_tokens)
    return y.reshape(B, S, d), aux


def moe_block_local(
    x: jnp.ndarray,             # (B, S, d)
    router_w: jnp.ndarray,      # (d, E)
    w_gate: jnp.ndarray,        # (E, d, ff)
    w_up: jnp.ndarray,          # (E, d, ff)
    w_down: jnp.ndarray,        # (E, ff, d)
    *,
    topk: int,
    capacity_factor: float = 1.25,
    n_shards: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Shard-local MoE dispatch (beyond-paper §Perf optimization).

    :func:`moe_block` sorts the *global* token stream, so under GSPMD every
    device materializes the full (T, d) activation — an all-gather whose
    traffic dwarfs the expert compute.  Here every data shard routes only
    its local tokens (leading ``n_shards`` axis stays sharded on the batch
    axes; per-shard expert capacity), and only the capacity-bounded expert
    buffer crosses the network: the resharding

        (shard, E, C_local, d): batch-sharded  →  expert-sharded

    lowers to the canonical MoE all-to-all, and back after the expert
    matmuls.  Collective volume per layer drops from O(T·d · L) gathers to
    2 × T·topk·d / #shards per chip — the textbook EP exchange.
    """
    B, S, d = x.shape
    E = router_w.shape[1]
    if n_shards <= 0:
        n_shards = current_batch_shards()
    T = B * S
    if T % n_shards:
        n_shards = 1
    Tl = T // n_shards
    C = moe_capacity(Tl, E, topk, capacity_factor)

    mesh = current_mesh()
    if mesh is not None and n_shards > 1:
        # GSPMD's gather/scatter partitioner cannot prove the dispatch
        # local (it all-gathers operand + broadcast u32 indices — measured
        # ~1 TiB/layer on olmoe); shard_map makes locality explicit.
        return _moe_shardmap(x, router_w, w_gate, w_up, w_down, mesh,
                             topk=topk, C=C, n_shards=n_shards)

    xs = x.reshape(n_shards, Tl, d)
    xs = logical_constraint(xs, "batch", None, None)
    s_idx = jnp.arange(n_shards)

    logits = jnp.einsum("std,de->ste", xs, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)           # (s, Tl, E) f32
    gate_vals, expert_idx = jax.lax.top_k(probs, topk)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    flat_e = expert_idx.reshape(n_shards, Tl * topk)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    tok_of = order // topk                            # (s, Tl*k)
    counts = jnp.zeros((n_shards, E), jnp.int32).at[
        s_idx[:, None], flat_e].add(1)
    starts = jnp.cumsum(counts, axis=-1) - counts     # (s, E)
    rank = jnp.arange(Tl * topk, dtype=jnp.int32)[None, :] - \
        jnp.take_along_axis(starts, sorted_e, axis=-1)
    keep = rank < C
    slot = sorted_e * C + jnp.minimum(rank, C - 1)
    slot = jnp.where(keep, slot, E * C)

    # Flat-row gather/scatter: take_along_axis with a trailing broadcast
    # materializes (s, Tl·k, d)-shaped u32 *index* tensors that GSPMD then
    # all-gathers (measured: 1 TiB/layer on olmoe).  Row-id forms keep the
    # indices (s·Tl·k,)-shaped.
    xf_flat = xs.reshape(n_shards * Tl, d)
    rows = (s_idx[:, None] * Tl + tok_of).reshape(-1)
    gathered = jnp.take(xf_flat, rows, axis=0)        # (s*Tl*k, d)
    stride = E * C + 1                                # +1 = per-shard drop slot
    flat_slot = (s_idx[:, None] * stride + slot).reshape(-1)
    buf = jnp.zeros((n_shards * stride, d), x.dtype).at[
        flat_slot].set(gathered, mode="drop")
    buf = buf.reshape(n_shards, stride, d)[:, :E * C]
    buf = buf.reshape(n_shards, E, C, d)
    # Keep the buffer batch-sharded (and replicated over the model axis):
    # the expert einsums below contract with E-sharded weights, so GSPMD
    # partitions them over E by *slicing* the locally-replicated buffer
    # (free) and the combine becomes a partial-sum all-reduce of (Tl, d) —
    # no token gathers.
    buf = logical_constraint(buf, "batch", None, None, None)

    g = jnp.einsum("secd,edf->secf", buf, w_gate.astype(x.dtype))
    u = jnp.einsum("secd,edf->secf", buf, w_up.astype(x.dtype))
    act = jax.nn.silu(g) * u
    out_buf = jnp.einsum("secf,efd->secd", act, w_down.astype(x.dtype))
    out_flat = out_buf.reshape(n_shards * E * C, d)

    pick_rows = (s_idx[:, None] * (E * C)
                 + jnp.minimum(slot, E * C - 1)).reshape(-1)
    picked = jnp.take(out_flat, pick_rows, axis=0)    # (s*Tl*k, d)
    gate_sorted = jnp.take_along_axis(
        gate_vals.reshape(n_shards, Tl * topk), order, axis=-1)
    contrib = picked * (gate_sorted * keep).astype(
        x.dtype).reshape(-1)[:, None]
    y = jnp.zeros((n_shards * Tl, d), x.dtype).at[rows].add(contrib)
    y = y.reshape(n_shards, Tl, d)
    y = logical_constraint(y, "batch", None, None)

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.sum(counts, axis=0).astype(jnp.float32) / (T * topk)
    aux_loss = E * jnp.sum(me * ce)
    dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) / (T * topk)
    aux = dict(moe_aux_loss=aux_loss, moe_dropped_frac=dropped,
               moe_frac_tokens=jnp.mean(ce))
    return y.reshape(B, S, d), aux


def _moe_shardmap(x, router_w, w_gate, w_up, w_down, mesh, *,
                  topk: int, C: int, n_shards: int):
    """shard_map dispatch/combine + GSPMD expert compute.

    Dispatch and combine run as explicitly-local per-device programs over
    the batch axes (replicated over ``model``); only the capacity-bounded
    expert buffer participates in cross-device communication, via the
    E-sharded expert einsums whose partial results reduce over ``model``.
    """
    B, S, d = x.shape
    E = router_w.shape[1]
    batch_axes = current_batch_axes() or tuple(
        a for a in ("pod", "data") if a in mesh.axis_names)
    xs = x.reshape(n_shards, (B * S) // n_shards, d)
    Tl = xs.shape[1]

    disp = jax.shard_map(
        lambda xl, rw: jax.tree.map(
            lambda a: a[None], _local_dispatch(xl[0], rw, topk, C)),
        mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None)),
        out_specs=P(batch_axes),
        check_vma=False,
    )
    buf, slot, rows, gate_sorted, keep, probs, counts = disp(xs, router_w)
    # buf: (n_shards, E, C, d) batch-sharded, replicated over model.
    buf = logical_constraint(buf, "batch", None, None, None)

    g = jnp.einsum("secd,edf->secf", buf, w_gate.astype(x.dtype))
    u = jnp.einsum("secd,edf->secf", buf, w_up.astype(x.dtype))
    act = jax.nn.silu(g) * u
    out_buf = jnp.einsum("secf,efd->secd", act, w_down.astype(x.dtype))
    out_buf = logical_constraint(out_buf, "batch", None, None, None)

    comb = jax.shard_map(
        lambda ob, sl, rw, gs, kp: _local_combine(
            ob[0], sl[0], rw[0], gs[0], kp[0], Tl)[None],
        mesh=mesh,
        in_specs=(P(batch_axes, None, None, None), P(batch_axes, None),
                  P(batch_axes, None), P(batch_axes, None),
                  P(batch_axes, None)),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )
    y = comb(out_buf, slot, rows, gate_sorted, keep)
    y = logical_constraint(y, "batch", None, None)

    T = B * S
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.sum(counts, axis=0).astype(jnp.float32) / (T * topk)
    aux_loss = E * jnp.sum(me * ce)
    dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) / (T * topk)
    aux = dict(moe_aux_loss=aux_loss, moe_dropped_frac=dropped,
               moe_frac_tokens=jnp.mean(ce))
    return y.reshape(B, S, d), aux
