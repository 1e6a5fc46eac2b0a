"""Hand-scheduled collective algorithms over ``lax.ppermute`` (L3 on TPU).

SURVEY.md §7 Milestone 2: the same pure schedule generators that drive the
CPU transports (mpi_tpu/schedules.py) are re-emitted here as ppermute step
sequences, so the reference's algorithm-vs-algorithm benchmark dimension
(ring vs recursive-halving, BASELINE.json:10; tree bcast/reduce,
BASELINE.json:8) exists on TPU alongside the fused XLA collectives
(SURVEY.md §3.3: "both required").

Every function takes group-level geometry:
* ``axis_name`` — the mesh axis the SPMD program runs over,
* ``size`` — ranks per group (static),
* ``grank`` — this shard's group-local rank (traced scalar),
* ``world_pairs(group_pairs)`` — expands group-level (src, dst) pairs to
  world-level ppermute pairs across all sibling groups (built by
  TpuCommunicator; validated by mpi_tpu.checker at trace time).

All control flow is trace-friendly: static round counts (unrolled Python
loops or ``lax.fori_loop`` where the permutation is step-invariant), dynamic
chunk indices via ``lax.dynamic_*_in_dim`` with traced ``grank`` — no
data-dependent Python branching (SURVEY.md §7 hard part 1).
"""

from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import ops as _ops
from .. import schedules

Pair = Tuple[int, int]
WorldPairs = Callable[[Sequence[Pair]], List[Pair]]


def _pad_flat(x: jnp.ndarray, size: int) -> Tuple[jnp.ndarray, int]:
    """Flatten and zero-pad to a multiple of ``size`` (equal ppermute chunks)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    padded = -(-n // size) * size if n else size
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat, n


def _ensure_varying(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Mark ``x`` manual-varying over ``axis_name`` if it isn't already.

    Loop carries fed to ppermute inside fori_loop must enter the loop with
    the same varying-axes type they leave with; inputs that are replicated
    (e.g. broadcast operands) need an explicit pcast."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, (axis_name,), to="varying")


def _mask_of(ranks: Sequence[int], axis_size: int, axis_name: str):
    """Traced bool: is this shard's world axis-index in ``ranks``?"""
    table = np.zeros(axis_size, dtype=bool)
    table[list(ranks)] = True
    return jnp.asarray(table)[lax.axis_index(axis_name)]


def tree_reduce_local(op: _ops.ReduceOp, stacked: jnp.ndarray) -> jnp.ndarray:
    """Reduce a stacked [P, ...] array along axis 0 with op.combine (static P)."""
    parts = [stacked[i] for i in range(stacked.shape[0])]
    return functools.reduce(op.combine, parts)


# ---------------------------------------------------------------------------
# Ring allreduce — the north-star schedule (BASELINE.json:5,10; SURVEY.md §3.3)
# ---------------------------------------------------------------------------


def ring_allreduce(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
    op: _ops.ReduceOp = _ops.SUM,
) -> jnp.ndarray:
    """Reduce-scatter ring + allgather ring: 2(P-1) ppermute steps, each
    moving 1/P of the buffer — bandwidth-optimal.  The ring permutation is
    step-invariant, so both phases run under ``lax.fori_loop`` (compile size
    independent of P); only the chunk index depends on the (traced) step."""
    if size == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat, n = _pad_flat(x, size)
    chunks = flat.reshape(size, -1)
    # the loop carry becomes axis-varying after the first ppermute; mark the
    # initial carry accordingly or shard_map's VMA check rejects the fori_loop
    chunks = _ensure_varying(chunks, axis_name)
    perm = world_pairs(schedules.ring_perm(size, 1))

    def rs_step(s, chunks):
        si = schedules.ring_rs_send_chunk(grank, s, size)
        ri = schedules.ring_rs_recv_chunk(grank, s, size)
        send = lax.dynamic_index_in_dim(chunks, si, 0, keepdims=False)
        recvd = lax.ppermute(send, axis_name, perm)
        cur = lax.dynamic_index_in_dim(chunks, ri, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(chunks, op.combine(cur, recvd), ri, 0)

    chunks = lax.fori_loop(0, size - 1, rs_step, chunks)

    def ag_step(s, chunks):
        si = schedules.ring_ag_send_chunk(grank, s, size)
        ri = schedules.ring_ag_recv_chunk(grank, s, size)
        send = lax.dynamic_index_in_dim(chunks, si, 0, keepdims=False)
        recvd = lax.ppermute(send, axis_name, perm)
        return lax.dynamic_update_index_in_dim(chunks, recvd, ri, 0)

    chunks = lax.fori_loop(0, size - 1, ag_step, chunks)
    return chunks.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Recursive halving/doubling allreduce (BASELINE.json:10)
# ---------------------------------------------------------------------------


def halving_allreduce(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
    op: _ops.ReduceOp = _ops.SUM,
) -> jnp.ndarray:
    """Recursive-halving reduce-scatter + recursive-doubling allgather:
    2·log2(P) ppermute steps, latency-optimal; power-of-two groups only.
    Rounds are unrolled — each halves the live buffer, so shapes stay static."""
    if size == 1:
        return x
    masks = schedules.halving_masks(size)  # raises for non-pow2
    shape, dtype = x.shape, x.dtype
    buf, n = _pad_flat(x, size)
    for mask in masks:
        perm = world_pairs(schedules.xor_perm(size, mask))
        half = buf.shape[0] // 2
        lower, upper = buf[:half], buf[half:]
        bit = (grank & mask) != 0
        # bit set → my half is the upper one; send the lower half away
        send = jnp.where(bit, lower, upper)
        keep = jnp.where(bit, upper, lower)
        recvd = lax.ppermute(send, axis_name, perm)
        buf = op.combine(keep, recvd)
    # buf is now the fully reduced chunk number ``grank``
    for mask in schedules.doubling_masks(size):
        perm = world_pairs(schedules.xor_perm(size, mask))
        recvd = lax.ppermute(buf, axis_name, perm)
        bit = (grank & mask) != 0
        buf = jnp.where(
            bit,
            jnp.concatenate([recvd, buf]),
            jnp.concatenate([buf, recvd]),
        )
    return buf[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Binomial tree bcast / reduce (BASELINE.json:8)
# ---------------------------------------------------------------------------


def tree_bcast(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
    axis_size: int,
    root: int = 0,
) -> jnp.ndarray:
    """Binomial-tree broadcast as log2(P) masked ppermute rounds.  Ranks not
    yet reached hold 0; ppermute delivers 0 to non-destinations, so
    ``buf + recvd`` is exact (each rank receives at most once)."""
    if size == 1:
        return x
    if x.dtype == jnp.bool_:
        return tree_bcast(x.astype(jnp.uint8), axis_name, size, grank,
                          world_pairs, axis_size, root).astype(jnp.bool_)
    buf = jnp.where(grank == root, x, jnp.zeros_like(x))
    for pairs in schedules.binomial_bcast_rounds(size, root):
        wp = world_pairs(pairs)
        recvd = lax.ppermute(buf, axis_name, wp)
        is_dst = _mask_of([d for _, d in wp], axis_size, axis_name)
        buf = buf + jnp.where(is_dst, recvd, jnp.zeros_like(recvd))
    return buf


def tree_reduce(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
    axis_size: int,
    op: _ops.ReduceOp = _ops.SUM,
    root: int = 0,
) -> jnp.ndarray:
    """Binomial-tree reduction to ``root``: children send their accumulator
    up the tree; non-root ranks end holding the op identity.  ppermute's
    zero-fill at non-destinations is replaced with the op identity so MAX/MIN
    stay correct."""
    if size == 1:
        return x
    ident = jnp.full(x.shape, op.identity(np.dtype(x.dtype)), dtype=x.dtype)
    buf = x
    for pairs in schedules.binomial_reduce_rounds(size, root):
        wp = world_pairs(pairs)
        recvd = lax.ppermute(buf, axis_name, wp)
        is_dst = _mask_of([d for _, d in wp], axis_size, axis_name)
        buf = op.combine(buf, jnp.where(is_dst, recvd, ident))
    return jnp.where(grank == root, buf, ident)


# ---------------------------------------------------------------------------
# Allgather: ring and recursive doubling
# ---------------------------------------------------------------------------


def ring_allgather(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
) -> jnp.ndarray:
    """P-1 ring steps; returns stacked [P, ...] in rank order."""
    out = jnp.zeros((size,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, grank, 0)
    if size == 1:
        return out
    out = _ensure_varying(out, axis_name)  # see ring_allreduce carry note
    perm = world_pairs(schedules.ring_perm(size, 1))

    def step(s, out):
        si = (grank - s) % size
        ri = (grank - s - 1) % size
        send = lax.dynamic_index_in_dim(out, si, 0, keepdims=False)
        recvd = lax.ppermute(send, axis_name, perm)
        return lax.dynamic_update_index_in_dim(out, recvd, ri, 0)

    return lax.fori_loop(0, size - 1, step, out)


def doubling_allgather(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
) -> jnp.ndarray:
    """Recursive doubling: log2(P) steps, buffer doubles each step; returns
    stacked [P, ...] in rank order (power-of-two groups only)."""
    buf = x[None]
    if size == 1:
        return buf
    for mask in schedules.doubling_masks(size):
        perm = world_pairs(schedules.xor_perm(size, mask))
        recvd = lax.ppermute(buf, axis_name, perm)
        bit = (grank & mask) != 0
        buf = jnp.where(
            bit,
            jnp.concatenate([recvd, buf], axis=0),
            jnp.concatenate([buf, recvd], axis=0),
        )
    return buf


def ring_reduce_scatter(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
    op: _ops.ReduceOp = _ops.SUM,
) -> jnp.ndarray:
    """Reduce-scatter ring on stacked [P, ...] blocks: P-1 ppermute steps;
    rank r ends holding the fully reduced block r (the rs-to-rank chunk
    indexing of mpi_tpu/schedules.py)."""
    if x.shape[0] != size:
        raise ValueError(f"need leading dim == {size}, got {x.shape}")
    chunks = _ensure_varying(x, axis_name)
    perm = world_pairs(schedules.ring_perm(size, 1))

    def step(s, chunks):
        si = schedules.ring_rs_block_send_chunk(grank, s, size)
        ri = schedules.ring_rs_block_recv_chunk(grank, s, size)
        send = lax.dynamic_index_in_dim(chunks, si, 0, keepdims=False)
        recvd = lax.ppermute(send, axis_name, perm)
        cur = lax.dynamic_index_in_dim(chunks, ri, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(chunks, op.combine(cur, recvd), ri, 0)

    chunks = lax.fori_loop(0, size - 1, step, chunks)
    return lax.dynamic_index_in_dim(chunks, grank, 0, keepdims=False)


# ---------------------------------------------------------------------------
# Pairwise alltoall (BASELINE.json:9)
# ---------------------------------------------------------------------------


def pairwise_alltoall(
    x: jnp.ndarray,
    axis_name: str,
    size: int,
    grank,
    world_pairs: WorldPairs,
) -> jnp.ndarray:
    """P-1 rounds; round k sends block (grank+k)%P to neighbor at distance k
    and receives the block slot (grank-k)%P.  Input/output: stacked [P, ...].
    Rounds are unrolled because each has a distinct (static) permutation."""
    if x.shape[0] != size:
        raise ValueError(
            f"alltoall payload must have leading dim == group size {size}, "
            f"got {x.shape}"
        )
    out = jnp.zeros_like(x)
    own = lax.dynamic_index_in_dim(x, grank, 0, keepdims=False)
    out = lax.dynamic_update_index_in_dim(out, own, grank, 0)
    for k in schedules.alltoall_rounds(size):
        perm = world_pairs(schedules.ring_perm(size, k))
        send = lax.dynamic_index_in_dim(x, (grank + k) % size, 0, keepdims=False)
        recvd = lax.ppermute(send, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(out, recvd, (grank - k) % size, 0)
    return out
