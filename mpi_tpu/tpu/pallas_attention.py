"""Fused ring attention as a Pallas TPU kernel (RDMA over ICI).

Long-context exact attention over a sequence-sharded axis (SURVEY.md §2
strategy table — the long-context strategy is first-class).  The
ppermute spelling lives in ``examples/ring_attention.py``; this module
is its TPU-first hot path: ONE kernel in which the K/V blocks circulate
the ring as RDMAs while the MXU computes attention against the block
that just landed — transfer hidden behind compute, the same
communication/compute overlap argument as ``pallas_ring``.

Protocol (a sibling of pallas_ring's — verified by the discrete-event
model ``ring_model.AttentionSim``, tests/test_pallas_protocol.py):

* Each device holds Q, K, V blocks of the sequence ([Sb, d] each).  At
  step 0 it computes attention of its Q against its OWN K/V and starts
  forwarding that K/V (one stacked [2*Sb, d] RDMA) to its right
  neighbor's landing slot.
* Arrival ``a`` (1..P-1) lands K/V block ``(rank - a) mod P`` in the
  double-buffered comm slot ``a % 2``; the device folds it into the
  online-softmax state (running rowmax ``m``, denominator ``l``,
  weighted accumulator ``o`` — all f32), and, while the fold runs,
  forwards the same block from the slot to the next neighbor.
* **Credit flow control** recycles the slots: arrival ``a+2`` re-uses
  slot ``a % 2``, so after consuming arrival ``a`` (fold done AND the
  forwarding RDMA has left the slot — ``wait_send`` precedes the
  credit) the device signals one credit to its LEFT neighbor, which
  gates that neighbor's send ``a+1``.  Sends 0 and 1 are credit-free
  (their target slots are virgin).
* Entry/exit neighbor barriers bracket the kernel, as in pallas_ring.

Numerics: the online-softmax recurrence
``m' = max(m, rowmax(S)); l' = l·e^{m-m'} + rowsum(e^{S-m'});
o' = o·e^{m-m'} + e^{S-m'}·V`` is an exact (not approximate) attention
— the standard flash/ring-attention algebra.  Accumulation is float32
for bf16 inputs.  Full OR causal attention (``causal=True`` masks by
global position — block indices come from the SMEM params, so the same
compiled kernel serves every rank); scale = 1/sqrt(d) by default.

**The tiled fold**: flash-attention-style inner tiling.  In a ring the
m/l/o state lives in HBM between arrivals; each arrival loops over
[tq]-row query tiles and [tk]-row K/V tiles (``lax.fori_loop``), staging
each tile through small VMEM buffers, so the live score block is [tq, tk]
and the block size is bounded by HBM, not VMEM.  The innermost loops of
both folds are software-pipelined (``_mk_tile_loop``): two buffer slots,
the next tile pair's copies running while the MXU folds this one.  The
fold is a local subroutine between protocol events, so
``AttentionSim``'s verification of the circulation covers it.

**VMEM plan** (``attention_vmem_plan``): the plan only picks the tile —
the largest sublane-aligned divisor of Sb, at most 512 rows, whose
buffers fit a VMEM budget (a causal ring takes those that divide Sb/2
first, for the zigzag layout below).  Where no tile fits, the forward
is diagnosed at trace time with the math shown and the backward plans
"fallback".

**Fused backward** (``_bwd_kernel`` — VERDICT r4 missing #3): under
differentiation the forward also emits the per-row logsumexp
``L = m + log l`` (skipped entirely on inference/fallback paths); the
backward is its own ring kernel in which [K, V] circulate in the INPUT
dtype and [dK, dV] in f32 (the wire-dtype != fold-dtype split, ISSUE
8 / VERDICT r5 #5: pristine K/V inputs lose nothing below f32 — bf16
halves their wire bytes — while the dK/dV partial sums keep full
precision; two RDMAs per hop on per-plane semaphore columns, protocol
otherwise unchanged) for a FULL cycle of P sends — each device
recomputes its block pair's probabilities from (Q, L), accumulates dQ
locally, adds its dK/dV contribution into the circulating payload, and
forwards; after P hops the accumulators land back home.  Fold-before-forward ordering
(the payload is mutated before it moves on) with the same
double-buffer + credit discipline — model-checked separately by
``ring_model.AttentionBwdSim``.  The backward fold is tiled like the
forward (dQ accumulating in its HBM output; a K/V-tile outer loop
carries dK/dV accumulators over a Q-tile inner loop), so long-context
training stays on the fused kernels; where its plan is "fallback" (no
tile fits the budget) the backward recomputes through the pure-jax
ppermute ring (correct at any size).

**Causal layout** (``_zigzag_tiles``): with the blocks contiguous,
rank r's causal work is its diagonal block plus r past blocks, and
the ring paces every rank to the last one.  So where causal attention
runs both fused kernels, with tiles that divide Sb/2, the wrapper
re-lays the sequence out zigzag: 2P chunks of Sb/2 rows, rank r
computing on chunk r and chunk 2P-1-r.  Every off-diagonal arrival
then costs every rank half a block (``attention_zigzag`` mpit pvar
counts the traces that take it).  Two ppermutes per array move the
halves before the kernels and back after them, outside the custom
VJP, so the caller's contiguous blocks and the kernels' circulation
protocol are unchanged; only the tile ranges each fold visits differ.
Every other case (full attention, windowed attention, P = 1, the
fallbacks) keeps the contiguous blocks.

**Sliding window** (``_band_tiles``): with ``window=W`` a query attends
the W newest keys up to itself.  On the contiguous layout each tiled
fold visits, per q tile, only the k tiles between its window's start and
its diagonal (and, in the backward, per k tile only the q tiles whose
windows reach it): exactly the tile pairs that hold an unmasked element.
Both bounds are masked by global position, by one mask function.  A past
block wholly before every local query's window folds nothing; the
circulation is unchanged.  The windowed kernels carry their own names
(``mpi_tpu_swa_attention_fwd`` / ``_bwd``) and the ``attention_window``
mpit pvar counts the traces that take the windowed tile ranges.

**A ring of one**: at P = 1 the same kernels run the step-0 fold alone —
no landing slots, RDMA, barrier or credit, and none of a collective's
compiler parameters.  Every q tile is final when its k loop ends, so the
forward's state never leaves VMEM: each q tile writes out (and L) from
the q loop, the write-back overlapping the next tile's load and fold,
with no m/l/o buffers in HBM and no separate output pass
(``attention_direct_out`` mpit pvar).  The kernels loop over heads in
``lax.fori_loop``s (row offsets traced), so the lowered code does not
grow with the heads.

Under the interpreter (CPU tier) RDMAs run serially (start+wait, no
credits/barriers) — same data path, no overlap; under vma typing or a
multi-axis mesh the interpreter executes a ppermute ring fallback
(same online-softmax algebra as jax ops) with the shared loud-fallback
warning.  The compiled multi-axis path addresses neighbors by mesh
coordinate exactly like pallas_ring.

**Head widths**: q/k heads may be wider than v heads (``d_qk != d_v``;
the output takes v's width).  Where the two widths agree, the K/V
buffers stack all K planes then all V planes, one RDMA a hop; where they
differ, K and V are buffers of their own, one RDMA each a hop on
per-plane semaphore columns (``_SndGroup``), the protocol unchanged.  A
q/k width that is not a whole number of lanes (192) is zero-padded to one
(256) before the kernels, since a DMA slice must cover whole lanes; the
zeros add nothing to QK^T, and autodiff slices dq/dk back.  A 128-wide
MXU takes a 192 contraction in the passes of 256 either way.

**Sinks** (``sinks=[Hq]`` float32): one learnable logit per query head
in every row's softmax denominator, carrying no value.  The forward
folds it in as the online softmax's initial state (m = s_h, l = 1,
o = 0) at the step-0 fold, so it enters each row once whatever the ring
size, and the logsumexp residual includes it; the backward kernel is
unchanged (its probabilities come from that residual), and dsinks_h =
-sum_i e^{s_h - L_i} (dO_i . O_i) is formed from the residuals outside
it.  ``attention_sink`` (mpit pvar) counts the traces that take it.

Restrictions (diagnosed): f32/bf16; v's head width ``d_v`` a multiple of
128 (lane width); block rows ``Sb`` a multiple of 8 (sublane tile).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ring import _check_args, _fallback, _world_pairs_of

_LANES = 128


_MASKED = -1e30  # large-negative finite (an -inf mask would NaN through exp)

# Conservative default VMEM budget: 16 MiB/core on current TPUs, minus
# headroom for Mosaic's own spills/semaphores/metadata.
_VMEM_BUDGET = 12 * 2 ** 20

# Largest tile edge of the tiled folds.  Mosaic unrolls a tile's vector
# ops, so compile time grows with tq*tk: 8 heads of [1024, 1024] tiles
# took ~95 s to compile for v5e, [512, 512] ~17 s (heads unrolled then;
# the head loops are rolled since).
_MAX_TILE = 512


def _online_fold(q, k, v, m, l, o, scale, mask=None):
    """One block's online-softmax fold (shared by kernel and fallback).
    q:[Sq,d] k,v:[Sb,d] m,l:[Sq,1] o:[Sq,d] (f32 state) → new (m,l,o).
    ``mask``: optional [Sq,Sb] bool, True = attend (False → _MASKED;
    a fully-masked block folds as exactly zero contribution)."""
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _MASKED)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    o_new = o * alpha + jnp.dot(p, v.astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _causal_mask(my, kv_idx, sb: int, i0=0, j0=0,
                 tq: Optional[int] = None, tk: Optional[int] = None,
                 window: Optional[int] = None):
    """[tq,tk] causal mask for rows ``i0..`` of query block ``my`` vs
    rows ``j0..`` of key block ``kv_idx`` (block indices traced, tile
    offsets traced or static): global key position must not exceed
    global query position, and with a ``window`` must lie less than
    ``window`` positions before it.  Defaults cover the whole [Sb,Sb]
    block."""
    tq = sb if tq is None else tq
    tk = sb if tk is None else tk
    qi = my * sb + i0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kj = kv_idx * sb + j0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    if window is None:
        return kj <= qi
    return (kj <= qi) & (kj > qi - window)


def _head_row(h, sb: int):
    """First row of head ``h``'s [Sb, d] plane in a heads-stacked buffer
    (the kernels loop over heads in a ``lax.fori_loop``, so ``h`` is
    traced: the hint keeps the row offset's alignment visible)."""
    return pl.multiple_of(h * sb, sb)


def _lane_pad(d: int) -> int:
    """``d`` rounded up to a whole number of lanes."""
    return -(-d // _LANES) * _LANES


def _tile_sizes(sb: int, sub: int, halves: bool = False,
                cap: int = _MAX_TILE):
    """Sublane-aligned divisors of ``sb`` up to ``cap``, descending;
    with ``halves``, those that also divide ``sb // 2`` (the zigzag
    layout's chunk) first."""
    ts = [t for t in range(min(sb, cap), 0, -1)
          if t % sub == 0 and sb % t == 0]
    if halves:
        ts.sort(key=lambda t: (sb // 2) % t != 0)  # stable: still descending
    return ts


# -- zigzag causal layout ----------------------------------------------------
#
# The global sequence is cut into 2P chunks of c = Sb/2 rows; rank r's
# block holds chunk r (local rows [0, c)) then chunk 2P-1-r (rows
# [c, 2c)).  Every off-diagonal K/V block then costs every rank the
# same half block, so no rank waits on the last one's causal work.


def _zigzag_pos(rank, row, c: int, size: int):
    """Global sequence position of local ``row`` (scalar or array) of
    ``rank``'s zigzag block of ``2*c`` rows."""
    return jnp.where(row < c, rank * c + row,
                     (2 * size - 1 - rank) * c + row - c)


def _zigzag_mask(my, c: int, size: int, i0, j0, tq: int, tk: int):
    """[tq,tk] causal mask of my own zigzag block against itself: rows
    ``i0..`` attend to rows ``j0..`` by global position.  A tile never
    straddles the two chunks (tile edges divide ``c``), so each tile's
    position is its first row's plus the iota."""
    qi = _zigzag_pos(my, i0, c, size) + \
        lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kj = _zigzag_pos(my, j0, c, size) + \
        lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return kj <= qi


class _Tiles(NamedTuple):
    """The tile pairs one arrival folds — the loop bounds of both tiled
    kernels.  The forward folds q tiles ``[q_lo, q_hi)``, q tile ``i``
    against the k tiles ``range(*k_of(i))``; the backward folds k tiles
    ``[k_lo, k_hi)``, k tile ``j`` against the q tiles
    ``range(*q_of(j))``.  Both visit the same pairs."""
    q_lo: object
    q_hi: object
    k_of: Callable
    k_lo: object
    k_hi: object
    q_of: Callable


def _zigzag_tiles(diag: bool, past, nq: int, nk: int, tq: int, tk: int):
    """:class:`_Tiles` of one causal arrival on the zigzag layout.
    ``diag`` (static): the arrival is my own block; otherwise ``past``
    (traced or Python bool) says the K/V block's rank is below mine.

    * diagonal: every q tile up to its diagonal (masked there);
    * past rank: every q tile against the low K/V chunk (its high chunk
      is in my future for both my chunks);
    * future rank: my high q chunk against every K/V tile (my low chunk
      precedes both of its chunks).
    """
    if diag:
        return _Tiles(0, nq, lambda i: (0, (i * tq + tq + tk - 1) // tk),
                      0, nk, lambda j: ((j * tk) // tq, nq))
    q_lo = jnp.where(past, 0, nq // 2)
    k_hi = jnp.where(past, nk // 2, nk)
    return _Tiles(q_lo, nq, lambda i: (0, k_hi), 0, k_hi,
                  lambda j: (q_lo, nq))


def _zigzag_arrival(a: int, my, size: int, sb: int, tq: int, tk: int):
    """Inside a kernel: ``_zigzag_tiles`` of arrival ``a`` (the K/V block
    of rank (my - a) mod size) and ``mask_of(i, j)``, the tile pair's
    causal mask — only the diagonal arrival a == 0 needs one, every pair
    another arrival visits is causal."""
    tiles = _zigzag_tiles(a == 0, lax.rem(my - a + size, size) < my,
                          sb // tq, sb // tk, tq, tk)
    if a > 0:
        return tiles, lambda i, j: None
    return tiles, lambda i, j: _zigzag_mask(my, sb // 2, size, i * tq,
                                            j * tk, tq, tk)


def _full_tiles(sb: int, tq: int, tk: int):
    """:class:`_Tiles` of full (non-causal) attention: every pair."""
    nq, nk = sb // tq, sb // tk
    return _Tiles(0, nq, lambda i: (0, nk), 0, nk, lambda j: (0, nq))


def _clamp0(x):
    """max(x, 0) of a Python int or a traced scalar."""
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _min(a, b):
    """min(a, b) of Python ints or traced scalars."""
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _band_tiles(off, window: Optional[int], sb: int, tq: int, tk: int):
    """:class:`_Tiles` of one causal arrival on the contiguous layout: my
    query block starts ``off`` (>= 0; Python int or traced) positions
    after the K/V block, and query position p attends key position p'
    iff ``0 <= p - p' < window`` (``window=None``: no lower bound).  A
    pair of tiles is visited iff it holds such an element; the window's
    lower bound cuts the k tiles wholly before a q tile's window (and
    the q tiles after a k tile's), the causal bound those past it.

    With query row r and key row c of the blocks, a tile pair (i, j)
    holds ``0 <= off + r - c < window`` for some element iff
    ``j*tk <= off + i*tq + tq - 1`` and ``off + i*tq - window + 1 <=
    j*tk + tk - 1``; the bounds below solve each for j, then for i."""
    nq, nk = sb // tq, sb // tk

    def k_of(i):
        hi = _min(nk, (off + i * tq + tq - 1) // tk + 1)
        if window is None:
            return 0, hi
        return _clamp0(off + i * tq - window + 1) // tk, hi

    def q_of(j):
        lo = _clamp0(j * tk - off) // tq
        if window is None:
            return lo, nq
        return lo, _min(nq, _clamp0(j * tk + tk + window - 2 - off
                                    + tq) // tq)

    if window is None:
        return _Tiles(0, nq, k_of, 0, nk, q_of)
    q_hi = _min(nq, _clamp0(sb - 2 + window - off + tq) // tq)
    return _Tiles(0, q_hi, k_of, _clamp0(off - window + 1) // tk, nk, q_of)


def _band_pairs(size: int, sb: int, tq: int, tk: int, window: int) -> int:
    """Query-key pairs of one query head in the tile pairs the windowed
    forward folds on the ring's last rank, which folds the most: its
    diagonal block and every past block that reaches a query's window
    (``_band_tiles`` at Python-int offsets)."""
    pairs = 0
    for a in range(size):
        if _before_window(a, sb, window):
            break
        t = _band_tiles(a * sb, window, sb, tq, tk)
        for i in range(t.q_lo, t.q_hi):
            lo, hi = t.k_of(i)
            pairs += tq * tk * max(0, hi - lo)
    return pairs


def _before_window(a: int, sb: int, window: Optional[int]) -> bool:
    """Contiguous layout: the K/V block ``a`` blocks before mine lies
    wholly before every query's window (its newest key is ``window`` or
    more positions before my oldest query), so that arrival folds
    nothing."""
    return window is not None and a * sb >= sb - 1 + window


def _band_arrival(a: int, my, sb: int, tq: int, tk: int,
                  window: Optional[int]):
    """Inside a kernel: ``_band_tiles`` of causal arrival ``a`` on the
    contiguous layout — the K/V block ``a`` blocks before mine (callers
    skip future blocks, so the offset ``a * sb`` is static) — and
    ``mask_of(i, j)``, the tile pair's mask by global position: the
    diagonal arrival and, with a window, every arrival need one (a tile
    may hold the window's edge); the pairs of a past arrival without a
    window are all causal."""
    tiles = _band_tiles(a * sb, window, sb, tq, tk)
    if a > 0 and window is None:
        return tiles, lambda i, j: None
    return tiles, lambda i, j: _causal_mask(my, my - a, sb, i * tq, j * tk,
                                            tq, tk, window)


def _zigzag_perms(size: int):
    """ppermute pairs moving contiguous half blocks to the zigzag layout:
    rank s's low half is chunk 2s and its high half chunk 2s+1; chunk x
    belongs to rank x (as its low half) when x < size, else to rank
    2*size-1-x (as its high half).  Even and odd chunks each form a
    permutation of the ranks."""
    dest = lambda x: x if x < size else 2 * size - 1 - x
    return ([(s, dest(2 * s)) for s in range(size)],
            [(s, dest(2 * s + 1)) for s in range(size)])


def _to_zigzag(x, axis_name: str, size: int):
    """This rank's contiguous block (rows on axis -2) → its zigzag block.
    Rank r's low chunk r comes from the even-chunk permutation when r is
    even, its high chunk 2*size-1-r from the odd one, and the other way
    round when r is odd."""
    even, odd = _zigzag_perms(size)
    lo, hi = jnp.split(x, 2, axis=-2)
    e = lax.ppermute(lo, axis_name, even)
    o = lax.ppermute(hi, axis_name, odd)
    r_even = lax.rem(lax.axis_index(axis_name), 2) == 0
    return jnp.concatenate([jnp.where(r_even, e, o),
                            jnp.where(r_even, o, e)], axis=-2)


def _from_zigzag(x, axis_name: str, size: int):
    """Inverse of :func:`_to_zigzag`."""
    even, odd = _zigzag_perms(size)
    lo, hi = jnp.split(x, 2, axis=-2)
    r_even = lax.rem(lax.axis_index(axis_name), 2) == 0
    e = jnp.where(r_even, lo, hi)  # my even chunk
    o = jnp.where(r_even, hi, lo)  # my odd chunk
    inv = lambda pairs: [(d, s) for s, d in pairs]
    return jnp.concatenate([lax.ppermute(e, axis_name, inv(even)),
                            lax.ppermute(o, axis_name, inv(odd))], axis=-2)


# Shared kernel helpers (one definition serves forward and backward —
# review round 5: protocol-critical code must not exist in two copies).


def _mk_dev_kw(mesh_ids: bool, axis_name: str):
    """device_id kwargs for an RDMA/signal aimed at axis index
    ``target`` (1-D logical ids, or dict-MESH coordinates on a
    multi-axis mesh — same scheme as pallas_ring)."""
    def dev_kw(target):
        if mesh_ids:
            return dict(device_id={axis_name: target},
                        device_id_type=pltpu.DeviceIdType.MESH)
        return dict(device_id=target,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

    return dev_kw


def _mk_barrier(pipelined: bool, dev_kw, left, right):
    """Entry/exit neighbor barrier (no-op on the serial interpreter)."""
    def neighbor_barrier():
        if not pipelined:
            return
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, inc=1, **dev_kw(left))
        pltpu.semaphore_signal(bar, inc=1, **dev_kw(right))
        pltpu.semaphore_wait(bar, 2)

    return neighbor_barrier


def _mk_copy_sync(copy_sem):
    """Local start+wait DMA through the shared copy semaphore."""
    def copy_sync(src, dst):
        cp = pltpu.make_async_copy(src, dst, copy_sem)
        cp.start()
        cp.wait()

    return copy_sync


def _mk_copy_par(par_sems):
    """Start INDEPENDENT local DMAs together, then wait them all — the
    folds stage several disjoint buffers at once (the m/l/o state; the
    backward's k/v and dk/dv tiles) and
    serializing them exposes every transfer's full HBM latency on chip
    (round 5).  Each copy gets its own semaphore by POSITION (all
    indices Python-static)."""
    def copy_par(*pairs):
        cps = [pltpu.make_async_copy(src, dst, par_sems.at[i])
               for i, (src, dst) in enumerate(pairs)]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    return copy_par


def _mk_tile_loop(sems):
    """The tiled folds' innermost loop, software-pipelined over two VMEM
    buffer slots: iteration n stages its tiles into slot n % 2 while
    iteration n - 1 folds out of the other slot.  ``sems`` is a [2, c]
    DMA semaphore array, one row per slot, so copies in flight into one
    slot never share a semaphore with the other slot's; the last column
    carries the write-back.  Every copy a loop starts is waited before
    the loop returns, so each fold still completes, in program order,
    before the circulation's next protocol event."""
    store_col = sems.shape[1] - 1

    def copies(pairs, slot, col0=0):
        return [pltpu.make_async_copy(src, dst, sems.at[slot, col0 + c])
                for c, (src, dst) in enumerate(pairs)]

    def start(cps):
        for cp in cps:
            cp.start()

    def wait(cps):
        for cp in cps:
            cp.wait()

    def loop(lo, hi, loads, body, carry, store=None):
        """``lax.fori_loop(lo, hi, ...)`` (bounds Python ints or traced)
        in which iteration n folds the tiles ``loads(n, slot)`` — (src,
        dst) pairs, each dst in buffer slot ``slot`` — by ``body(n, slot,
        carry)``.  Iteration n + 1's copies start before iteration n
        waits for its own.  ``store(n, slot)``: one (src, dst) write-back
        started after the fold and waited one iteration later, before
        any copy into its slot starts again, or at the loop's end."""
        def slot_of(n):
            return lax.rem(n, 2)

        def write_back(n):
            return copies([store(n, slot_of(n))], slot_of(n), store_col)

        pl.when(lo < hi)(
            lambda: start(copies(loads(lo, slot_of(lo)), slot_of(lo))))

        def step(n, carry):
            slot, other = slot_of(n), 1 - slot_of(n)
            if store is not None:
                pl.when(n > lo)(lambda: wait(write_back(n - 1)))
            pl.when(n + 1 < hi)(
                lambda: start(copies(loads(n + 1, other), other)))
            wait(copies(loads(n, slot), slot))
            carry = body(n, slot, carry)
            if store is not None:
                start(write_back(n))
            return carry

        carry = lax.fori_loop(lo, hi, step, carry)
        if store is not None:
            pl.when(hi > lo)(lambda: wait(write_back(hi - 1)))
        return carry

    return loop


def _pair_grad_tile(qh, doh, lse1, delta1, kb, vb, scale, mask=None):
    """The flash-backward algebra of one tile pair: given f32 Q/dO
    rows, their lse/delta columns, and a K/V tile,
    return (dq, dk, dv) contributions.  ``mask``: optional [rows, cols]
    bool, True = attend (probabilities zeroed elsewhere)."""
    s = jnp.dot(qh, kb.T, preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse1)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jnp.dot(doh, vb.T, preferred_element_type=jnp.float32)
    ds_ = p * (dp - delta1) * scale
    return (jnp.dot(ds_, kb, preferred_element_type=jnp.float32),
            jnp.dot(ds_.T, qh, preferred_element_type=jnp.float32),
            jnp.dot(p.T, doh, preferred_element_type=jnp.float32))


def _mk_snd(first_src, comm_hbm, send_sem, recv_sem, dev_kw, right,
            col=None):
    """Send-descriptor factory shared by both ring kernels: send ``u``
    forwards from ``first_src`` (u == 0: the block that never landed in
    a slot) or comm slot u%2, into the right neighbor's slot (u+1)%2,
    on the (parity)-indexed send/recv semaphores.  One definition —
    the slot/sem indexing IS the protocol the models check.

    ``col`` selects a PLANE column of (parity, plane)-shaped semaphores:
    the split-dtype backward (wire-dtype K/V + f32 dK/dV, ISSUE 8 /
    VERDICT r5 #5) circulates two buffers per hop, each on its own
    semaphore column but the SAME slot parity — the protocol schedule is
    untouched, only the payload is split."""
    def snd(u):
        dst_slot = (u + 1) % 2
        src = first_src if u == 0 else comm_hbm.at[u % 2]
        if col is None:
            ss, rs = send_sem.at[dst_slot], recv_sem.at[dst_slot]
        else:
            ss, rs = send_sem.at[dst_slot, col], recv_sem.at[dst_slot, col]
        return pltpu.make_async_remote_copy(
            src_ref=src, dst_ref=comm_hbm.at[dst_slot],
            send_sem=ss, recv_sem=rs,
            **dev_kw(right))

    return snd


class _SndGroup:
    """Every plane of one circulation hop as one descriptor: each
    protocol call fans out to the planes' RDMAs (same hop, same slot
    parity, per-plane semaphore columns) — the split-dtype backward's
    K/V and dK/dV planes, and K and V of different widths — so the
    send/credit schedule reads, and model-checks, exactly like the
    single-buffer version."""

    __slots__ = ("planes",)

    def __init__(self, *planes):
        self.planes = planes

    def start(self):
        for p in self.planes:
            p.start()

    def wait(self):
        for p in self.planes:
            p.wait()

    def wait_send(self):
        for p in self.planes:
            p.wait_send()

    def wait_recv(self):
        for p in self.planes:
            p.wait_recv()


def _mk_snd_planes(firsts, comms, send_sem, recv_sem, dev_kw, right):
    """``_mk_snd`` over every plane of a hop: ``firsts`` / ``comms`` hold
    one send-0 source and one landing-slot buffer per plane.  One plane
    sends on the (parity)-indexed semaphores; several send together as
    a :class:`_SndGroup`, plane p on (parity, p)-indexed ones."""
    if len(firsts) == 1:
        return _mk_snd(firsts[0], comms[0], send_sem, recv_sem, dev_kw,
                       right)
    snds = [_mk_snd(f, c, send_sem, recv_sem, dev_kw, right, col=p)
            for p, (f, c) in enumerate(zip(firsts, comms))]
    return lambda u: _SndGroup(*(snd(u) for snd in snds))


def _kv_at(planes, v0: int, slot=None):
    """(k_at, v_at) of one K/V block held in ``planes``: ``k_at(r0, n)``
    / ``v_at(r0, n)`` address ``n`` rows, from row ``r0``, of its
    head-stacked K / V planes — in one [K; V] buffer (the V planes from
    row ``v0``) where K and V share a width, else in a K buffer and a V
    buffer.  ``slot``: the buffers are landing slots, indexed first."""
    def at(ref, r0, n):
        if slot is None:
            return ref.at[pl.ds(r0, n)]
        return ref.at[slot, pl.ds(r0, n)]

    if len(planes) == 1:
        kv, = planes
        return (lambda r0, n: at(kv, r0, n),
                lambda r0, n: at(kv, v0 + r0, n))
    k, v = planes
    return lambda r0, n: at(k, r0, n), lambda r0, n: at(v, r0, n)


def attention_vmem_plan(sb: int, d: int, hq: int, hkv: int, dtype,
                        vmem_limit_bytes: Optional[int] = None,
                        for_backward: bool = False, halves: bool = False,
                        *, dv: Optional[int] = None,
                        window: Optional[int] = None):
    """Pick the fold's tile from a VMEM budget (trace time).

    Returns ``("tiled", (t, t))`` with the largest sublane-aligned
    divisor tile of ``sb`` (at most ``_MAX_TILE`` rows) whose buffers
    fit; ``halves`` prefers tiles that divide ``sb // 2``
    (``_tile_sizes``), as the causal ring's zigzag layout needs.  Where
    no tile fits, the backward returns ``("fallback", None)`` (→
    ppermute recompute, correct at any size) and the forward raises
    NotImplementedError with the arithmetic, which the caller
    (pallas_ring_attention) turns into the loud ppermute-ring fallback
    (warning + ``attention_fallbacks`` pvar).

    ``d`` is the q/k width the kernels stage (whole lanes), ``dv`` the
    v and output width (default ``d``).  A sliding ``window`` caps the
    tile at the window rounded up to whole lanes: a band narrower than
    the tile would fold mostly masked pairs (a 128-key window folds
    about 2x the pairs it keeps on 128-row tiles, 8x on 512-row ones).

    The estimates are deliberately generous (temporaries counted at
    f32, a spare plane for Mosaic's fusions) so a tile that fits here
    fits on real hardware with headroom."""
    from .pallas_ring import _SUBLANES

    dk = d
    dv = d if dv is None else dv
    esz = jnp.dtype(dtype).itemsize
    limit = _VMEM_BUDGET if vmem_limit_bytes is None else vmem_limit_bytes
    sub = _SUBLANES.get(jnp.dtype(dtype), 8)
    cap = _MAX_TILE if window is None else min(_MAX_TILE, _lane_pad(window))
    if for_backward:
        for t in _tile_sizes(sb, sub, halves, cap):
            tiled = (2 * (t * (dk + dv) * esz  # q/do tiles, two slots
                          + 2 * t * _LANES * 4  # lse/delta tiles
                          + t * dk * 4)        # dq tile
                     + t * (dk + dv) * esz     # k/v tiles (wire dtype)
                     + t * (dk + dv) * 4       # dk/dv staging buffers
                     + t * (dk + dv) * 4       # dk/dv loop carries
                     + 4 * t * t * 4           # s/p/dp/ds temporaries
                     + t * (dk + dv) * 4)      # fold temporaries
            if tiled <= limit:
                return ("tiled", (t, t))
        return ("fallback", None)  # recompute always works

    def tiled(t):
        return (t * dk * esz                   # q tile
                + 2 * t * (dk + dv) * esz      # k/v tiles, two slots
                + 2 * t * _LANES * 4           # m, l tiles
                + t * dv * 4                   # o tile
                + 2 * t * t * 4                # score tile temporaries
                + t * (dk + dv) * 4)           # fold temporaries

    for t in _tile_sizes(sb, sub, halves, cap):
        if tiled(t) <= limit:
            return ("tiled", (t, t))
    raise NotImplementedError(
        f"ring attention cannot fit VMEM budget {limit} bytes: even the "
        f"minimal {sub}-row tile at d={d} needs ~{tiled(sub)} bytes "
        f"(Sb={sb}, Hq={hq}, Hkv={hkv}, {jnp.dtype(dtype).name}). "
        f"Raise vmem_limit_bytes or shrink the head dim.")


def _kernel(params_smem, *refs,
            axis_name: str, size: int, sb: int, dk: int, dv: int,
            scale: float, pipelined: bool, mesh_ids: bool,
            tiles: Tuple[int, int], causal: bool = False, hq: int = 1,
            hkv: int = 1, with_lse: bool = False, zigzag: bool = False,
            window: Optional[int] = None, sink: bool = False):
    """See module docstring for the step/slot/credit schedule.

    Multi-head layout (``hq`` query heads, ``hkv`` K/V heads — GQA when
    hkv < hq): the per-head [Sb, dh] planes are stacked along rows —
    q/out/m/l/o rows [h*Sb, (h+1)*Sb) belong to query head h; where K
    and V share a width (``dk == dv``) the circulating buffer stacks all
    K planes then all V planes ([hkv*Sb] + [hkv*Sb] rows), so ONE RDMA
    moves every head's K/V and the circulation/credit protocol is
    byte-identical to the single-head case (pure payload relabeling —
    verified by the GQA AttentionSim runs, tests/test_pallas_protocol.py);
    otherwise K ([hkv*Sb, dk]) and V ([hkv*Sb, dv]) circulate as two
    planes of one hop (``_SndGroup``).  Inputs after ``params_smem``:
    the [hq] f32 sink logits in SMEM (``sink``), q, then the K/V planes.

    ``tiles=(tq, tk)``: the fold's query and K/V tile rows; in a ring
    the m/l/o state lives in HBM between arrivals (module docstring), and
    a last pass writes out (and L) from it.

    ``zigzag`` (causal): the blocks are in the zigzag layout and
    each arrival folds the tile ranges of ``_zigzag_tiles``; otherwise a
    causal arrival folds those of ``_band_tiles`` (``window``: the
    sliding window's lower bound too).

    ``size == 1`` is a ring of one: the step-0 fold alone, with no
    landing slots, RDMA, barrier or credit.  Its state never leaves VMEM
    and there is no m/l/o in HBM: each q tile writes out (and L) from the
    q loop through the staging tiles, its write-back waited at the next
    q tile's epilogue or after the last.

    ``with_lse`` adds a second output ref carrying L = m + log l (the
    fused backward's residual); inference/fallback-backward paths skip
    its VMEM broadcast and HBM write entirely.

    ``sink``: query head h's sink logit s_h is the a == 0 fold's initial
    state (m = s_h, l = 1, o = 0), so it enters every row's denominator
    once, and L includes it."""
    P = size
    n_kv = 1 if dk == dv else 2  # K/V planes
    sinks_smem = refs[0] if sink else None
    refs = refs[int(sink):]
    q_hbm, kv_hbm = refs[0], refs[1:1 + n_kv]
    refs = refs[1 + n_kv:]
    n_out = 2 if with_lse else 1
    out_hbm, lse_hbm = refs[0], refs[1] if with_lse else None
    refs = refs[n_out:]
    if P > 1:
        comm_hbm = refs[:n_kv]  # landing slots, per plane
        m_hbm, l_hbm, o_hbm = refs[n_kv:n_kv + 3]  # state between arrivals
        refs = refs[n_kv + 3:]
    (qt_vmem, kt_vmem, vt_vmem, mt_vmem, lt_vmem, ot_vmem, tile_sems,
     copy_sem, send_sem, recv_sem, credit_sem, par_sems) = refs
    tq, tk = tiles
    tile_loop = _mk_tile_loop(tile_sems)
    left = params_smem[0]
    right = params_smem[1]
    my = params_smem[2]
    g = hq // hkv  # query heads per K/V head (GQA group size)
    dev_kw = _mk_dev_kw(mesh_ids, axis_name)
    neighbor_barrier = _mk_barrier(pipelined and P > 1, dev_kw, left, right)
    copy_sync = _mk_copy_sync(copy_sem)
    copy_par = _mk_copy_par(par_sems)
    # send u (0..P-2): the block computed at step u moves on
    if P > 1:
        fwd_rdma = _mk_snd_planes(kv_hbm, comm_hbm, send_sem, recv_sem,
                                  dev_kw, right)

    def out_copies(r0):
        """The write-back of the q tile at rows ``r0`` in a ring of one:
        out from ``ot_vmem`` (and L from ``mt_vmem``), one semaphore
        each."""
        pairs = [(ot_vmem, out_hbm.at[pl.ds(r0, tq)])]
        if with_lse:
            pairs.append((mt_vmem, lse_hbm.at[pl.ds(r0, tq)]))
        return [pltpu.make_async_copy(src, dst, par_sems.at[c])
                for c, (src, dst) in enumerate(pairs)]

    def wait_out(r0):
        for cp in out_copies(r0):
            cp.wait()

    # -- the fold: flash-style [tq, tk] inner loop, state in HBM in a ring --

    def fold(a, src):
        """Fold arrival ``a`` whose K/V block sits in the HBM planes
        ``src`` (``_kv_at``).  Reads never conflict with the concurrent
        forwarding RDMA (read/read); the credit follows both the fold
        and wait_send in program order, so a slot is recycled only after
        its block is folded and forwarded."""
        k_at, v_at = _kv_at(src, hkv * sb)

        def run(t, mask_of):
            """Fold the tile pairs of ``t`` (:class:`_Tiles`);
            ``mask_of(i, j)``: the tile pair's mask, or None."""
            def head(h, _):
                kvh = h // g
                base = _head_row(h, sb)

                def q_body(i, _):
                    r0 = base + i * tq
                    if a == 0:
                        copy_sync(q_hbm.at[pl.ds(r0, tq)], qt_vmem)
                        if sink:
                            m0 = jnp.full((tq, 1), sinks_smem[h],
                                          jnp.float32)
                            l0 = jnp.ones((tq, 1), jnp.float32)
                        else:
                            m0 = jnp.full((tq, 1), -jnp.inf, jnp.float32)
                            l0 = jnp.zeros((tq, 1), jnp.float32)
                        o0 = jnp.zeros((tq, dv), jnp.float32)
                    else:
                        # the q tile rides the same parallel batch as
                        # the state tiles (review round 5)
                        copy_par((q_hbm.at[pl.ds(r0, tq)], qt_vmem),
                                 (m_hbm.at[pl.ds(r0, tq)], mt_vmem),
                                 (l_hbm.at[pl.ds(r0, tq)], lt_vmem),
                                 (o_hbm.at[pl.ds(r0, tq)], ot_vmem))
                        m0 = mt_vmem[:, :1]
                        l0 = lt_vmem[:, :1]
                        o0 = ot_vmem[:]

                    def kv_tiles(j, slot):
                        r0 = kvh * sb + j * tk
                        return ((k_at(r0, tk), kt_vmem.at[slot]),
                                (v_at(r0, tk), vt_vmem.at[slot]))

                    def k_body(j, slot, carry):
                        m, l, o = carry
                        return _online_fold(qt_vmem[:], kt_vmem[slot],
                                            vt_vmem[slot], m, l, o, scale,
                                            mask_of(i, j))

                    m, l, o = tile_loop(*t.k_of(i), kv_tiles, k_body,
                                        (m0, l0, o0))
                    if P == 1:
                        # the only arrival: the tile is final, so out
                        # (and L) leave from here.  The previous tile's
                        # write-back still reads the staging tiles
                        # (tiles run in row order from row 0).
                        pl.when(r0 > 0)(lambda: wait_out(r0 - tq))
                        ot_vmem[:] = o / l
                        if with_lse:
                            mt_vmem[:] = jnp.broadcast_to(m + jnp.log(l),
                                                          (tq, _LANES))
                        for cp in out_copies(r0):
                            cp.start()
                        return 0
                    mt_vmem[:] = jnp.broadcast_to(m, (tq, _LANES))
                    lt_vmem[:] = jnp.broadcast_to(l, (tq, _LANES))
                    ot_vmem[:] = o
                    copy_par((mt_vmem, m_hbm.at[pl.ds(r0, tq)]),
                             (lt_vmem, l_hbm.at[pl.ds(r0, tq)]),
                             (ot_vmem, o_hbm.at[pl.ds(r0, tq)]))
                    return 0

                lax.fori_loop(t.q_lo, t.q_hi, q_body, 0)
                return 0

            lax.fori_loop(0, hq, head, 0)
            if P == 1:
                assert (t.q_lo, t.q_hi) == (0, sb // tq)  # every q tile
                wait_out(hq * sb - tq)

        # q tiles an arrival leaves out keep their m/l/o state in HBM
        # untouched; the diagonal arrival a == 0 visits every q tile
        if zigzag:
            run(*_zigzag_arrival(a, my, P, sb, tq, tk))
        elif not causal:
            run(_full_tiles(sb, tq, tk), lambda i, j: None)
        elif a == 0:
            run(*_band_arrival(0, my, sb, tq, tk, window))
        elif not _before_window(a, sb, window):
            # past blocks only: a future block (my < a) folds nothing
            pl.when(my >= a)(
                lambda: run(*_band_arrival(a, my, sb, tq, tk, window)))

    # the online-softmax state is written by the a=0 fold, which loads
    # no prior state
    neighbor_barrier()

    # step 0: my own block computes and starts circulating
    fold(0, kv_hbm)
    if P >= 2:
        fwd_rdma(0).start()
        if pipelined:
            fwd_rdma(0).wait_send()  # sem hygiene, as in attention_program
        else:
            fwd_rdma(0).wait()

    for a in range(1, P):
        slot = a % 2
        if pipelined:
            fwd_rdma(a - 1).wait_recv()  # arrival a lands in comm[slot]
        if a <= P - 2:
            # forward while the fold below runs; send a >= 2 first
            # waits for the credit arming its destination slot
            if pipelined:
                if a >= 2:
                    pltpu.semaphore_wait(credit_sem.at[(a + 1) % 2], 1)
                fwd_rdma(a).start()
            else:
                fwd_rdma(a).start()
                fwd_rdma(a).wait()
        fold(a, tuple(c.at[slot] for c in comm_hbm))
        if pipelined and a <= P - 2:
            # slot free only after the forward READ it out (wait_send),
            # then credit the writer for arrival a+2's reuse
            fwd_rdma(a).wait_send()
        if pipelined and a + 2 <= P - 1:
            pltpu.semaphore_signal(credit_sem.at[slot], inc=1,
                                   **dev_kw(left))

    # output of a ring (a ring of one wrote its own from the q loop): out
    # = o / l and (with_lse) the logsumexp L = m + log l — the fused
    # backward kernel's residual
    def out_body(i, _):
        r0 = i * tq
        copy_par((m_hbm.at[pl.ds(r0, tq)], mt_vmem),
                 (l_hbm.at[pl.ds(r0, tq)], lt_vmem),
                 (o_hbm.at[pl.ds(r0, tq)], ot_vmem))
        ot_vmem[:] = ot_vmem[:] / lt_vmem[:, :1]
        copy_sync(ot_vmem, out_hbm.at[pl.ds(r0, tq)])
        if with_lse:
            mt_vmem[:] = mt_vmem[:] + jnp.log(lt_vmem[:])
            copy_sync(mt_vmem, lse_hbm.at[pl.ds(r0, tq)])
        return 0

    if P > 1:
        lax.fori_loop(0, (hq * sb) // tq, out_body, 0)

    neighbor_barrier()


def _bwd_kernel(params_smem, *refs,
                axis_name: str, size: int, sb: int, dk: int, dv: int,
                scale: float,
                pipelined: bool, mesh_ids: bool, causal: bool,
                hq: int, hkv: int, tiles: Tuple[int, int],
                zigzag: bool = False, window: Optional[int] = None):
    """Fused ring-attention backward: [K, V] and [dK, dV] circulate for
    a FULL cycle of P sends; dQ accumulates locally; dK/dV accumulate
    in the circulating payload and land home at arrival P.
    Fold-BEFORE-forward (the payload is mutated, then moves on),
    double-buffered slots, credits gating sends u >= 2; the retire +
    credit of hop u-1 comes BEFORE hop u's credit wait — a signal must
    precede, in program order, any wait it transitively feeds, or the
    ring deadlocks at P >= 3 (review round 5 caught exactly that bug
    in the first ordering).  The schedule is model-checked by
    ``ring_model.AttentionBwdSim`` (sends 0..P-1, arrivals 1..P, the
    home arrival consumed without forwarding — exhaustive interleaving
    search + adversarial schedules, tests/test_pallas_protocol.py).

    SPLIT-DTYPE circulation (ISSUE 8 / VERDICT r5 #5 — the TPU side of
    the wire-dtype != fold-dtype seam): the K/V planes are PRISTINE
    INPUTS, so they circulate in the input dtype (bf16 inputs: half the
    wire bytes, bit-identical values — bf16→f32 is exact, so nothing is
    lost versus the old f32 circulation); the dK/dV planes are PARTIAL
    SUMS, so they circulate f32.  Each hop is two RDMAs on per-plane
    semaphore columns sharing one slot parity (_SndGroup): the
    send/credit/barrier protocol — and therefore the model check — is
    unchanged, only the payload is split.  Where K and V differ in width
    (``dk != dv``) each of the two is split again, K from V and dK from
    dV: four planes a hop.

    Per-pair algebra (flash backward, exact):  P_ = exp(S - L) (the
    saved logsumexp — no rescaling pass), dP = dO·Vᵀ,
    dS = P_∘(dP - D)·scale with D = rowsum(dO∘Out) precomputed,
    dQ += dS·K, dK += dSᵀ·Q, dV = P_ᵀ·dO.  The MXU folds are f32
    regardless of the circulation dtype (staged K/V tiles upcast at the
    matmul).

    ``tiles=(tq, tk)``: flash-style tiling.  dQ accumulates in its HBM
    output; each arrival loops K/V-tiles (outer, dK/dV tile carried as
    values) over Q-tiles (inner, residuals + dQ staged per tile, the
    next tile's copies in flight during this one's fold, its dQ written
    back while the next folds).  ``zigzag`` (causal): the blocks are in
    the zigzag layout and each arrival folds the tile ranges of
    ``_zigzag_tiles``; otherwise a causal arrival folds those of
    ``_band_tiles`` (``window``: with the sliding window's bound).

    ``size == 1`` is a ring of one: fold 0 writes dK/dV home directly,
    with no own/landing buffers, RDMA, barrier or credit.

    Refs after ``params_smem``: inputs q, the K/V planes, dO, L, delta;
    outputs dQ, the dK/dV planes (home), and in a ring the own and
    landing buffers of the K/V and the dK/dV planes."""
    P = size
    n = 1 if dk == dv else 2  # planes of K/V, and of dK/dV
    q_hbm, kv_hbm = refs[0], refs[1:1 + n]
    do_hbm, lse_hbm, delta_hbm, dq_hbm = refs[1 + n:5 + n]
    dkv_hbm = refs[5 + n:5 + 2 * n]
    refs = refs[5 + 2 * n:]
    if P > 1:
        own_kv_hbm, own_dkv_hbm, comm_kv_hbm, comm_dkv_hbm = (
            refs[i * n:(i + 1) * n] for i in range(4))
        refs = refs[4 * n:]
    else:
        # my own contribution is the whole result; nothing circulates
        own_kv_hbm = comm_kv_hbm = comm_dkv_hbm = None
        own_dkv_hbm = dkv_hbm
    (qt_vmem, dot_vmem, lset_vmem, deltat_vmem, kt_vmem, vt_vmem,
     accb_vmem, accb2_vmem, dqt_vmem, tile_sems, copy_sem, send_sem,
     recv_sem, credit_sem, par_sems) = refs
    tq, tk = tiles
    tile_loop = _mk_tile_loop(tile_sems)
    left = params_smem[0]
    right = params_smem[1]
    my = params_smem[2]
    g = hq // hkv
    dev_kw = _mk_dev_kw(mesh_ids, axis_name)
    neighbor_barrier = _mk_barrier(pipelined and P > 1, dev_kw, left, right)
    copy_sync = _mk_copy_sync(copy_sem)
    copy_par = _mk_copy_par(par_sems)

    # send u (0..P-1): the block folded at step u moves on; send 0
    # reads the assembled own-block buffer, not a comm slot.  The K/V
    # and dK/dV planes of a hop (split dtypes), one protocol (_SndGroup)
    if P > 1:
        snd = _mk_snd_planes(own_kv_hbm + own_dkv_hbm,
                             comm_kv_hbm + comm_dkv_hbm, send_sem, recv_sem,
                             dev_kw, right)

    def tiled_bounds(a):
        """(:class:`_Tiles`, mask_of) of arrival ``a``'s tiled fold,
        ``mask_of(i, j)`` the tile pair's mask or None — the forward's
        tile ranges and masks (``_zigzag_arrival`` / ``_band_arrival``).
        Off the zigzag layout a causal arrival a > 0 is strictly past or
        skipped by the caller's pl.when."""
        if zigzag:
            return _zigzag_arrival(a, my, P, sb, tq, tk)
        if causal:
            return _band_arrival(a, my, sb, tq, tk, window)
        return _full_tiles(sb, tq, tk), lambda i, j: None

    def fold_grads(kv_planes, dkv_planes, init_zero, bounds, slot=None):
        """Flash-tiled pair gradients: dK/dV tiles ride the inner-loop
        carry (loaded from — or, ``init_zero``, started at zero in —
        the ``dkv_planes``), residuals and the dQ accumulator stage per
        Q-tile straight from/to their HBM refs (dQ lives in its OUTPUT
        ref between arrivals).  ``kv_planes`` hold the arrived K/V
        block (``_kv_at``; ``slot``: both are landing slots);
        ``bounds`` are ``tiled_bounds``'."""
        t, mask_of = bounds
        k_at, v_at = _kv_at(kv_planes, hkv * sb, slot)
        dk_at, dv_at = _kv_at(dkv_planes, hkv * sb, slot)

        def head(h, _):
            kvh = h // g

            def j_body(j, _):
                kr = _head_row(kvh, sb) + j * tk
                copy_par((k_at(kr, tk), kt_vmem), (v_at(kr, tk), vt_vmem))

                def load():
                    copy_par((dk_at(kr, tk), accb_vmem),
                             (dv_at(kr, tk), accb2_vmem))

                def zero():
                    accb_vmem[:] = jnp.zeros((tk, dk), jnp.float32)
                    accb2_vmem[:] = jnp.zeros((tk, dv), jnp.float32)

                # zero the dK/dV tiles only for the FIRST query head of
                # each K/V group: later heads of the group must
                # accumulate into (not overwrite) what earlier heads
                # stored (re-zeroing per head would drop all but the
                # last head's own-block contribution under GQA)
                if not init_zero:
                    load()
                elif g == 1:
                    zero()
                else:
                    first = lax.rem(h, g) == 0
                    pl.when(first)(zero)
                    pl.when(jnp.logical_not(first))(load)
                dk0 = accb_vmem[:]
                dv0 = accb2_vmem[:]

                def q_rows(i):
                    return pl.ds(_head_row(h, sb) + i * tq, tq)

                def q_tiles(i, slot):
                    rows = q_rows(i)
                    return ((q_hbm.at[rows], qt_vmem.at[slot]),
                            (do_hbm.at[rows], dot_vmem.at[slot]),
                            (lse_hbm.at[rows], lset_vmem.at[slot]),
                            (delta_hbm.at[rows], deltat_vmem.at[slot]),
                            (dq_hbm.at[rows], dqt_vmem.at[slot]))

                def dq_store(i, slot):
                    return dqt_vmem.at[slot], dq_hbm.at[q_rows(i)]

                def i_body(i, slot, carry):
                    dk_t, dv_t = carry
                    dq_c, dk_c, dv_c = _pair_grad_tile(
                        qt_vmem[slot].astype(jnp.float32),
                        dot_vmem[slot].astype(jnp.float32),
                        lset_vmem[slot, :, :1], deltat_vmem[slot, :, :1],
                        kt_vmem[:].astype(jnp.float32),
                        vt_vmem[:].astype(jnp.float32), scale,
                        mask_of(i, j))
                    dqt_vmem[slot] = dqt_vmem[slot] + dq_c
                    return dk_t + dk_c, dv_t + dv_c

                # the last dQ write-back is waited at the loop's end, so
                # the next k tile's first dQ read sees it
                dk_t, dv_t = tile_loop(*t.q_of(j), q_tiles, i_body,
                                       (dk0, dv0), store=dq_store)
                accb_vmem[:] = dk_t
                accb2_vmem[:] = dv_t
                copy_par((accb_vmem, dk_at(kr, tk)),
                         (accb2_vmem, dv_at(kr, tk)))
                return 0

            lax.fori_loop(t.k_lo, t.k_hi, j_body, 0)
            return 0

        lax.fori_loop(0, hq, head, 0)

    # dQ accumulates in its output ref: zero it tile by tile
    def zq_body(i, _):
        dqt_vmem[0] = jnp.zeros((tq, dk), jnp.float32)
        copy_sync(dqt_vmem.at[0], dq_hbm.at[pl.ds(i * tq, tq)])
        return 0

    lax.fori_loop(0, (hq * sb) // tq, zq_body, 0)

    # fold 0 (own block) and assemble the circulating payload: K/V
    # planes straight from the input (IN the input/wire dtype), dK/dV
    # planes = my own f32 contribution (every other rank's accumulates
    # en route)
    if P > 1:
        for src, own in zip(kv_hbm, own_kv_hbm):
            copy_sync(src, own)
    fold_grads(kv_hbm, own_dkv_hbm, init_zero=True, bounds=tiled_bounds(0))

    neighbor_barrier()

    if P >= 2:
        snd(0).start()
        if not pipelined:
            snd(0).wait()

    # arrivals 1..P (the last my own block coming home); none at P = 1
    for a in (range(1, P + 1) if P > 1 else ()):
        slot = a % 2
        if pipelined:
            snd(a - 1).wait_recv()  # arrival a lands in comm[slot]
        if a < P:
            # fold BEFORE forward: the dK/dV planes must carry my
            # contribution when the block moves on
            kv_idx = lax.rem(my - a + P, P)

            def consume(a=a, slot=slot):
                fold_grads(comm_kv_hbm, comm_dkv_hbm, init_zero=False,
                           bounds=tiled_bounds(a), slot=slot)

            if causal and not zigzag:
                # the diagonal block is always arrival 0 (kv_idx == my
                # iff a ≡ 0 mod P), so arrivals 1..P-1 are either
                # strictly past (mask provably all-True without a
                # window — skip its materialization) or future (skip
                # everything); a past block wholly before the window
                # folds nothing either
                if not _before_window(a, sb, window):
                    pl.when(kv_idx < my)(consume)
            else:
                # full attention, or zigzag: every arrival folds (half
                # a block on the zigzag layout)
                consume()
            if pipelined:
                # FIRST retire the previous hop and credit its slot —
                # this signal transitively feeds the right neighbor's
                # credit wait below; emitting it after our own wait
                # would close a ring-wide cycle (deadlock at P >= 3)
                snd(a - 1).wait_send()
                if 1 <= a - 1 <= P - 2:
                    pltpu.semaphore_signal(credit_sem.at[(a - 1) % 2],
                                           inc=1, **dev_kw(left))
                if a >= 2:
                    pltpu.semaphore_wait(credit_sem.at[(a + 1) % 2], 1)
                snd(a).start()
            else:
                snd(a).start()
                snd(a).wait()
        else:
            # home arrival: my block returns with every rank's dK/dV
            if pipelined:
                snd(a - 1).wait_send()
            for comm, home in zip(comm_dkv_hbm, dkv_hbm):
                copy_sync(comm.at[slot], home)

    neighbor_barrier()


def _ring_neighbors(axis_name: str, size: int) -> jnp.ndarray:
    """[left, right, my] int32 SMEM params (my = causal block index)."""
    idx = lax.axis_index(axis_name)
    return jnp.stack([lax.rem(idx - 1 + size, size),
                      lax.rem(idx + 1, size), idx]).astype(jnp.int32)


def _fallback_attention(q, k, v, axis_name: str, size: int, scale: float,
                        causal: bool = False, window: Optional[int] = None,
                        sinks=None):
    """The same online-softmax ring as jax ops over ppermute — the
    vma/multi-axis interpreter path, and the recompute body of the
    out-of-budget custom-vjp backward.  Accepts both layouts ([Sb, d]
    and [H, Sb, d]); the multi-head ring rotates the WHOLE [Hkv, Sb, d]
    K/V stacks once per step (one ppermute pair per step, exactly like
    the kernel's single circulating RDMA) with per-head folds inside —
    NOT one ring per head (review round 4).  ``sinks`` ([Hq]): each
    head's state starts at m = s_h, l = 1, as in the kernel."""
    multihead = q.ndim == 3
    q3 = q if multihead else q[None]
    k3 = k if multihead else k[None]
    v3 = v if multihead else v[None]
    hq, sb, _ = q3.shape
    dv = v3.shape[-1]
    hkv = k3.shape[0]
    g = hq // hkv
    world_pairs = _world_pairs_of(size, None)
    perm = world_pairs([(r, (r + 1) % size) for r in range(size)])
    my = lax.axis_index(axis_name)
    if sinks is None:
        m = [jnp.full((sb, 1), -jnp.inf, jnp.float32) for _ in range(hq)]
        l = [jnp.zeros((sb, 1), jnp.float32) for _ in range(hq)]
    else:
        m = [jnp.broadcast_to(sinks[h], (sb, 1)) for h in range(hq)]
        l = [jnp.ones((sb, 1), jnp.float32) for _ in range(hq)]
    o = [jnp.zeros((sb, dv), jnp.float32) for _ in range(hq)]
    kb, vb = k3, v3
    for step in range(size):
        mask = None
        if causal:
            kv_idx = lax.rem(my - step + size, size)
            # shared by every head
            mask = _causal_mask(my, kv_idx, sb, window=window)
        for h in range(hq):
            m[h], l[h], o[h] = _online_fold(q3[h], kb[h // g], vb[h // g],
                                            m[h], l[h], o[h], scale, mask)
        if step < size - 1:  # the last fold's blocks need no rotation
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
    out = jnp.stack([(o[h] / l[h]) for h in range(hq)]).astype(q.dtype)
    return out if multihead else out[0]


def pallas_ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          axis_name: str, size: int, *,
                          scale: float = None, causal: bool = False,
                          window: Optional[int] = None,
                          sinks: Optional[jnp.ndarray] = None,
                          interpret: bool = False,
                          vmem_limit_bytes: Optional[int] = None
                          ) -> jnp.ndarray:
    """Exact attention (full, causal with ``causal=True``, or causal
    within a sliding ``window``) over a sequence-sharded axis.  Two
    shapes:

    * single-head: ``q``/``k`` = this device's [Sb, d_qk] blocks, ``v``
      its [Sb, d_v] block;
    * multi-head / GQA: ``q`` = [Hq, Sb, d_qk], ``k`` = [Hkv, Sb, d_qk],
      ``v`` = [Hkv, Sb, d_v] with ``Hq % Hkv == 0`` — query head h
      attends K/V head ``h // (Hq//Hkv)`` (Hkv == Hq is classic
      multi-head, Hkv == 1 is MQA).  ALL heads ride ONE circulating hop
      per step.

    Returns this device's output block, shaped like ``q`` but ``d_v``
    wide.  Call inside shard_map over a mesh with ``axis_name``; the
    global sequence is the concatenation of the blocks in rank order.
    ``d_v`` must be a multiple of 128; a ``d_qk`` that is not (192) is
    zero-padded to one before the kernels (module docstring).  The
    default scale is 1/sqrt(d_qk).

    ``sinks`` ([Hq], float32, optional): a learnable logit per query
    head in every row's softmax denominator, with no value:
    o_i = sum_j e^{z_ij} v_j / (e^{s_h} + sum_j e^{z_ij}).  It is
    differentiable (the custom VJP returns dsinks) and counted by the
    ``attention_sink`` mpit pvar.

    The compiled path is the in-kernel RDMA circulation described in
    the module docstring, its folds tiled by ``attention_vmem_plan``
    from ``vmem_limit_bytes`` (default ~12 MiB); where no tile fits,
    the forward runs the loud ppermute-ring fallback and the backward
    recomputes through the pure-jax ring.  ``interpret=True`` (the CPU
    tier) runs the serial same-kernel
    path, or — under vma typing / a multi-axis mesh — the ppermute
    fallback with the shared loud warning.  ``interpret`` may also be a
    ``pltpu.InterpretParams``: Pallas's TPU interpreter, which models
    the local DMAs and their semaphores (a ring of one needs no more).

    Differentiable: the forward emits the logsumexp residual and the
    backward runs its own fused ring kernel ([K,V,dK,dV] circulation).

    Sliding window (``causal=True, window=W``): the query at global
    position i attends key j iff ``i - W < j <= i`` — W keys, itself
    included, as Hugging Face's ``sliding_window`` means.  The tiled
    folds skip the k tiles wholly before a query tile's window and mask
    its edge tile as they mask the diagonal; an arrival wholly before
    every local query's window folds nothing (it still circulates).
    The tiles are at most W rows, rounded up to whole lanes.  Windowed
    kernels are named ``mpi_tpu_swa_attention_fwd`` / ``_bwd``; the
    ``attention_window`` mpit pvar counts the traces that take the
    windowed tile ranges, and ``attention_band_pairs`` adds the
    query-key pairs each such trace's tile plan folds (``_band_pairs``).

    ``size == 1`` runs the same kernels as a ring of one: the step-0
    fold alone, with no landing slots, RDMA, barrier or credit, and
    none of a collective's compiler parameters; the forward writes out
    (and the residual) from its q loop, with no softmax state in HBM
    (``attention_direct_out`` mpit pvar).

    Causal layout: with ``causal=True``, no window, ``size >= 2``, a
    fused backward and tiles that divide Sb/2, the kernels run on
    the zigzag layout (module docstring), which gives every rank the
    same causal work; two ppermutes per array move the halves in and
    out.  The blocks this function takes and returns stay contiguous in
    rank order, and the circulation protocol is the same on either
    layout.  A windowed call keeps the contiguous layout, which is
    already balanced for W <= Sb."""
    if q.ndim not in (2, 3):
        raise ValueError(
            f"ring attention wants [Sb, dh] or [H, Sb, dh] blocks, got "
            f"q {q.shape}")
    if k.shape[:-1] != v.shape[:-1] or q.shape[-2:] != k.shape[-2:] or \
            not q.ndim == k.ndim == v.ndim:
        raise ValueError(
            f"ring attention wants equal [.., rows, d] blocks for q/k (k/v "
            f"may differ from q only in the HEAD count, v from k only in "
            f"its width), got {q.shape}/{k.shape}/{v.shape}")
    if k.dtype != q.dtype or v.dtype != q.dtype:
        raise ValueError(
            f"ring attention wants one dtype for q/k/v (the circulating "
            f"K/V buffer is allocated as q's), got "
            f"{q.dtype}/{k.dtype}/{v.dtype}")
    multihead = q.ndim == 3
    hq = q.shape[0] if multihead else 1
    hkv = k.shape[0] if multihead else 1
    if hkv < 1 or hq % hkv or hkv > hq:
        raise ValueError(
            f"GQA wants Hq a positive multiple of Hkv, got Hq={hq} "
            f"Hkv={hkv}")
    sb, d_qk = q.shape[-2:]
    dv = v.shape[-1]
    if dv % _LANES:
        raise NotImplementedError(
            f"value head dim must be a multiple of {_LANES} (lane width), "
            f"got {dv}")
    from .pallas_ring import _SUBLANES

    sub = _SUBLANES.get(jnp.dtype(q.dtype), 8)
    if sb % sub:
        raise NotImplementedError(
            f"block rows must be a multiple of {sub} ({jnp.dtype(q.dtype)} "
            f"sublane tile), got {sb}")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"a sliding window wants causal=True and window >= 1, got "
            f"causal={causal} window={window}")
    if sinks is not None:
        if jnp.shape(sinks) != (hq,):
            raise ValueError(
                f"sinks wants one logit per query head, shape ({hq},), got "
                f"{jnp.shape(sinks)}")
        sinks = jnp.asarray(sinks, jnp.float32)
    if scale is None:
        scale = 1.0 / float(np.sqrt(d_qk))
    d = _lane_pad(d_qk)  # the q/k width the kernels stage
    # shared dtype/vma/mesh probing with the ring collectives (f32/bf16)
    vma_on, multi_axis = _check_args(q, axis_name, size, sub, "sum")
    # Tiles from the VMEM budget.  A forward NO tile can satisfy
    # degrades to the ppermute ring (graceful degradation, ROADMAP r5
    # #4): primal-identical and correct at any size, just without the
    # kernel's RDMA overlap — so the substitution is LOUD (warning +
    # ``attention_fallbacks`` mpit pvar), exactly like the vma/multi-
    # axis interpreter fallback, instead of the former
    # NotImplementedError that made an over-tight budget fatal.  A
    # causal ring prefers tiles that divide Sb/2 (the zigzag layout's).
    from .. import mpit

    halves = causal and window is None and size >= 2
    plan = functools.partial(attention_vmem_plan, sb, d, hq, hkv, q.dtype,
                             vmem_limit_bytes, halves=halves, dv=dv,
                             window=window)
    try:
        _, tiles = plan(for_backward=False)
    except NotImplementedError as e:
        import warnings

        warnings.warn(
            f"ring attention forward out of VMEM budget — executing the "
            f"ppermute ring fallback; timings will not reflect the RDMA "
            f"kernel. ({e})", RuntimeWarning, stacklevel=2)
        mpit.count(attention_oob=1)
        return _fallback_attention(q, k, v, axis_name, size, scale, causal,
                                   window, sinks)
    _, bwd_tiles = plan(for_backward=True)
    bwd_fused = bwd_tiles is not None

    def _reference(q_, k_, v_, sinks_=None):
        """Pure-jax ring (differentiable) — primal-identical to the
        kernel; the out-of-budget custom-vjp backward recomputes
        through it."""
        return _fallback_attention(q_, k_, v_, axis_name, size, scale,
                                   causal, window, sinks_)

    if (vma_on or (multi_axis and size > 1)) and interpret:
        _fallback("ring_attention", axis_name, vma_on, multi_axis)
        return _reference(q, k, v, sinks)
    # causal layout (module docstring): zigzag where both kernels fold
    # tiles of half blocks; every other case keeps contiguous blocks
    zigzag = (halves and bwd_fused
              and all((sb // 2) % t == 0 for t in tiles + bwd_tiles))
    mpit.count(attention_zigzag=int(zigzag),
               attention_window=int(window is not None),
               attention_sink=int(sinks is not None),
               attention_direct_out=int(size == 1),
               attention_band_pairs=0 if window is None else
               _band_pairs(size, sb, *tiles, window))
    ring = size > 1
    kind = "ring" if window is None else "swa"
    n_kv = 1 if d == dv else 2  # K/V planes (the kernels' layout)

    vma = (frozenset(jax.typeof(q).vma) | {axis_name} if vma_on
           else frozenset())

    def _hbm_call(name, kern, outs, scratch, collective_id, smem, *args):
        """One ring-kernel launch, named ``name`` in the program and in a
        device trace: ``smem`` in SMEM, ``args`` in HBM.
        ``outs`` = (shape, dtype) pairs, all in HBM: the results first,
        then the kernel's HBM working buffers (landing slots, tiled
        state).  Mosaic allocates scratch only in VMEM/SMEM, so those
        buffers are extra outputs the caller drops."""
        return pl.pallas_call(
            kern,
            out_shape=tuple(jax.ShapeDtypeStruct(s, dt, vma=vma)
                            for s, dt in outs),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(smem) +
                     [pl.BlockSpec(memory_space=pl.ANY)] * len(args),
            out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                            for _ in outs),
            scratch_shapes=scratch,
            # a ring of one is a local kernel: no collective id, no side
            # effects, so its executable caches like any other
            compiler_params=None if interpret else pltpu.CompilerParams(
                collective_id=collective_id if ring else None,
                has_side_effects=ring, vmem_limit_bytes=vmem_limit_bytes),
            interpret=interpret,
            name=name,
        )(*smem, *args)

    def _flat(x, heads, width):
        return x.reshape(heads * sb, width) if multihead else x

    def _kv_planes(kf, vf):
        """The kernels' K/V planes: one [K; V] buffer where K and V share
        a width, else K and V."""
        return (jnp.concatenate([kf, vf], axis=0),) if n_kv == 1 else (kf, vf)

    def _kv_shapes(lead=()):
        """(shape, rows) of the K/V planes, ``lead`` dims first."""
        if n_kv == 1:
            return [lead + (2 * hkv * sb, d)]
        return [lead + (hkv * sb, d), lead + (hkv * sb, dv)]

    def _kernel_call(q_, k_, v_, sinks_=None, *, with_lse):
        """→ out [shaped like q, ``dv`` wide], or (out, lse [hq*sb,
        _LANES] f32) when ``with_lse`` (the fused-backward residual;
        inference and fallback-backward paths skip its cost entirely)."""
        # flat multi-head layout (see _kernel docstring): q/out stack
        # query heads along rows; the circulating planes stack every
        # head's K (V) so one hop carries every head
        qf = _flat(q_, hq, d)
        kv = _kv_planes(_flat(k_, hkv, d), _flat(v_, hkv, dv))
        params = _ring_neighbors(axis_name, size)
        smem = (params,) if sinks_ is None else (params, sinks_)
        kern = functools.partial(
            _kernel, axis_name=axis_name, size=size, sb=sb, dk=d, dv=dv,
            scale=scale, pipelined=not interpret, mesh_ids=multi_axis,
            causal=causal, hq=hq, hkv=hkv, tiles=tiles,
            with_lse=with_lse, zigzag=zigzag, window=window,
            sink=sinks_ is not None)
        outs = [((hq * sb, dv), jnp.float32)]
        if with_lse:
            outs.append(((hq * sb, _LANES), jnp.float32))
        if ring:  # a ring of one keeps its state in VMEM
            outs += [(s, q.dtype) for s in _kv_shapes((2,))]  # landing slots
            outs += [((hq * sb, _LANES), jnp.float32),    # m state
                     ((hq * sb, _LANES), jnp.float32),    # l state
                     ((hq * sb, dv), jnp.float32)]        # o state
        tq, tk = tiles
        sems = (2,) if n_kv == 1 else (2, n_kv)
        scratch = [
            pltpu.VMEM((tq, d), q.dtype),                # q tile
            pltpu.VMEM((2, tk, d), q.dtype),             # k tiles (slots)
            pltpu.VMEM((2, tk, dv), q.dtype),            # v tiles (slots)
            pltpu.VMEM((tq, _LANES), jnp.float32),       # m tile
            pltpu.VMEM((tq, _LANES), jnp.float32),       # l tile
            pltpu.VMEM((tq, dv), jnp.float32),           # o tile
            pltpu.SemaphoreType.DMA((2, 2)),             # k/v tiles (slot)
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(sems),               # send (parity, plane)
            pltpu.SemaphoreType.DMA(sems),               # recv (parity, plane)
            pltpu.SemaphoreType.REGULAR((2,)),           # slot credits
            pltpu.SemaphoreType.DMA((8,)),               # parallel tiles
        ]
        res = _hbm_call(f"mpi_tpu_{kind}_attention_fwd", kern, outs,
                        scratch, 16, smem, qf, *kv)
        out = res[0].astype(q_.dtype)
        out = out.reshape(hq, sb, dv) if multihead else out
        return (out, res[1]) if with_lse else out

    def _bwd_kernel_call(res, ct):
        """Fused backward: ``res`` = (q, k, v[, sinks], out, lse) → (dq,
        dk, dv[, dsinks]) like q/k/v[/sinks]."""
        q_, k_, v_ = res[:3]
        sinks_ = res[3] if len(res) == 6 else None
        out, lse = res[-2:]
        qf = _flat(q_, hq, d)
        kf, vf = _flat(k_, hkv, d), _flat(v_, hkv, dv)
        dof = _flat(ct, hq, dv)
        outf = _flat(out, hq, dv)
        # the K/V planes circulate in the INPUT dtype (split-dtype seam:
        # pristine inputs lose nothing below f32, and bf16 halves their
        # wire bytes); only the dK/dV partial sums ride f32
        kv = _kv_planes(kf, vf)
        delta = jnp.sum(dof.astype(jnp.float32) * outf.astype(jnp.float32),
                        axis=1, keepdims=True)
        grads = ()
        if sinks_ is not None:
            # dsinks_h = -sum_i e^{s_h - L_i} delta_i over my rows of head h
            p_sink = jnp.exp(sinks_[:, None] - lse[:, 0].reshape(hq, sb))
            grads = (-jnp.sum(p_sink * delta.reshape(hq, sb), axis=1),)
        delta = jnp.broadcast_to(delta, (hq * sb, _LANES))
        params = _ring_neighbors(axis_name, size)
        kern = functools.partial(
            _bwd_kernel, axis_name=axis_name, size=size, sb=sb, dk=d, dv=dv,
            scale=scale, pipelined=not interpret, mesh_ids=multi_axis,
            causal=causal, hq=hq, hkv=hkv, tiles=bwd_tiles, zigzag=zigzag,
            window=window)
        outs = [((hq * sb, d), jnp.float32)]             # dQ
        outs += [(s, jnp.float32) for s in _kv_shapes()]  # dK/dV (home)
        if ring:
            outs += ([(s, q.dtype) for s in _kv_shapes()]         # own [K,V]
                     + [(s, jnp.float32) for s in _kv_shapes()]   # own [dK,dV]
                     + [(s, q.dtype) for s in _kv_shapes((2,))]   # K/V slots
                     + [(s, jnp.float32)                          # dK/dV slots
                        for s in _kv_shapes((2,))])
        tqb, tkb = bwd_tiles
        scratch = [
            pltpu.VMEM((2, tqb, d), q.dtype),            # q tiles (slots)
            pltpu.VMEM((2, tqb, dv), q.dtype),           # dOut tiles
            pltpu.VMEM((2, tqb, _LANES), jnp.float32),   # lse tiles
            pltpu.VMEM((2, tqb, _LANES), jnp.float32),   # delta tiles
            pltpu.VMEM((tkb, d), q.dtype),               # k tile
            pltpu.VMEM((tkb, dv), q.dtype),              # v tile
            pltpu.VMEM((tkb, d), jnp.float32),           # dk buffer
            pltpu.VMEM((tkb, dv), jnp.float32),          # dv buffer
            pltpu.VMEM((2, tqb, d), jnp.float32),        # dq tiles
            pltpu.SemaphoreType.DMA((2, 6)),             # 5 loads, dq out
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, 2 * n_kv)),      # send (parity, plane)
            pltpu.SemaphoreType.DMA((2, 2 * n_kv)),      # recv (parity, plane)
            pltpu.SemaphoreType.REGULAR((2,)),           # slot credits
            pltpu.SemaphoreType.DMA((8,)),               # parallel tiles
        ]
        dq, *dkv = _hbm_call(f"mpi_tpu_{kind}_attention_bwd", kern, outs,
                             scratch, 17, (params,), qf, *kv, dof, lse,
                             delta)[:1 + n_kv]
        dq = dq.astype(q_.dtype)
        if n_kv == 1:
            dk = dkv[0][:hkv * sb].astype(k_.dtype)
            dv_ = dkv[0][hkv * sb:].astype(v_.dtype)
        else:
            dk, dv_ = dkv[0].astype(k_.dtype), dkv[1].astype(v_.dtype)
        if multihead:
            dq, dk, dv_ = (dq.reshape(hq, sb, d), dk.reshape(hkv, sb, d),
                           dv_.reshape(hkv, sb, dv))
        return (dq, dk, dv_) + grads

    def _primal(*args):
        return _kernel_call(*args, with_lse=False)

    # Differentiable wrapper: jax cannot autodiff through the kernel's
    # remote DMAs, so the backward is either the fused [K,V,dK,dV]
    # ring kernel above or a recompute
    # through the pure-jax ring (out-of-budget fallback; ppermutes
    # transpose to the inverse rotation) — either way the fused kernel
    # stays the forward hot path and training can jax.grad straight
    # through it.
    attn = jax.custom_vjp(_primal)

    def _fwd(*args):
        if not bwd_fused:
            # the recompute backward needs only the inputs — skip the
            # lse output and do not pin out/lse across fwd..bwd
            return _kernel_call(*args, with_lse=False), args
        out, lse = _kernel_call(*args, with_lse=True)
        return out, args + (out, lse)

    def _bwd(res, ct):
        if not bwd_fused:
            _, vjp = jax.vjp(_reference, *res)
            return vjp(ct)
        return _bwd_kernel_call(res, ct)

    attn.defvjp(_fwd, _bwd)
    if d != d_qk:
        # whole lanes for the kernels' DMAs; autodiff slices dq/dk back
        pad = [(0, 0)] * (q.ndim - 1) + [(0, d - d_qk)]
        q, k = jnp.pad(q, pad), jnp.pad(k, pad)
    extra = ()
    if sinks is not None:
        if vma_on and axis_name not in jax.typeof(sinks).vma:
            # the custom VJP's dsinks are my rows' partial sums (varying);
            # autodiff transposes this cast to the sum over the ring
            sinks = lax.pcast(sinks, axis_name, to="varying")
        extra = (sinks,)
    if not zigzag:
        return attn(q, k, v, *extra)
    # the exchange stays outside the custom_vjp, so autodiff transposes
    # it: the kernels see zigzag blocks only, the caller contiguous ones
    zz = functools.partial(_to_zigzag, axis_name=axis_name, size=size)
    return _from_zigzag(attn(zz(q), zz(k), zz(v), *extra), axis_name, size)
