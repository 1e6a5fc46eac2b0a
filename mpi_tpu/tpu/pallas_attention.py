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

**VMEM planning** (``attention_vmem_plan`` — VERDICT r4 missing #2):
the fold is executed in one of two modes chosen at trace time from a
VMEM budget:

* *resident* — Q, the K/V staging buffer, and the m/l/o state all live
  in VMEM and each fold materializes one [Sb, Sb] score block.  The
  fast path for blocks up to ~1-2k rows at d=128/f32.
* *tiled* — flash-attention-style inner tiling: the m/l/o state lives
  in HBM; each arrival loops over [tq]-row query tiles and
  [tk]-row K/V tiles (``lax.fori_loop``), staging each tile through
  small VMEM buffers, so the live score block is [tq, tk] and the
  block size is bounded by HBM, not VMEM.  Tile sizes are the largest
  sublane-aligned divisors of Sb, at most 512, that fit the budget.

Either way the RDMA circulation (slots, credits, barriers) is
IDENTICAL — the fold is a local subroutine between protocol events, so
``AttentionSim``'s verification covers both modes.  An impossible
budget (no tile fits) is diagnosed at trace time with the math shown.

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
``ring_model.AttentionBwdSim``.  The backward fold is VMEM-planned
like the forward: resident, or flash-tiled (dQ accumulating in its
HBM output; a K/V-tile outer loop carries dK/dV accumulators over a
Q-tile inner loop), so long-context training stays on the fused
kernels; only an impossible budget falls back to recomputing through
the pure-jax ppermute ring (correct at any size).

Under the interpreter (CPU tier) RDMAs run serially (start+wait, no
credits/barriers) — same data path, no overlap; under vma typing or a
multi-axis mesh the interpreter executes a ppermute ring fallback
(same online-softmax algebra as jax ops) with the shared loud-fallback
warning.  The compiled multi-axis path addresses neighbors by mesh
coordinate exactly like pallas_ring.

Restrictions (diagnosed): f32/bf16; head dim ``d`` a multiple of 128
(lane width); block rows ``Sb`` a multiple of 8 (sublane tile); a
VMEM budget no tile size can satisfy raises with the numbers.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

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
# ops, so compile time grows with tq*tk times the heads: 8 heads of
# [1024, 1024] tiles took ~95 s to compile for v5e, [512, 512] ~17 s.
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
                 tq: Optional[int] = None, tk: Optional[int] = None):
    """[tq,tk] causal mask for rows ``i0..`` of query block ``my`` vs
    rows ``j0..`` of key block ``kv_idx`` (block indices traced, tile
    offsets traced or static): global key position must not exceed
    global query position.  Defaults cover the whole [Sb,Sb] block."""
    tq = sb if tq is None else tq
    tk = sb if tk is None else tk
    qi = my * sb + i0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kj = kv_idx * sb + j0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return kj <= qi


def _tile_sizes(sb: int, sub: int):
    """Sublane-aligned divisors of ``sb`` up to ``_MAX_TILE``, descending."""
    return [t for t in range(min(sb, _MAX_TILE), 0, -1)
            if t % sub == 0 and sb % t == 0]


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
    tiled folds stage several disjoint tiles per step (k+v; the m/l/o
    state; the backward's five residuals) and serializing them exposes
    every transfer's full HBM latency on chip (round 5).  Each copy
    gets its own semaphore by POSITION (all indices Python-static)."""
    def copy_par(*pairs):
        cps = [pltpu.make_async_copy(src, dst, par_sems.at[i])
               for i, (src, dst) in enumerate(pairs)]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    return copy_par


def _pair_grad_tile(qh, doh, lse1, delta1, kb, vb, scale, mask=None):
    """ONE copy of the flash-backward algebra (review round 5: the
    resident and tiled folds must not carry separate copies of it):
    given f32 Q/dO rows, their lse/delta columns, and a K/V tile,
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


class _SndPair:
    """Both planes of one split-dtype circulation hop as one descriptor:
    every protocol call fans out to the K/V-plane and dK/dV-plane RDMAs
    (same hop, same slot parity, per-plane semaphore columns), so the
    backward's send/credit schedule reads — and model-checks — exactly
    like the single-buffer version."""

    __slots__ = ("kv", "dkv")

    def __init__(self, kv, dkv):
        self.kv, self.dkv = kv, dkv

    def start(self):
        self.kv.start()
        self.dkv.start()

    def wait(self):
        self.kv.wait()
        self.dkv.wait()

    def wait_send(self):
        self.kv.wait_send()
        self.dkv.wait_send()

    def wait_recv(self):
        self.kv.wait_recv()
        self.dkv.wait_recv()


def attention_vmem_plan(sb: int, d: int, hq: int, hkv: int, dtype,
                        vmem_limit_bytes: Optional[int] = None,
                        for_backward: bool = False):
    """Choose the fold execution mode from a VMEM budget (trace time).

    Returns ``("resident", None)`` when the whole-block fold fits, or
    ``("tiled", (tq, tk))`` with the largest sublane-aligned divisor
    tile (at most ``_MAX_TILE`` rows) that fits — for the forward AND
    (round 5) the backward.  A backward no tile can satisfy returns ``("fallback", None)`` (→
    ppermute recompute, correct at any size); the forward instead
    raises NotImplementedError with the arithmetic — which the caller
    (pallas_ring_attention) converts into the loud ppermute-ring
    fallback (warning + ``attention_fallbacks`` pvar), so an over-tight
    budget degrades instead of failing (ROADMAP r5 #4).

    The estimates are deliberately generous (temporaries counted at
    f32, a spare plane for Mosaic's fusions) so a "resident" or
    "tiled" verdict holds on real hardware with headroom."""
    from .pallas_ring import _SUBLANES

    esz = jnp.dtype(dtype).itemsize
    limit = _VMEM_BUDGET if vmem_limit_bytes is None else vmem_limit_bytes
    sub = _SUBLANES.get(jnp.dtype(dtype), 8)
    if for_backward:
        resident = (hq * sb * d * esz          # Q
                    + hq * sb * d * esz        # dOut
                    + 2 * hq * sb * _LANES * 4  # lse, delta staging
                    + 2 * hkv * sb * d * esz   # K/V staging (wire dtype)
                    + 2 * hkv * sb * d * 4     # dK/dV staging
                    + hq * sb * d * 4          # dQ accumulator
                    + 4 * sb * sb * 4          # s/p/dp/ds temporaries
                    + 2 * sb * d * 4)          # fold temporaries
        if resident <= limit:
            return ("resident", None)
        for t in _tile_sizes(sb, sub):
            tiled = (2 * t * d * esz           # q/do tiles
                     + 2 * t * _LANES * 4      # lse/delta tiles
                     + 2 * t * d * esz         # k/v tiles (wire dtype)
                     + 2 * t * d * 4           # dk/dv staging buffers
                     + t * d * 4               # dq tile
                     + 2 * t * d * 4           # dk/dv loop carries
                     + 4 * t * t * 4           # s/p/dp/ds temporaries
                     + 2 * t * d * 4)          # fold temporaries
            if tiled <= limit:
                return ("tiled", (t, t))
        return ("fallback", None)  # recompute always works
    resident = (hq * sb * d * esz              # Q
                + 2 * hkv * sb * d * esz       # K/V staging
                + 2 * hq * sb * _LANES * 4     # m, l ([.., 1] buffers are
                #                                lane-padded to 128 lanes)
                + hq * sb * _LANES * 4         # lse staging
                + hq * sb * d * 4              # o accumulator
                + 2 * sb * sb * 4              # score + exp temporaries
                + 2 * sb * d * 4)              # fold temporaries
    if resident <= limit:
        return ("resident", None)
    for t in _tile_sizes(sb, sub):
        tiled = (3 * t * d * esz               # q/k/v tiles
                 + 2 * t * _LANES * 4          # m, l tiles
                 + t * d * 4                   # o tile
                 + 2 * t * t * 4               # score tile temporaries
                 + 2 * t * d * 4)              # fold temporaries
        if tiled <= limit:
            return ("tiled", (t, t))
    t = sub
    need = 3 * t * d * esz + 2 * t * _LANES * 4 + t * d * 4 \
        + 2 * t * t * 4 + 2 * t * d * 4
    raise NotImplementedError(
        f"ring attention cannot fit VMEM budget {limit} bytes: even the "
        f"minimal {t}-row tile at d={d} needs ~{need} bytes "
        f"(Sb={sb}, Hq={hq}, Hkv={hkv}, {jnp.dtype(dtype).name}). "
        f"Raise vmem_limit_bytes or shrink the head dim.")


def _kernel(params_smem, q_hbm, kv_hbm, *refs,
            axis_name: str, size: int, sb: int, d: int,
            scale: float, pipelined: bool, mesh_ids: bool,
            causal: bool = False, hq: int = 1, hkv: int = 1,
            tiles: Optional[Tuple[int, int]] = None,
            with_lse: bool = False):
    """See module docstring for the step/slot/credit schedule.

    Multi-head layout (``hq`` query heads, ``hkv`` K/V heads — GQA when
    hkv < hq): the per-head [Sb, dh] planes are stacked along rows —
    q/out/m/l/o rows [h*Sb, (h+1)*Sb) belong to query head h; the
    circulating buffer stacks all K planes then all V planes
    ([hkv*Sb] + [hkv*Sb] rows), so ONE RDMA moves every head's K/V and
    the circulation/credit protocol is byte-identical to the
    single-head case (pure payload relabeling — verified by the GQA
    AttentionSim runs, tests/test_pallas_protocol.py).

    ``tiles=None`` → resident fold; ``tiles=(tq, tk)`` → flash-style
    inner tiling with the m/l/o state in HBM (module
    docstring).  The protocol events are identical in both modes.

    ``with_lse`` adds a second output ref carrying L = m + log l (the
    fused backward's residual); inference/fallback-backward paths skip
    its VMEM broadcast and HBM write entirely."""
    if with_lse:
        out_hbm, lse_hbm = refs[0], refs[1]
        refs = refs[2:]
    else:
        out_hbm, lse_hbm = refs[0], None
        refs = refs[1:]
    if tiles is None:
        if with_lse:
            (comm_hbm, q_vmem, kv_vmem, m_vmem, l_vmem, o_vmem, lse_vmem,
             copy_sem, send_sem, recv_sem, credit_sem,
             par_sems) = refs
        else:
            (comm_hbm, q_vmem, kv_vmem, m_vmem, l_vmem, o_vmem,
             copy_sem, send_sem, recv_sem, credit_sem,
             par_sems) = refs
    else:
        (comm_hbm, m_hbm, l_hbm, o_hbm, qt_vmem, kt_vmem, vt_vmem,
         mt_vmem, lt_vmem, ot_vmem,
         copy_sem, send_sem, recv_sem, credit_sem, par_sems) = refs
        tq, tk = tiles
    left = params_smem[0]
    right = params_smem[1]
    my = params_smem[2]
    P = size
    g = hq // hkv  # query heads per K/V head (GQA group size)
    dev_kw = _mk_dev_kw(mesh_ids, axis_name)
    neighbor_barrier = _mk_barrier(pipelined, dev_kw, left, right)
    copy_sync = _mk_copy_sync(copy_sem)
    copy_par = _mk_copy_par(par_sems)
    # send u (0..P-2): the block computed at step u moves on
    fwd_rdma = _mk_snd(kv_hbm, comm_hbm, send_sem, recv_sem, dev_kw, right)

    # -- resident fold: whole block staged in VMEM --------------------------

    def load_kv(src_ref):
        copy_sync(src_ref, kv_vmem)

    def fold_resident(a):
        def body(mask):
            for h in range(hq):
                kvh = h // g
                rows = pl.ds(h * sb, sb)
                k = kv_vmem[pl.ds(kvh * sb, sb), :]
                v = kv_vmem[pl.ds((hkv + kvh) * sb, sb), :]
                m, l, o = _online_fold(q_vmem[rows, :], k, v,
                                       m_vmem[rows, :], l_vmem[rows, :],
                                       o_vmem[rows, :], scale, mask)
                m_vmem[rows, :] = m
                l_vmem[rows, :] = l
                o_vmem[rows, :] = o

        if not causal:
            body(None)
            return
        # arrival a carries K/V block (my - a) mod P; the first fold
        # (a=0, own block) always has its diagonal unmasked, so the
        # running max is finite from step 0 on.  Blocks entirely in the
        # future (kv_idx > my) contribute exactly zero — skip their MXU
        # passes outright (the circulation/credit schedule above is
        # untouched, so the model-checked protocol is unchanged).
        kv_idx = lax.rem(my - a + P, P)

        @pl.when(kv_idx <= my)
        def _():
            body(_causal_mask(my, kv_idx, sb))

    # -- tiled fold: state in HBM, flash-style [tq, tk] inner loop ----------

    def fold_tiled(a, src):
        """Fold arrival ``a`` whose K/V block sits in HBM ref ``src``
        ([2*hkv*sb, d]).  Reads never conflict with the concurrent
        forwarding RDMA (read/read); the credit still follows both the
        fold and wait_send in program order, so slot recycling is
        exactly the resident protocol."""
        nq, nk = sb // tq, sb // tk

        def run(kv_idx):
            for h in range(hq):
                kvh = h // g
                base = h * sb

                def q_body(i, _, h=h, kvh=kvh, base=base):
                    r0 = base + i * tq
                    if a == 0:
                        copy_sync(q_hbm.at[pl.ds(r0, tq)], qt_vmem)
                        m0 = jnp.full((tq, 1), -jnp.inf, jnp.float32)
                        l0 = jnp.zeros((tq, 1), jnp.float32)
                        o0 = jnp.zeros((tq, d), jnp.float32)
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

                    def k_body(j, carry):
                        m, l, o = carry
                        copy_par((src.at[pl.ds(kvh * sb + j * tk, tk)],
                                  kt_vmem),
                                 (src.at[pl.ds((hkv + kvh) * sb + j * tk,
                                               tk)], vt_vmem))
                        mask = None
                        if causal:
                            mask = _causal_mask(my, kv_idx, sb,
                                                i * tq, j * tk, tq, tk)
                        return _online_fold(qt_vmem[:], kt_vmem[:],
                                            vt_vmem[:], m, l, o, scale,
                                            mask)

                    nk_eff = nk
                    if causal:
                        # on the DIAGONAL block (kv_idx == my) k-tiles
                        # past this q-tile's last row are fully masked
                        # — skip their DMAs and MXU passes (roughly
                        # half the tile grid; review round 5).  Earlier
                        # blocks (kv_idx < my) need every tile.
                        nk_eff = jnp.where(
                            kv_idx == my,
                            (i * tq + tq + tk - 1) // tk, nk)
                    m, l, o = lax.fori_loop(0, nk_eff, k_body,
                                            (m0, l0, o0))
                    mt_vmem[:] = jnp.broadcast_to(m, (tq, _LANES))
                    lt_vmem[:] = jnp.broadcast_to(l, (tq, _LANES))
                    ot_vmem[:] = o
                    copy_par((mt_vmem, m_hbm.at[pl.ds(r0, tq)]),
                             (lt_vmem, l_hbm.at[pl.ds(r0, tq)]),
                             (ot_vmem, o_hbm.at[pl.ds(r0, tq)]))
                    return 0

                lax.fori_loop(0, nq, q_body, 0)

        if causal and a > 0:
            kv_idx = lax.rem(my - a + P, P)

            @pl.when(kv_idx <= my)
            def _():
                run(kv_idx)
        else:
            run(my)  # a == 0 → kv_idx == my; mask unused when not causal

    def fold(a, src):
        if tiles is None:
            fold_resident(a)
        else:
            fold_tiled(a, src)

    # init: Q to VMEM; online-softmax state (resident mode only — the
    # tiled state is written by the a=0 fold, which loads no prior state)
    if tiles is None:
        copy_sync(q_hbm, q_vmem)
        m_vmem[:] = jnp.full((hq * sb, 1), -jnp.inf, jnp.float32)
        l_vmem[:] = jnp.zeros((hq * sb, 1), jnp.float32)
        o_vmem[:] = jnp.zeros((hq * sb, d), jnp.float32)

    neighbor_barrier()

    # step 0: my own block computes and starts circulating
    if tiles is None:
        load_kv(kv_hbm)
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
        if tiles is None:
            load_kv(comm_hbm.at[slot])
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
        fold(a, comm_hbm.at[slot])
        if pipelined and a <= P - 2:
            # slot free only after the forward READ it out (wait_send),
            # then credit the writer for arrival a+2's reuse
            fwd_rdma(a).wait_send()
        if pipelined and a + 2 <= P - 1:
            pltpu.semaphore_signal(credit_sem.at[slot], inc=1,
                                   **dev_kw(left))

    # output: out = o / l and (with_lse) the logsumexp L = m + log l —
    # the fused backward kernel's residual
    if tiles is None:
        out = o_vmem[:] / l_vmem[:]
        if with_lse:
            lse_vmem[:] = jnp.broadcast_to(
                m_vmem[:] + jnp.log(l_vmem[:]), (hq * sb, _LANES))
        o_vmem[:] = out
        if with_lse:
            copy_par((o_vmem, out_hbm), (lse_vmem, lse_hbm))
        else:
            copy_sync(o_vmem, out_hbm)
    else:
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

        lax.fori_loop(0, (hq * sb) // tq, out_body, 0)

    neighbor_barrier()


def _bwd_kernel(params_smem, q_hbm, kv_hbm, do_hbm, lse_hbm, delta_hbm,
                dq_hbm, dkv_hbm, own_kv_hbm, own_dkv_hbm,
                comm_kv_hbm, comm_dkv_hbm, *refs,
                axis_name: str, size: int, sb: int, d: int, scale: float,
                pipelined: bool, mesh_ids: bool, causal: bool,
                hq: int, hkv: int,
                tiles: Optional[Tuple[int, int]] = None):
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
    semaphore columns sharing one slot parity (_SndPair): the
    send/credit/barrier protocol — and therefore the model check — is
    unchanged, only the payload is split.

    Per-pair algebra (flash backward, exact):  P_ = exp(S - L) (the
    saved logsumexp — no rescaling pass), dP = dO·Vᵀ,
    dS = P_∘(dP - D)·scale with D = rowsum(dO∘Out) precomputed,
    dQ += dS·K, dK += dSᵀ·Q, dV = P_ᵀ·dO.  The MXU folds are f32
    regardless of the circulation dtype (staged K/V tiles upcast at the
    matmul).

    ``tiles=None`` → resident fold (everything staged whole in VMEM);
    ``tiles=(tq, tk)`` → flash-style tiling (round 5: the fused
    backward must not fall off to the ppermute recompute exactly where
    long contexts need it): dQ accumulates in its HBM output, each
    arrival loops K/V-tiles (outer, dK/dV tile carried as values) over
    Q-tiles (inner, residuals + dQ staged per tile) — the circulation
    protocol is byte-identical in both modes."""
    if tiles is None:
        (q_vmem, do_vmem, lse_vmem, delta_vmem, kv_vmem, dkv_vmem,
         dq_vmem, copy_sem, send_sem, recv_sem, credit_sem,
         par_sems) = refs
    else:
        (qt_vmem, dot_vmem, lset_vmem, deltat_vmem, kt_vmem, vt_vmem,
         accb_vmem, accb2_vmem, dqt_vmem, copy_sem, send_sem, recv_sem,
         credit_sem, par_sems) = refs
        tq, tk = tiles
    left = params_smem[0]
    right = params_smem[1]
    my = params_smem[2]
    P = size
    g = hq // hkv
    kv_rows = 2 * hkv * sb  # K+V planes; dK+dV planes follow
    dev_kw = _mk_dev_kw(mesh_ids, axis_name)
    neighbor_barrier = _mk_barrier(pipelined, dev_kw, left, right)
    copy_sync = _mk_copy_sync(copy_sem)
    copy_par = _mk_copy_par(par_sems)

    # send u (0..P-1): the block folded at step u moves on; send 0
    # reads the assembled own-block buffer, not a comm slot.  Two
    # planes per hop (split dtypes), one protocol (_SndPair).
    snd_kv = _mk_snd(own_kv_hbm, comm_kv_hbm, send_sem, recv_sem, dev_kw,
                     right, col=0)
    snd_dkv = _mk_snd(own_dkv_hbm, comm_dkv_hbm, send_sem, recv_sem,
                      dev_kw, right, col=1)

    def snd(u):
        return _SndPair(snd_kv(u), snd_dkv(u))

    def pair_grads(kv_idx, masked):
        """dQ/dK/dV contributions of my Q rows against the K/V block in
        kv_vmem; dK/dV accumulate into dkv_vmem (all heads).  ``masked``
        (static) applies the causal mask — only the DIAGONAL block
        (kv_idx == my) needs it: strictly-past blocks are all-True and
        future blocks are skipped by the caller's pl.when, so the mask
        materialization stays off the P-2 hot arrivals (review round
        5)."""
        for h in range(hq):
            kvh = h // g
            rows = pl.ds(h * sb, sb)
            mask = _causal_mask(my, kv_idx, sb) if masked else None
            dq_c, dk_c, dv_c = _pair_grad_tile(
                q_vmem[rows, :].astype(jnp.float32),
                do_vmem[rows, :].astype(jnp.float32),
                lse_vmem[rows, :][:, :1], delta_vmem[rows, :][:, :1],
                kv_vmem[pl.ds(kvh * sb, sb), :].astype(jnp.float32),
                kv_vmem[pl.ds((hkv + kvh) * sb, sb), :]
                .astype(jnp.float32), scale, mask)
            dq_vmem[rows, :] = dq_vmem[rows, :] + dq_c
            krows = pl.ds(kvh * sb, sb)
            vrows = pl.ds((hkv + kvh) * sb, sb)
            dkv_vmem[krows, :] = dkv_vmem[krows, :] + dk_c
            dkv_vmem[vrows, :] = dkv_vmem[vrows, :] + dv_c

    def pair_grads_tiled(kv_idx, kv_at, dkv_at, init_zero, masked):
        """Flash-tiled pair gradients: dK/dV tiles ride the inner-loop
        carry (loaded from — or, ``init_zero``, started at zero in —
        the dK/dV planes addressed by ``dkv_at(row0, n)``), residuals
        and the dQ accumulator stage per Q-tile straight from/to their
        HBM refs (dQ lives in its OUTPUT ref between arrivals).
        ``kv_at(row0, n)`` addresses the arrived K/V planes.  The
        protocol sees the exact same consume window as the resident
        fold."""
        nq, nk = sb // tq, sb // tk
        for h in range(hq):
            kvh = h // g

            # zero the dK/dV tiles only for the FIRST query head of
            # each K/V group: later heads of the group must accumulate
            # into (not overwrite) what earlier heads stored — review
            # round 5 caught the per-head re-zeroing dropping all but
            # the last head's own-block contribution under GQA
            zero_here = init_zero and (h % g == 0)

            def j_body(j, _, h=h, kvh=kvh, zero_here=zero_here):
                kr = kvh * sb + j * tk
                copy_par((kv_at(kr, tk), kt_vmem),
                         (kv_at(hkv * sb + kr, tk), vt_vmem))
                if zero_here:
                    dk0 = jnp.zeros((tk, d), jnp.float32)
                    dv0 = jnp.zeros((tk, d), jnp.float32)
                else:
                    copy_par((dkv_at(kr, tk), accb_vmem),
                             (dkv_at(hkv * sb + kr, tk), accb2_vmem))
                    dk0 = accb_vmem[:]
                    dv0 = accb2_vmem[:]

                def i_body(i, carry, h=h):
                    dk, dv = carry
                    r0 = h * sb + i * tq
                    copy_par((q_hbm.at[pl.ds(r0, tq)], qt_vmem),
                             (do_hbm.at[pl.ds(r0, tq)], dot_vmem),
                             (lse_hbm.at[pl.ds(r0, tq)], lset_vmem),
                             (delta_hbm.at[pl.ds(r0, tq)], deltat_vmem),
                             (dq_hbm.at[pl.ds(r0, tq)], dqt_vmem))
                    mask = (_causal_mask(my, kv_idx, sb, i * tq, j * tk,
                                         tq, tk) if masked else None)
                    dq_c, dk_c, dv_c = _pair_grad_tile(
                        qt_vmem[:].astype(jnp.float32),
                        dot_vmem[:].astype(jnp.float32),
                        lset_vmem[:, :1], deltat_vmem[:, :1],
                        kt_vmem[:].astype(jnp.float32),
                        vt_vmem[:].astype(jnp.float32), scale, mask)
                    dqt_vmem[:] = dqt_vmem[:] + dq_c
                    copy_sync(dqt_vmem, dq_hbm.at[pl.ds(r0, tq)])
                    return dk + dk_c, dv + dv_c

                # on the DIAGONAL block, q-tiles strictly above this
                # k-tile are fully masked — skip them (mirrors the
                # forward's diagonal tile-skip)
                i_lo = (j * tk) // tq if masked else 0
                dk, dv = lax.fori_loop(i_lo, nq, i_body, (dk0, dv0))
                accb_vmem[:] = dk
                accb2_vmem[:] = dv
                copy_par((accb_vmem, dkv_at(kr, tk)),
                         (accb2_vmem, dkv_at(hkv * sb + kr, tk)))
                return 0

            lax.fori_loop(0, nk, j_body, 0)

    if tiles is None:
        # stage the rank-local residuals once (independent → parallel)
        copy_par((q_hbm, q_vmem), (do_hbm, do_vmem),
                 (lse_hbm, lse_vmem), (delta_hbm, delta_vmem))
        dq_vmem[:] = jnp.zeros((hq * sb, d), jnp.float32)
    else:
        # dQ accumulates in its output ref: zero it tile by tile
        def zq_body(i, _):
            dqt_vmem[:] = jnp.zeros((tq, d), jnp.float32)
            copy_sync(dqt_vmem, dq_hbm.at[pl.ds(i * tq, tq)])
            return 0

        lax.fori_loop(0, (hq * sb) // tq, zq_body, 0)

    # fold 0 (own block) and assemble the circulating payload: K/V
    # planes straight from the input (IN the input/wire dtype), dK/dV
    # planes = my own f32 contribution (every other rank's accumulates
    # en route)
    copy_sync(kv_hbm, own_kv_hbm)
    if tiles is None:
        copy_sync(kv_hbm, kv_vmem)
        dkv_vmem[:] = jnp.zeros((kv_rows, d), jnp.float32)
        pair_grads(my, masked=causal)  # a=0 is the diagonal block
        copy_sync(dkv_vmem, own_dkv_hbm)
    else:
        pair_grads_tiled(
            my, kv_at=lambda r0, n: kv_hbm.at[pl.ds(r0, n)],
            dkv_at=lambda r0, n: own_dkv_hbm.at[pl.ds(r0, n)],
            init_zero=True, masked=causal)

    neighbor_barrier()

    if P >= 2:
        snd(0).start()
        if not pipelined:
            snd(0).wait()

    for a in range(1, P + 1):
        slot = a % 2
        if pipelined:
            snd(a - 1).wait_recv()  # arrival a lands in comm[slot]
        if a < P:
            # fold BEFORE forward: the dK/dV planes must carry my
            # contribution when the block moves on
            def consume(kv_idx, masked, slot=slot):
                if tiles is None:
                    copy_sync(comm_kv_hbm.at[slot], kv_vmem)
                    copy_sync(comm_dkv_hbm.at[slot], dkv_vmem)
                    pair_grads(kv_idx, masked)
                    copy_sync(dkv_vmem, comm_dkv_hbm.at[slot])
                else:
                    pair_grads_tiled(
                        kv_idx,
                        kv_at=lambda r0, n: comm_kv_hbm.at[
                            slot, pl.ds(r0, n)],
                        dkv_at=lambda r0, n: comm_dkv_hbm.at[
                            slot, pl.ds(r0, n)],
                        init_zero=False, masked=masked)

            if causal:
                # the diagonal block is always arrival 0 (kv_idx == my
                # iff a ≡ 0 mod P), so arrivals 1..P-1 are either
                # strictly past (mask provably all-True — skip its
                # materialization) or future (skip everything)
                kv_idx = lax.rem(my - a + P, P)

                @pl.when(kv_idx < my)
                def _():
                    consume(kv_idx, masked=False)
            else:
                consume(lax.rem(my - a + P, P), masked=False)
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
            copy_sync(comm_dkv_hbm.at[slot], dkv_hbm)

    if tiles is None:
        copy_sync(dq_vmem, dq_hbm)  # tiled mode accumulated in place
    neighbor_barrier()


def _ring_neighbors(axis_name: str, size: int) -> jnp.ndarray:
    """[left, right, my] int32 SMEM params (my = causal block index)."""
    idx = lax.axis_index(axis_name)
    return jnp.stack([lax.rem(idx - 1 + size, size),
                      lax.rem(idx + 1, size), idx]).astype(jnp.int32)


def _fallback_attention(q, k, v, axis_name: str, size: int, scale: float,
                        causal: bool = False):
    """The same online-softmax ring as jax ops over ppermute — the
    vma/multi-axis interpreter path, and the recompute body of the
    out-of-budget custom-vjp backward.  Accepts both layouts ([Sb, d]
    and [H, Sb, d]); the multi-head ring rotates the WHOLE [Hkv, Sb, d]
    K/V stacks once per step (one ppermute pair per step, exactly like
    the kernel's single circulating RDMA) with per-head folds inside —
    NOT one ring per head (review round 4)."""
    multihead = q.ndim == 3
    q3 = q if multihead else q[None]
    k3 = k if multihead else k[None]
    v3 = v if multihead else v[None]
    hq, sb, d = q3.shape
    hkv = k3.shape[0]
    g = hq // hkv
    world_pairs = _world_pairs_of(size, None)
    perm = world_pairs([(r, (r + 1) % size) for r in range(size)])
    my = lax.axis_index(axis_name)
    m = [jnp.full((sb, 1), -jnp.inf, jnp.float32) for _ in range(hq)]
    l = [jnp.zeros((sb, 1), jnp.float32) for _ in range(hq)]
    o = [jnp.zeros((sb, d), jnp.float32) for _ in range(hq)]
    kb, vb = k3, v3
    for step in range(size):
        mask = None
        if causal:
            kv_idx = lax.rem(my - step + size, size)
            mask = _causal_mask(my, kv_idx, sb)  # shared by every head
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
                          interpret: bool = False,
                          vmem_limit_bytes: Optional[int] = None
                          ) -> jnp.ndarray:
    """Exact attention (full, or causal with ``causal=True``) over a
    sequence-sharded axis.  Two shapes:

    * single-head: ``q``/``k``/``v`` = this device's [Sb, dh] blocks;
    * multi-head / GQA: ``q`` = [Hq, Sb, dh], ``k``/``v`` =
      [Hkv, Sb, dh] with ``Hq % Hkv == 0`` — query head h attends K/V
      head ``h // (Hq//Hkv)`` (Hkv == Hq is classic multi-head,
      Hkv == 1 is MQA).  ALL heads ride ONE circulating RDMA per step.

    Returns this device's output block, shaped like ``q``.  Call inside
    shard_map over a mesh with ``axis_name``; the global sequence is
    the concatenation of the blocks in rank order.

    The compiled path is the in-kernel RDMA circulation described in
    the module docstring, with the fold mode (resident / tiled) chosen
    by ``attention_vmem_plan`` from ``vmem_limit_bytes`` (default ~12
    MiB); ``interpret=True`` (the CPU tier) runs the serial same-kernel
    path, or — under vma typing / a multi-axis mesh — the ppermute
    fallback with the shared loud warning.

    Differentiable: the forward emits the logsumexp residual and the
    backward runs its own fused ring kernel ([K,V,dK,dV] circulation)
    in resident or flash-tiled mode per its VMEM plan; only an
    impossible budget recomputes through the pure-jax ring."""
    if q.ndim not in (2, 3):
        raise ValueError(
            f"ring attention wants [Sb, dh] or [H, Sb, dh] blocks, got "
            f"q {q.shape}")
    if k.shape != v.shape or q.shape[-2:] != k.shape[-2:] or \
            q.ndim != k.ndim:
        raise ValueError(
            f"ring attention wants equal [.., rows, d] blocks for q/k/v "
            f"(k/v may differ from q only in the HEAD count), got "
            f"{q.shape}/{k.shape}/{v.shape}")
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
    sb, d = q.shape[-2:]
    if d % _LANES:
        raise NotImplementedError(
            f"head dim must be a multiple of {_LANES} (lane width), got {d}")
    from .pallas_ring import _SUBLANES

    sub = _SUBLANES.get(jnp.dtype(q.dtype), 8)
    if sb % sub:
        raise NotImplementedError(
            f"block rows must be a multiple of {sub} ({jnp.dtype(q.dtype)} "
            f"sublane tile), got {sb}")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    # shared dtype/vma/mesh probing with the ring collectives (f32/bf16)
    vma_on, multi_axis = _check_args(q, axis_name, size, sub, "sum")
    # Fold mode from the VMEM budget.  A forward NO tile can satisfy
    # degrades to the ppermute ring (graceful degradation, ROADMAP r5
    # #4): primal-identical and correct at any size, just without the
    # kernel's RDMA overlap — so the substitution is LOUD (warning +
    # ``attention_fallbacks`` mpit pvar), exactly like the vma/multi-
    # axis interpreter fallback, instead of the former
    # NotImplementedError that made an over-tight budget fatal.
    try:
        _, tiles = attention_vmem_plan(sb, d, hq, hkv, q.dtype,
                                       vmem_limit_bytes)
    except NotImplementedError as e:
        import warnings

        from .. import mpit

        warnings.warn(
            f"ring attention forward out of VMEM budget — executing the "
            f"ppermute ring fallback; timings will not reflect the RDMA "
            f"kernel. ({e})", RuntimeWarning, stacklevel=2)
        mpit.count(attention_oob=1)
        return _fallback_attention(q, k, v, axis_name, size, scale, causal)
    bwd_mode, bwd_tiles = attention_vmem_plan(
        sb, d, hq, hkv, q.dtype, vmem_limit_bytes, for_backward=True)
    bwd_fused = bwd_mode in ("resident", "tiled")

    def _per_head(fn, q_, k_, v_):
        """Apply a [Sb,dh]-block function per query head (GQA maps
        query head h to K/V head h // (Hq//Hkv))."""
        if not multihead:
            return fn(q_, k_, v_)
        return jnp.stack([fn(q_[h], k_[h // (hq // hkv)],
                             v_[h // (hq // hkv)]) for h in range(hq)])

    def _local_one(qh, kh, vh):
        m0 = jnp.full((sb, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((sb, 1), jnp.float32)
        o0 = jnp.zeros((sb, d), jnp.float32)
        mask = (_causal_mask(jnp.int32(0), jnp.int32(0), sb)
                if causal else None)
        _, l1, o1 = _online_fold(qh, kh, vh, m0, l0, o0, scale, mask)
        return (o1 / l1).astype(q.dtype)

    def _reference(q_, k_, v_):
        """Pure-jax ring (differentiable) — primal-identical to the
        kernel; the out-of-budget custom-vjp backward recomputes
        through it.  Only reached with size >= 2 (size == 1 returns
        below, before any _reference call site)."""
        return _fallback_attention(q_, k_, v_, axis_name, size, scale,
                                   causal)

    if size == 1:
        return _per_head(_local_one, q, k, v)
    if (vma_on or multi_axis) and interpret:
        _fallback("ring_attention", axis_name, vma_on, multi_axis)
        return _reference(q, k, v)

    vma = (frozenset(jax.typeof(q).vma) | {axis_name} if vma_on
           else frozenset())

    def _hbm_call(kern, outs, scratch, collective_id, params, *args):
        """One ring-kernel launch: ``params`` in SMEM, ``args`` in HBM.
        ``outs`` = (shape, dtype) pairs, all in HBM: the results first,
        then the kernel's HBM working buffers (landing slots, tiled
        state).  Mosaic allocates scratch only in VMEM/SMEM, so those
        buffers are extra outputs the caller drops."""
        return pl.pallas_call(
            kern,
            out_shape=tuple(jax.ShapeDtypeStruct(s, dt, vma=vma)
                            for s, dt in outs),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] +
                     [pl.BlockSpec(memory_space=pl.ANY)] * len(args),
            out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                            for _ in outs),
            scratch_shapes=scratch,
            compiler_params=None if interpret else pltpu.CompilerParams(
                collective_id=collective_id, has_side_effects=True,
                vmem_limit_bytes=vmem_limit_bytes),
            interpret=interpret,
        )(params, *args)

    def _kernel_call(q_, k_, v_, with_lse):
        """→ out [shaped like q], or (out, lse [hq*sb, _LANES] f32)
        when ``with_lse`` (the fused-backward residual; inference and
        fallback-backward paths skip its cost entirely)."""
        # flat multi-head layout (see _kernel docstring): q/out stack
        # query heads along rows; the circulating buffer stacks all K
        # planes then all V planes so one RDMA carries every head
        qf = q_.reshape(hq * sb, d) if multihead else q_
        kf = k_.reshape(hkv * sb, d) if multihead else k_
        vf = v_.reshape(hkv * sb, d) if multihead else v_
        kv = jnp.concatenate([kf, vf], axis=0)
        params = _ring_neighbors(axis_name, size)
        kern = functools.partial(
            _kernel, axis_name=axis_name, size=size, sb=sb, d=d,
            scale=scale, pipelined=not interpret, mesh_ids=multi_axis,
            causal=causal, hq=hq, hkv=hkv, tiles=tiles,
            with_lse=with_lse)
        outs = [((hq * sb, d), jnp.float32)]
        if with_lse:
            outs.append(((hq * sb, _LANES), jnp.float32))
        outs.append(((2, 2 * hkv * sb, d), q.dtype))     # landing slots
        if tiles is None:
            scratch = [
                pltpu.VMEM((hq * sb, d), q.dtype),       # Q (all heads)
                pltpu.VMEM((2 * hkv * sb, d), q.dtype),  # K/V staging
                pltpu.VMEM((hq * sb, 1), jnp.float32),   # m
                pltpu.VMEM((hq * sb, 1), jnp.float32),   # l
                pltpu.VMEM((hq * sb, d), jnp.float32),   # o
            ]
            if with_lse:
                scratch.append(
                    pltpu.VMEM((hq * sb, _LANES), jnp.float32))  # lse
        else:
            tq, tk = tiles
            outs += [((hq * sb, _LANES), jnp.float32),    # m state
                     ((hq * sb, _LANES), jnp.float32),    # l state
                     ((hq * sb, d), jnp.float32)]         # o state
            scratch = [
                pltpu.VMEM((tq, d), q.dtype),            # q tile
                pltpu.VMEM((tk, d), q.dtype),            # k tile
                pltpu.VMEM((tk, d), q.dtype),            # v tile
                pltpu.VMEM((tq, _LANES), jnp.float32),   # m tile
                pltpu.VMEM((tq, _LANES), jnp.float32),   # l tile
                pltpu.VMEM((tq, d), jnp.float32),        # o tile
            ]
        scratch += [
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),               # send (parity)
            pltpu.SemaphoreType.DMA((2,)),               # recv (parity)
            pltpu.SemaphoreType.REGULAR((2,)),           # slot credits
            pltpu.SemaphoreType.DMA((8,)),               # parallel tiles
        ]
        res = _hbm_call(kern, outs, scratch, 16, params, qf, kv)
        out = res[0].astype(q_.dtype)
        out = out.reshape(hq, sb, d) if multihead else out
        return (out, res[1]) if with_lse else out

    def _bwd_kernel_call(q_, k_, v_, out, lse, ct):
        """Fused backward (resident or tiled mode): → (dq, dk, dv)
        like q/k/v."""
        qf = q_.reshape(hq * sb, d) if multihead else q_
        kf = k_.reshape(hkv * sb, d) if multihead else k_
        vf = v_.reshape(hkv * sb, d) if multihead else v_
        dof = ct.reshape(hq * sb, d) if multihead else ct
        outf = out.reshape(hq * sb, d) if multihead else out
        # the K/V planes circulate in the INPUT dtype (split-dtype seam:
        # pristine inputs lose nothing below f32, and bf16 halves their
        # wire bytes); only the dK/dV partial sums ride f32
        kv = jnp.concatenate([kf, vf], axis=0)
        delta = jnp.sum(dof.astype(jnp.float32) * outf.astype(jnp.float32),
                        axis=1, keepdims=True)
        delta = jnp.broadcast_to(delta, (hq * sb, _LANES))
        params = _ring_neighbors(axis_name, size)
        kern = functools.partial(
            _bwd_kernel, axis_name=axis_name, size=size, sb=sb, d=d,
            scale=scale, pipelined=not interpret, mesh_ids=multi_axis,
            causal=causal, hq=hq, hkv=hkv, tiles=bwd_tiles)
        kv_rows = 2 * hkv * sb
        outs = [((hq * sb, d), jnp.float32),             # dQ
                ((kv_rows, d), jnp.float32),             # dK/dV (home)
                ((kv_rows, d), q.dtype),                 # own [K,V] (wire)
                ((kv_rows, d), jnp.float32),             # own [dK,dV]
                ((2, kv_rows, d), q.dtype),              # K/V landing slots
                ((2, kv_rows, d), jnp.float32)]          # dK/dV landing slots
        if bwd_tiles is None:
            scratch = [
                pltpu.VMEM((hq * sb, d), q.dtype),           # Q
                pltpu.VMEM((hq * sb, d), q.dtype),           # dOut
                pltpu.VMEM((hq * sb, _LANES), jnp.float32),  # lse
                pltpu.VMEM((hq * sb, _LANES), jnp.float32),  # delta
                pltpu.VMEM((kv_rows, d), q.dtype),           # K/V staging
                pltpu.VMEM((kv_rows, d), jnp.float32),       # dK/dV staging
                pltpu.VMEM((hq * sb, d), jnp.float32),       # dQ accum
            ]
        else:
            tqb, tkb = bwd_tiles
            scratch = [
                pltpu.VMEM((tqb, d), q.dtype),               # q tile
                pltpu.VMEM((tqb, d), q.dtype),               # dOut tile
                pltpu.VMEM((tqb, _LANES), jnp.float32),      # lse tile
                pltpu.VMEM((tqb, _LANES), jnp.float32),      # delta tile
                pltpu.VMEM((tkb, d), q.dtype),               # k tile
                pltpu.VMEM((tkb, d), q.dtype),               # v tile
                pltpu.VMEM((tkb, d), jnp.float32),           # dk buffer
                pltpu.VMEM((tkb, d), jnp.float32),           # dv buffer
                pltpu.VMEM((tqb, d), jnp.float32),           # dq tile
            ]
        scratch += [
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, 2)),             # send (parity, plane)
            pltpu.SemaphoreType.DMA((2, 2)),             # recv (parity, plane)
            pltpu.SemaphoreType.REGULAR((2,)),           # slot credits
            pltpu.SemaphoreType.DMA((8,)),               # parallel tiles
        ]
        dq, dkv = _hbm_call(kern, outs, scratch, 17,
                            params, qf, kv, dof, lse, delta)[:2]
        dq = dq.astype(q_.dtype)
        dk = dkv[:hkv * sb].astype(k_.dtype)
        dv = dkv[hkv * sb:].astype(v_.dtype)
        if multihead:
            return (dq.reshape(hq, sb, d), dk.reshape(hkv, sb, d),
                    dv.reshape(hkv, sb, d))
        return dq, dk, dv

    def _primal(q_, k_, v_):
        return _kernel_call(q_, k_, v_, with_lse=False)

    # Differentiable wrapper: jax cannot autodiff through the kernel's
    # remote DMAs, so the backward is either the fused [K,V,dK,dV]
    # ring kernel above (resident or tiled plan) or a recompute
    # through the pure-jax ring (out-of-budget fallback; ppermutes
    # transpose to the inverse rotation) — either way the fused kernel
    # stays the forward hot path and training can jax.grad straight
    # through it.
    attn = jax.custom_vjp(_primal)

    def _fwd(q_, k_, v_):
        if not bwd_fused:
            # the recompute backward needs only the inputs — skip the
            # lse output and do not pin out/lse across fwd..bwd
            return _kernel_call(q_, k_, v_, with_lse=False), (q_, k_, v_)
        out, lse = _kernel_call(q_, k_, v_, with_lse=True)
        return out, (q_, k_, v_, out, lse)

    def _bwd(res, ct):
        if not bwd_fused:
            q_, k_, v_ = res
            _, vjp = jax.vjp(_reference, q_, k_, v_)
            return vjp(ct)
        q_, k_, v_, out, lse = res
        return _bwd_kernel_call(q_, k_, v_, out, lse, ct)

    attn.defvjp(_fwd, _bwd)
    return attn(q, k, v)
