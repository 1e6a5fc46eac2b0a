"""MPI_T tool interface [S: MPI-3 ch.14] — the introspection chapter.

A deliberately small, honest implementation of the two variable kinds:

* **Control variables (cvar)**: named knobs a tool can read and set.
  Registered here are the real, load-bearing ones this library already
  has (collective algorithm crossover, the collective-IO buffering
  limit, receive timeout default).
* **Performance variables (pvar)**: counters a tool can read/reset.
  Counted (thread-safely) at the one choke point every process backend
  shares — P2PCommunicator._send_internal / _recv_internal, plus every
  collective entry point — so message/collective counts are exact
  regardless of transport.  ``bytes_sent`` counts SIZED payloads
  (arrays / bytes); opaque pickled objects count 0 there (their wire
  size is a transport detail).

Sessions are the MPI_T scoping object; handles are (session, variable)
pairs, pythonically collapsed — a session simply records which pvars it
reset, so reads are session-relative like the standard requires.
"""

from __future__ import annotations

import sys
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "cvar_register", "cvar_list", "cvar_read", "cvar_write",
    "pvar_list", "pvar_read", "pvar_reset",
    "hist_record", "pvar_hist_list", "pvar_hist_read",
    "pvar_hist_reset", "hist_quantile", "hist_cumulative",
    "Session", "session_create",
]

_lock = threading.Lock()


# -- performance variables (exact transport-level counters) ------------------

class _Counters:
    __slots__ = ("sends", "send_bytes", "recvs", "collectives",
                 "pallas_fallbacks", "bytes_raw", "bytes_pickled", "copies",
                 "proc_failed", "revokes", "shrinks",
                 "faulty_dropped", "faulty_duplicated", "attention_oob",
                 "attention_zz", "attention_win", "attention_sink",
                 "attention_band", "attention_direct",
                 "sm_hits", "sm_bytes", "sm_fallbacks",
                 "v_deadlocks", "v_mismatches", "v_leaked", "v_double_waits",
                 "v_buf_overlaps", "v_comms_unfreed",
                 "v_wildcard_races", "v_clock_bytes",
                 "prog_wakeups", "prog_completions", "prog_idle_parks",
                 "rejoins", "epoch_skews",
                 "comp_saved", "comp_fallbacks",
                 "tuned_hits", "tuned_fallbacks",
                 "link_reconnects", "link_replayed", "link_masked",
                 "link_retained", "link_cow_snaps", "link_cow_bytes",
                 "link_syscalls", "link_rsyscalls", "link_torn",
                 "nbc_threads", "nbc_sms", "persist_starts",
                 "trace_events", "tpu_traces", "tpu_reuses",
                 "rp_hits", "rp_misses", "rp_rdv", "rp_steered",
                 "rp_fold", "rp_user_in", "rp_user_fb",
                 "store_elections", "store_truncated", "store_dropped")

    def __init__(self) -> None:
        self.sends = 0
        self.send_bytes = 0
        self.recvs = 0
        self.collectives = 0
        self.pallas_fallbacks = 0
        self.bytes_raw = 0
        self.bytes_pickled = 0
        self.copies = 0
        self.proc_failed = 0
        self.revokes = 0
        self.shrinks = 0
        self.faulty_dropped = 0
        self.faulty_duplicated = 0
        self.attention_oob = 0
        self.attention_zz = 0
        self.attention_win = 0
        self.attention_sink = 0
        self.attention_band = 0
        self.attention_direct = 0
        self.sm_hits = 0
        self.sm_bytes = 0
        self.sm_fallbacks = 0
        self.v_deadlocks = 0
        self.v_mismatches = 0
        self.v_leaked = 0
        self.v_double_waits = 0
        self.v_buf_overlaps = 0
        self.v_comms_unfreed = 0
        self.v_wildcard_races = 0
        self.v_clock_bytes = 0
        self.prog_wakeups = 0
        self.prog_completions = 0
        self.prog_idle_parks = 0
        self.rejoins = 0
        self.epoch_skews = 0
        self.comp_saved = 0
        self.comp_fallbacks = 0
        self.tuned_hits = 0
        self.tuned_fallbacks = 0
        self.link_reconnects = 0
        self.link_replayed = 0
        self.link_masked = 0
        self.link_retained = 0
        self.link_cow_snaps = 0
        self.link_cow_bytes = 0
        self.link_syscalls = 0
        self.link_rsyscalls = 0
        self.link_torn = 0
        self.nbc_threads = 0
        self.nbc_sms = 0
        self.persist_starts = 0
        self.trace_events = 0
        self.tpu_traces = 0
        self.tpu_reuses = 0
        self.rp_hits = 0
        self.rp_misses = 0
        self.rp_rdv = 0
        self.rp_steered = 0
        self.rp_fold = 0
        self.rp_user_in = 0
        self.rp_user_fb = 0
        self.store_elections = 0
        self.store_truncated = 0
        self.store_dropped = 0


counters = _Counters()  # incremented by communicator.py / codec.py (count())


def count(sends: int = 0, send_bytes: int = 0, recvs: int = 0,
          collectives: int = 0, pallas_fallbacks: int = 0,
          bytes_raw: int = 0, bytes_pickled: int = 0, copies: int = 0,
          proc_failed: int = 0, revokes: int = 0, shrinks: int = 0,
          faulty_dropped: int = 0, faulty_duplicated: int = 0,
          attention_oob: int = 0, attention_zigzag: int = 0,
          attention_window: int = 0, attention_sink: int = 0,
          attention_band_pairs: int = 0, attention_direct_out: int = 0,
          coll_sm_hits: int = 0,
          coll_sm_bytes: int = 0, coll_sm_fallbacks: int = 0,
          verify_deadlocks: int = 0, verify_mismatches: int = 0,
          verify_requests_leaked: int = 0, verify_double_waits: int = 0,
          verify_buffer_overlaps: int = 0,
          verify_comms_unfreed: int = 0,
          verify_wildcard_races: int = 0,
          verify_clock_bytes: int = 0,
          progress_wakeups: int = 0, progress_completions: int = 0,
          progress_idle_parks: int = 0,
          rejoins: int = 0, epoch_skews: int = 0,
          bytes_compressed_saved: int = 0,
          compress_fallbacks: int = 0,
          tuned_table_hits: int = 0,
          tuned_table_fallbacks: int = 0,
          link_reconnects: int = 0,
          link_frames_replayed: int = 0,
          link_faults_masked: int = 0,
          link_bytes_retained: int = 0,
          link_cow_snapshots: int = 0,
          link_cow_bytes: int = 0,
          link_send_syscalls: int = 0,
          link_recv_syscalls: int = 0,
          link_torn_frames: int = 0,
          nbc_threads_spawned: int = 0,
          nbc_state_machines: int = 0,
          persistent_starts: int = 0,
          trace_events: int = 0,
          tpu_program_traces: int = 0,
          tpu_program_reuses: int = 0,
          recv_pool_hits: int = 0,
          recv_pool_misses: int = 0,
          recv_pool_rendezvous: int = 0,
          recv_bytes_steered: int = 0,
          recv_pool_fold_fallbacks: int = 0,
          recv_user_inplace: int = 0,
          recv_user_fallbacks: int = 0,
          store_elections: int = 0,
          store_entries_truncated: int = 0,
          store_partition_dropped: int = 0) -> None:
    """Thread-safe increment (rank-threads of the local backend share
    this process's counters; unsynchronized += would lose updates)."""
    with _lock:
        counters.sends += sends
        counters.send_bytes += send_bytes
        counters.recvs += recvs
        counters.collectives += collectives
        counters.pallas_fallbacks += pallas_fallbacks
        counters.bytes_raw += bytes_raw
        counters.bytes_pickled += bytes_pickled
        counters.copies += copies
        counters.proc_failed += proc_failed
        counters.revokes += revokes
        counters.shrinks += shrinks
        counters.faulty_dropped += faulty_dropped
        counters.faulty_duplicated += faulty_duplicated
        counters.attention_oob += attention_oob
        counters.attention_zz += attention_zigzag
        counters.attention_win += attention_window
        counters.attention_sink += attention_sink
        counters.attention_band += attention_band_pairs
        counters.attention_direct += attention_direct_out
        counters.sm_hits += coll_sm_hits
        counters.sm_bytes += coll_sm_bytes
        counters.sm_fallbacks += coll_sm_fallbacks
        counters.v_deadlocks += verify_deadlocks
        counters.v_mismatches += verify_mismatches
        counters.v_leaked += verify_requests_leaked
        counters.v_double_waits += verify_double_waits
        counters.v_buf_overlaps += verify_buffer_overlaps
        counters.v_comms_unfreed += verify_comms_unfreed
        counters.v_wildcard_races += verify_wildcard_races
        counters.v_clock_bytes += verify_clock_bytes
        counters.prog_wakeups += progress_wakeups
        counters.prog_completions += progress_completions
        counters.prog_idle_parks += progress_idle_parks
        counters.rejoins += rejoins
        counters.epoch_skews += epoch_skews
        counters.comp_saved += bytes_compressed_saved
        counters.comp_fallbacks += compress_fallbacks
        counters.tuned_hits += tuned_table_hits
        counters.tuned_fallbacks += tuned_table_fallbacks
        counters.link_reconnects += link_reconnects
        counters.link_replayed += link_frames_replayed
        counters.link_masked += link_faults_masked
        counters.link_retained += link_bytes_retained
        counters.link_cow_snaps += link_cow_snapshots
        counters.link_cow_bytes += link_cow_bytes
        counters.link_syscalls += link_send_syscalls
        counters.link_rsyscalls += link_recv_syscalls
        counters.link_torn += link_torn_frames
        counters.nbc_threads += nbc_threads_spawned
        counters.nbc_sms += nbc_state_machines
        counters.persist_starts += persistent_starts
        counters.trace_events += trace_events
        counters.tpu_traces += tpu_program_traces
        counters.tpu_reuses += tpu_program_reuses
        counters.rp_hits += recv_pool_hits
        counters.rp_misses += recv_pool_misses
        counters.rp_rdv += recv_pool_rendezvous
        counters.rp_steered += recv_bytes_steered
        counters.rp_fold += recv_pool_fold_fallbacks
        counters.rp_user_in += recv_user_inplace
        counters.rp_user_fb += recv_user_fallbacks
        counters.store_elections += store_elections
        counters.store_truncated += store_entries_truncated
        counters.store_dropped += store_partition_dropped

_PVARS: Dict[str, Callable[[], int]] = {
    "msgs_sent": lambda: counters.sends,
    "bytes_sent": lambda: counters.send_bytes,
    "msgs_received": lambda: counters.recvs,
    "collectives_started": lambda: counters.collectives,
    # times a pallas_ring call executed the vma/multi-axis ppermute
    # fallback instead of the kernel (pallas_ring.py _fallback; VERDICT
    # r3 weak #4 — sim benchmarks must not silently measure the wrong
    # implementation)
    "pallas_ring_fallbacks": lambda: counters.pallas_fallbacks,
    # wire-plane byte accounting (codec.py): array payload bytes that
    # shipped as raw frames vs bytes that went through the pickler, plus
    # host-side payload copies (self-send value copies, non-contiguous
    # compactions).  These are the counters that PROVE a hot path stayed
    # zero-copy — e.g. the segmented allreduce asserts 0 pickled array
    # bytes at bandwidth sizes (ISSUE 1 acceptance).
    "bytes_raw_sent": lambda: counters.bytes_raw,
    "bytes_pickled_sent": lambda: counters.bytes_pickled,
    "payload_copies": lambda: counters.copies,
    # ULFM fault-tolerance events (mpi_tpu/ft.py): distinct world ranks
    # this process declared dead (detector hit or transport evidence);
    # revocations applied to a communicator (local revoke() + delivered
    # remote notifications); shrinks that completed agreement and built
    # a survivor communicator.
    "proc_failures_detected": lambda: counters.proc_failed,
    "revokes_delivered": lambda: counters.revokes,
    "shrinks_completed": lambda: counters.shrinks,
    # fault-injection tallies (transport/faulty.py): messages the chaos
    # wrapper dropped / delivered twice — lets a chaos sweep assert the
    # injection actually fired without a handle on every wrapper.
    "faulty_dropped": lambda: counters.faulty_dropped,
    "faulty_duplicated": lambda: counters.faulty_duplicated,
    # ring-attention forwards that ran the ppermute fallback because no
    # tile fit the VMEM budget (tpu/pallas_attention.py — graceful
    # degradation instead of NotImplementedError; ROADMAP r5 #4)
    "attention_fallbacks": lambda: counters.attention_oob,
    # causal ring-attention traces that took the zigzag layout (balanced
    # causal work across the ring; tpu/pallas_attention.py): says
    # whether the mechanism engaged
    "attention_zigzag": lambda: counters.attention_zz,
    # sliding-window attention traces that took the windowed tile
    # ranges (k tiles before a query tile's window skipped;
    # tpu/pallas_attention.py): says whether the window engaged
    "attention_window": lambda: counters.attention_win,
    # ring-attention traces that folded a per-head sink logit into the
    # softmax denominator (tpu/pallas_attention.py ``sinks=``)
    "attention_sink": lambda: counters.attention_sink,
    # query-key pairs (one query head) that the windowed kernels' tile
    # plans fold on the ring's fullest rank, summed over the traces that
    # take the windowed tile ranges: against the pairs the window keeps,
    # what the tile plan wastes
    "attention_band_pairs": lambda: counters.attention_band,
    # ring-attention traces whose forward writes out and lse from its q
    # loop (a ring of one: no m/l/o state in HBM, no separate output
    # pass; tpu/pallas_attention.py)
    "attention_direct_out": lambda: counters.attention_direct,
    # shared-memory collective arena (mpi_tpu/coll_sm.py): collectives
    # served entirely by arena load/store (zero ring frames), per-rank
    # payload bytes moved through it, and eligible requests that fell
    # back to the wire algorithms (non-array payload, payload larger
    # than a slot, nbc clone, mismatched reduction geometry)
    "coll_sm_hits": lambda: counters.sm_hits,
    "coll_sm_bytes": lambda: counters.sm_bytes,
    "coll_sm_fallbacks": lambda: counters.sm_fallbacks,
    # runtime correctness verifier (mpi_tpu/verify): deadlocks proven
    # (DeadlockError raised instead of a hang), collective-signature
    # mismatches (CollectiveMismatchError), and the finalize-report
    # lints — requests leaked (GC'd/finalized unwaited), second wait()
    # on a completed request, overlapping live nonblocking buffers (the
    # message-race case), and split/dup comms never freed.
    "verify_deadlocks_detected": lambda: counters.v_deadlocks,
    "verify_collective_mismatches": lambda: counters.v_mismatches,
    "verify_requests_leaked": lambda: counters.v_leaked,
    "verify_double_waits": lambda: counters.v_double_waits,
    "verify_buffer_overlaps": lambda: counters.v_buf_overlaps,
    "verify_comms_unfreed": lambda: counters.v_comms_unfreed,
    # wildcard-race detector (mpi_tpu/verify/vclock.py): ANY_SOURCE
    # receives whose consumed message was CONCURRENT (no happens-before
    # edge, per the piggybacked vector clocks) with another eligible
    # pending sender — the nondeterministic match MPL009 flags
    # statically, observed at runtime; and the clock bytes piggybacked
    # on frames to prove it.  Both exactly 0 outside verify mode (the
    # off-mode zero-cost contract).
    "verify_wildcard_races": lambda: counters.v_wildcard_races,
    "verify_clock_bytes": lambda: counters.v_clock_bytes,
    # async progress engine (mpi_tpu/progress.py): engine-thread wakeups
    # (the added cost the ``progress`` cvar prices), nonblocking
    # requests completed in the BACKGROUND (by the engine rather than a
    # caller's wait/test), and parks that expired with no traffic (the
    # engine's idle duty cycle).  All exactly 0 with progress=none —
    # the off-mode zero-cost contract (bench.py --verify-overhead
    # --progress and tests/test_progress.py assert it).
    "progress_wakeups": lambda: counters.prog_wakeups,
    "progress_completions": lambda: counters.prog_completions,
    "progress_idle_parks": lambda: counters.prog_idle_parks,
    # elastic membership (mpi_tpu/membership.py): rejoin handshakes this
    # process completed (either side), and stale-epoch handshakes it
    # rejected/diagnosed (EpochSkewError — the false-suspicion group
    # split surfacing as an error instead of a cross-wired hang)
    "rejoins_completed": lambda: counters.rejoins,
    "epoch_skews_detected": lambda: counters.epoch_skews,
    # compressed collectives (mpi_tpu/compress.py): logical fold-dtype
    # bytes minus actual wire bytes, accumulated at encode time (bf16
    # halves, scaled-int quarters; a top-k ratio that overshoots dense
    # counts NEGATIVE — honest accounting), and eligible
    # algorithm="compressed" requests that declined to the classic path
    # (non-float dtype, unsupported op).  bytes_raw_sent keeps counting
    # the actual wire bytes, so the halving claim is assertable.
    "bytes_compressed_saved": lambda: counters.comp_saved,
    "compress_fallbacks": lambda: counters.comp_fallbacks,
    # tuned dispatch (mpi_tpu/tuning): algorithm="auto" decisions served
    # by a matching tuning-table row vs decisions that fell back to the
    # built-in seed constants (no table / no matching row / row not
    # applicable to this group).  With no table configured every auto
    # decision is a fallback and dispatch is byte-identical to the
    # constants (asserted in tests/test_tuning.py).
    "tuned_table_hits": lambda: counters.tuned_hits,
    "tuned_table_fallbacks": lambda: counters.tuned_fallbacks,
    # resilient socket links (mpi_tpu/resilience.py + transport/
    # socket.py): connections re-established after an ESTABLISHED one
    # was lost (link faults healed by reconnect, not initial setup),
    # retained frames replayed through a resume handshake, send-path
    # OSErrors classified as link faults and masked end-to-end (the
    # caller's send completed despite the fault), and the bytes copied
    # into the retained replay window (the honest price of
    # replay-after-reset — the user-space analogue of the kernel
    # socket buffer a reset discards).
    "link_reconnects": lambda: counters.link_reconnects,
    "link_frames_replayed": lambda: counters.link_replayed,
    "link_faults_masked": lambda: counters.link_masked,
    "link_bytes_retained": lambda: counters.link_retained,
    # refcounted buffer ownership (mpi_tpu/bufpool.py, ISSUE 11):
    # retained frames are now by-REFERENCE views of the caller's
    # buffers, so link_bytes_retained prices retention (pinned memory,
    # replay bound) without a copy; these two price exactly the
    # copy-on-write snapshots that buffer REUSE forced (fold into /
    # conflicting send over / posted write buffer on a still-unacked
    # region).  Zero on the no-reuse path — the decoupling the ISSUE 11
    # acceptance demands.  link_send_syscalls counts data-plane socket
    # write calls (one vectored sendmsg per frame on the batched path,
    # vs one write per header/meta/segment before it).
    "link_cow_snapshots": lambda: counters.link_cow_snaps,
    "link_cow_bytes": lambda: counters.link_cow_bytes,
    "link_send_syscalls": lambda: counters.link_syscalls,
    # receive twin of link_send_syscalls (ISSUE 19): data-plane socket
    # READ calls on the raw-body path — one vectored recvmsg_into per
    # multi-segment frame (scatter-gather receive) vs one recv_into per
    # segment before it.  Headers/meta keep their own exact reads and
    # are not counted.
    "link_recv_syscalls": lambda: counters.link_rsyscalls,
    # torn frames (ISSUE 17 small fix): reader-side disconnects that
    # landed MID-FRAME (partial header/meta/body bytes then EOF or
    # error) — a reset the replay protocol must heal, distinguished
    # from a clean between-frames close (graceful shutdown /
    # membership departure), which is not counted.
    "link_torn_frames": lambda: counters.link_torn,
    # engine-owned nonblocking collectives (mpi_tpu/nbc.py, ISSUE 12):
    # per-call _ThreadRequest threads actually SPAWNED (the cost the
    # state machines remove — exactly 0 when every i-collective rode
    # the engine), schedule state machines launched in their place, and
    # persistent-collective start() re-fires (the hot-loop path that
    # skips per-call compile/resolve/verify work).
    "nbc_threads_spawned": lambda: counters.nbc_threads,
    "nbc_state_machines": lambda: counters.nbc_sms,
    "persistent_starts": lambda: counters.persist_starts,
    # flight recorder (mpi_tpu/telemetry, ISSUE 13): events recorded
    # into the per-rank ring buffer.  Exactly 0 with tracing off — the
    # off-mode zero-cost contract (every instrumented seam is one
    # `telemetry.REC is None` attribute test; bench.py --verify-overhead
    # --trace asserts it alongside the unchanged wire accounting).
    "trace_events": lambda: counters.trace_events,
    # TPU entry (mpi_tpu/tpu/runner.py run_spmd): traces of the SPMD
    # program, counted in the traced body, so an executed call costs
    # nothing, and calls served by a program kept from an earlier call
    # (no trace, lower or compile).  Always on; per process, traces +
    # reuses = jit calls.  Traces that rise with every mpi_tpu.run call
    # mean each call's key is new (a new function object, shape, dtype
    # or keyword value) or unhashable.
    "tpu_program_traces": lambda: counters.tpu_traces,
    "tpu_program_reuses": lambda: counters.tpu_reuses,
    # receive-side zero-copy (mpi_tpu/recvpool.py, ISSUE 17): pool
    # requests served by a recycled size-class buffer vs fresh
    # allocations (the page-fault pass the pool removes), frames the
    # reader STEERED directly into a posted irecv's destination buffer
    # (the rendezvous path — the intermediate receive copy removed
    # entirely), and the body bytes that moved that way.  The socket
    # 16MB allreduce asserts payload_copies drops by exactly the
    # steered stores (tests/test_recvpool.py).
    "recv_pool_hits": lambda: counters.rp_hits,
    "recv_pool_misses": lambda: counters.rp_misses,
    "recv_pool_rendezvous": lambda: counters.rp_rdv,
    "recv_bytes_steered": lambda: counters.rp_steered,
    # rendezvous steering races LOST (ISSUE 18 satellite, the ISSUE 17
    # residual (c)): frames whose exact-match channel had no posted
    # entry yet (reader beat the poster) or whose posted destination
    # was steering-ineligible, so the body folded through the pool
    # instead of a direct store.  A visibility counter only — the
    # deterministic payload_copies assertions are NOT derived from it.
    "recv_pool_fold_fallbacks": lambda: counters.rp_fold,
    # user-buffer rendezvous (ISSUE 19): irecv(buf=)/recv_init(buf=)
    # completions whose payload WAS the registered buffer (bytes landed
    # in place, the final store skipped) vs armed completions that had
    # to copy (the match raced the reader, a heal replay re-presented
    # the frame, or a wildcard/probe stole the steered frame — the
    # named fallback the tentpole demands).  Unarmed buf= completions
    # count neither.
    "recv_user_inplace": lambda: counters.rp_user_in,
    "recv_user_fallbacks": lambda: counters.rp_user_fb,
    # replicated namespace store (mpi_tpu/federation_store.py, ISSUE
    # 18): store-leader elections STARTED by a node in this process,
    # uncommitted log entries truncated away by a new leader's
    # conflict check (the minority's stale intents being discarded at
    # heal), and node-to-node frames dropped by an installed partition
    # map (proof the injection actually fired).  All exactly 0 in
    # file-store / non-federated runs.
    "store_elections": lambda: counters.store_elections,
    "store_entries_truncated": lambda: counters.store_truncated,
    "store_partition_dropped": lambda: counters.store_dropped,
    # gauges, not counters: current max term / commit index over this
    # process's live store nodes (0 with none).  Lazy sys.modules
    # lookup — reading a pvar must not import the federation tier.
    "store_term": lambda: _store_gauge("term"),
    "store_commit_index": lambda: _store_gauge("commit_index"),
}


def _store_gauge(field: str) -> int:
    mod = sys.modules.get("mpi_tpu.federation_store")
    return 0 if mod is None else int(mod.store_gauge(field))


def pvar_list() -> List[str]:
    """MPI_T_pvar_get_info over all indices: the variable names."""
    return sorted(_PVARS)


def pvar_read(name: str) -> int:
    """Absolute (process-lifetime) value of a performance variable."""
    try:
        return int(_PVARS[name]())
    except KeyError:
        raise KeyError(f"unknown pvar {name!r}; have {pvar_list()}") from None


def pvar_reset(name: str) -> int:
    """MPI_T semantics put reset in the session; module-level reset just
    returns the current value to subtract (see Session)."""
    return pvar_read(name)


# -- histogram pvars (ISSUE 13: distributions beside the counters) -----------
#
# Log-bucketed (base-2) histograms for the latencies a mean would lie
# about: bucket k holds values in [2^(k-1), 2^k) nanoseconds, so 64
# buckets span sub-ns to ~292 years with zero configuration and O(1)
# record cost (one bit_length + one increment under the module lock).
# Quantiles are estimated from the bucket boundaries (geometric
# midpoint 2^(k-0.5), clamped to the observed min/max) — the standard
# HDR-style tradeoff: <= ~41% relative error per estimate, which is
# exactly enough to tell a 1.5ms lease p99 from a 6s one.
#
# Recording sites: every traced collective (coll_latency_s — gated on
# the flight recorder, it is the HOT path), every serve lease grant
# (lease_acquire_s — always on, the grant is a control round-trip) and
# every socket link heal (link_heal_s — always on, healing is already
# a multi-ms reconnect).  hist_record() accepts any name, so new
# distributions need no registry edit.

_HIST_BUCKETS = 64


class _Hist:
    __slots__ = ("counts", "n", "total_ns", "min_ns", "max_ns")

    def __init__(self) -> None:
        self.counts = [0] * _HIST_BUCKETS
        self.n = 0
        self.total_ns = 0
        self.min_ns = None  # type: Optional[int]
        self.max_ns = 0

    def add(self, ns: int) -> None:
        self.counts[min(_HIST_BUCKETS - 1, ns.bit_length())] += 1
        self.n += 1
        self.total_ns += ns
        if self.min_ns is None or ns < self.min_ns:
            self.min_ns = ns
        if ns > self.max_ns:
            self.max_ns = ns


# pre-seeded so pvar_hist_list() is stable before any event fires (the
# three distributions the README documents); hist_record creates others
# on demand.
_HISTS: Dict[str, _Hist] = {
    "coll_latency_s": _Hist(),
    "lease_acquire_s": _Hist(),
    "link_heal_s": _Hist(),
}


def hist_record(name: str, seconds: float) -> None:
    """Record one sample (seconds; negative clamps to 0) into the named
    log-bucketed histogram, creating it on first use."""
    ns = max(0, int(seconds * 1e9))
    with _lock:
        h = _HISTS.get(name)
        if h is None:
            h = _HISTS[name] = _Hist()
        h.add(ns)


def pvar_hist_list() -> List[str]:
    with _lock:
        return sorted(_HISTS)


def pvar_hist_read(name: str) -> Dict[str, Any]:
    """Snapshot of a histogram pvar: count/sum/min/max plus the
    non-empty buckets as ``{upper_bound_seconds: count}``."""
    with _lock:
        h = _HISTS.get(name)
        if h is None:
            raise KeyError(f"unknown histogram pvar {name!r}; have "
                           f"{sorted(_HISTS)}")
        return {
            "count": h.n,
            "sum_s": h.total_ns / 1e9,
            "min_s": (h.min_ns or 0) / 1e9,
            "max_s": h.max_ns / 1e9,
            "buckets": {(1 << k) / 1e9: c
                        for k, c in enumerate(h.counts) if c},
        }


def pvar_hist_reset(name: str) -> None:
    with _lock:
        if name in _HISTS:
            _HISTS[name] = _Hist()


def hist_cumulative(name: str) -> List[Tuple[float, int]]:
    """Cumulative (upper_bound_seconds, count<=bound) pairs over the
    non-empty prefix — the Prometheus ``le`` bucket series."""
    with _lock:
        h = _HISTS.get(name)
        if h is None:
            raise KeyError(f"unknown histogram pvar {name!r}")
        out: List[Tuple[float, int]] = []
        cum = 0
        top = max((k for k, c in enumerate(h.counts) if c), default=-1)
        for k in range(top + 1):
            cum += h.counts[k]
            out.append(((1 << k) / 1e9, cum))
        return out


def hist_quantile(name: str, q: float) -> Optional[float]:
    """Estimated q-quantile (seconds) from the bucket boundaries, or
    None for an empty histogram."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    with _lock:
        h = _HISTS.get(name)
        if h is None:
            raise KeyError(f"unknown histogram pvar {name!r}")
        if h.n == 0:
            return None
        target = q * h.n
        cum = 0
        for k, c in enumerate(h.counts):
            cum += c
            if cum >= target and c:
                # geometric midpoint of [2^(k-1), 2^k), clamped to the
                # observed extremes so a single-sample histogram reads
                # back its own value
                est = 2.0 ** (k - 0.5)
                return min(max(est, float(h.min_ns or 0)),
                           float(h.max_ns)) / 1e9
        return h.max_ns / 1e9  # pragma: no cover - cum==n on last bucket


# -- control variables -------------------------------------------------------

_CVARS: Dict[str, Tuple[Callable[[], Any], Optional[Callable[[Any], None]],
                        str]] = {}


def cvar_register(name: str, reader: Callable[[], Any],
                  writer: Optional[Callable[[Any], None]],
                  desc: str) -> None:
    with _lock:
        _CVARS[name] = (reader, writer, desc)


def cvar_list() -> Dict[str, str]:
    """name -> description (MPI_T_cvar_get_info)."""
    _ensure_builtin_cvars()
    return {k: v[2] for k, v in sorted(_CVARS.items())}


def cvar_read(name: str) -> Any:
    _ensure_builtin_cvars()
    try:
        return _CVARS[name][0]()
    except KeyError:
        raise KeyError(f"unknown cvar {name!r}; have "
                       f"{sorted(_CVARS)}") from None


def cvar_write(name: str, value: Any) -> None:
    _ensure_builtin_cvars()
    try:
        reader, writer, _ = _CVARS[name]
    except KeyError:
        raise KeyError(f"unknown cvar {name!r}; have "
                       f"{sorted(_CVARS)}") from None
    if writer is None:
        raise PermissionError(f"cvar {name!r} is read-only")
    writer(value)


_builtin_done = False

# replicated-gather footprint warning threshold (bytes of the full
# [size, ...] stack PER DEVICE).  Held here (not in tpu/communicator)
# so reading/writing the cvar never needs a jax import; list-wrapped so
# the closures below share one mutable cell.
_GATHER_WARN_BYTES = [64 * 2 ** 20]


def _ensure_builtin_cvars() -> None:
    """The knobs that actually steer this library — registered LAZILY so
    importing mpit from the transports cannot cycle back through io/
    communicator at module-import time."""
    global _builtin_done
    if _builtin_done:
        return
    # imports OUTSIDE the lock (they can run user-level module code);
    # registration + flag UNDER it, flag LAST — a concurrent reader must
    # never observe done=True with the registry still empty
    from . import coll_sm as _sm
    from . import communicator as _c
    from . import compress as _compress
    from . import ft as _ft
    from . import io as _io
    from . import membership as _membership
    from . import nbc as _nbc
    from . import progress as _prog
    from . import recvpool as _recvpool
    from . import resilience as _resilience
    from . import tuning as _tuning
    from .transport import shm as _shm
    from .transport import socket as _socket
    from .verify import state as _vstate

    def _set_sm_arena(v):
        if int(v) < 0:
            raise ValueError("coll_sm_arena_bytes must be >= 0 (0 = off)")
        _sm._ARENA_BYTES = int(v)

    def _set_sm_eager(v):
        if int(v) < 0:
            raise ValueError("coll_sm_eager_bytes must be >= 0")
        _sm._EAGER_BYTES = int(v)

    def _get_limit():
        return _io._COLLECTIVE_BUFFER_LIMIT

    def _set_limit(v):
        _io._COLLECTIVE_BUFFER_LIMIT = int(v)

    def _get_cross():
        return _c._RING_CROSSOVER_BYTES

    def _set_cross(v):
        _c._RING_CROSSOVER_BYTES = int(v)

    def _get_raben():
        return _c._RABENSEIFNER_CROSSOVER_BYTES

    def _set_raben(v):
        _c._RABENSEIFNER_CROSSOVER_BYTES = int(v)

    def _get_seg():
        return _c._SEGMENT_BYTES

    def _set_seg(v):
        if int(v) < 0:
            raise ValueError(
                "collective_segment_bytes must be >= 0 (0 = per-transport)")
        _c._SEGMENT_BYTES = int(v)

    def _get_recv_timeout():
        return _c._RECV_TIMEOUT_DEFAULT or 0.0

    def _set_recv_timeout(v):
        if float(v) < 0:
            raise ValueError("recv_timeout_s must be >= 0 (0 = no timeout)")
        _c._RECV_TIMEOUT_DEFAULT = float(v) or None

    def _get_shm_wt():
        return _shm._WRITE_TIMEOUT

    def _set_shm_wt(v):
        if float(v) <= 0:
            raise ValueError("shm_write_timeout_s must be > 0")
        _shm._WRITE_TIMEOUT = float(v)

    def _set_detect(v):
        if float(v) <= 0:
            raise ValueError("fault_detect_timeout_s must be > 0")
        _ft._DETECT_TIMEOUT_S = float(v)

    def _set_heartbeat(v):
        if float(v) <= 0:
            raise ValueError("fault_heartbeat_interval_s must be > 0")
        _ft._HEARTBEAT_S = float(v)

    def _set_verify_stall(v):
        if float(v) <= 0:
            raise ValueError("verify_stall_timeout_s must be > 0")
        _vstate._STALL_TIMEOUT_S = float(v)

    def _set_progress(v):
        if v not in _prog.MODES:
            raise ValueError(
                f"progress must be one of {list(_prog.MODES)}, got {v!r}")
        _prog._DEFAULT_MODE = v

    def _set_nbc_mode(v):
        if v not in _nbc.MODES:
            raise ValueError(
                f"nbc_mode must be one of {list(_nbc.MODES)}, got {v!r}")
        _nbc._MODE = v

    def _set_nbc_fold_workers(v):
        if int(v) < 1:
            raise ValueError("nbc_fold_workers must be >= 1")
        _nbc._FOLD_WORKERS = int(v)

    def _set_nbc_sm_max(v):
        if int(v) < 0:
            raise ValueError("nbc_sm_max_bytes must be >= 0 (0 = no cap)")
        _nbc._SM_MAX_BYTES = int(v)

    with _lock:
        if _builtin_done:
            return
        _CVARS["io_collective_buffer_limit_bytes"] = (
            _get_limit, _set_limit,
            "write_at_all aggregates at rank 0 below this total (two-"
            "phase collective buffering); above it ranks write "
            "independently")
        _CVARS["allreduce_ring_crossover_bytes"] = (
            _get_cross, _set_cross,
            "CPU-backend allreduce auto algorithm picks latency-optimal "
            "recursive halving below this payload size (pow2 groups), "
            "bandwidth-optimal ring at or above it")
        _CVARS["allreduce_rabenseifner_crossover_bytes"] = (
            _get_raben, _set_raben,
            "CPU-backend allreduce auto algorithm hands payloads at or "
            "above this size to the Rabenseifner composition (block-ring "
            "reduce_scatter + ring allgather, any group size) instead of "
            "the classic ring; derived from the measured host sweep "
            "(benchmarks/results/host_sweep2_post.json)")
        _CVARS["collective_segment_bytes"] = (
            _get_seg, _set_seg,
            "pipeline segment size of the host collective engine: element "
            "ranges above this many bytes ship as multiple raw frames so "
            "the receiver's fold of segment k overlaps the transport "
            "streaming segment k+1.  0 (default) defers to each "
            "transport's coll_segment_hint (shm: stay inside the ring; "
            "socket: amortize per-frame host work); nonzero overrides "
            "every transport (keep window*segment below the shm ring "
            "capacity; see communicator._SEG_WINDOW) and also lowers "
            "reduce_scatter's segmented-path gate to any payload "
            "spanning more than one segment (default gate: "
            "communicator._RS_SEGMENT_MIN_BYTES)")
        _CVARS["recv_timeout_s"] = (
            _get_recv_timeout, _set_recv_timeout,
            "default recv_timeout of newly created communicators: a "
            "blocking receive with no matching message raises RecvTimeout "
            "after this many seconds instead of hanging (0 = wait "
            "forever).  Per-communicator .recv_timeout still overrides")
        _CVARS["shm_write_timeout_s"] = (
            _get_shm_wt, _set_shm_wt,
            "shm transport no-progress stall bound: a ring write (full "
            "ring, nobody draining) or mid-frame read with no progress "
            "for this long declares the peer dead (TransportError).  The "
            "data plane's last-resort constant — the ft.py detector "
            "(fault_detect_timeout_s) should fire far earlier")
        _CVARS["fault_detect_timeout_s"] = (
            lambda: _ft._DETECT_TIMEOUT_S, _set_detect,
            "ULFM failure-detection bound (mpi_tpu/ft.py): a peer whose "
            "heartbeat is stale this long is declared dead and every "
            "fault-tolerant blocking wait on it raises ProcFailedError "
            "(MPI_ERR_PROC_FAILED).  Read at ft.enable() time")
        _CVARS["fault_heartbeat_interval_s"] = (
            lambda: _ft._HEARTBEAT_S, _set_heartbeat,
            "how often each fault-tolerant rank publishes its heartbeat "
            "and scans its peers' (mpi_tpu/ft.py); keep well below "
            "fault_detect_timeout_s.  Read at ft.enable() time")
        _CVARS["verify_stall_timeout_s"] = (
            lambda: _vstate._STALL_TIMEOUT_S, _set_verify_stall,
            "runtime-verifier stall bound (mpi_tpu/verify): a verified "
            "blocking wait (or nonblocking polling loop) stuck this long "
            "publishes its pending op out-of-band and runs the wait-for "
            "deadlock analysis — a proven cross-rank cycle/knot raises "
            "DeadlockError naming every rank, its pending op, and its "
            "call site.  Read at verify.enable() time")
        _CVARS["progress"] = (
            lambda: _prog._DEFAULT_MODE, _set_progress,
            "default async-progress mode of newly created worlds "
            "(mpi_tpu/progress.py): 'thread' starts one dedicated "
            "progress engine per world — background completion for "
            "nonblocking ops, doorbell-parked transport draining, "
            "pure-polling drain loops join deadlock detection; 'none' "
            "(default) keeps progress caller-financed with a single "
            "attribute test per operation.  Explicit run_local("
            "progress=...) and the MPI_TPU_PROGRESS environment "
            "variable override; read at world creation")
        _CVARS["nbc_mode"] = (
            lambda: _nbc._MODE, _set_nbc_mode,
            "nonblocking-collective dispatch mode (mpi_tpu/nbc.py): "
            "'auto' compiles i-collectives into schedule state machines "
            "advanced by the async progress engine whenever the world "
            "runs one (zero per-call threads — nbc_threads_spawned "
            "stays 0, nbc_state_machines counts); 'thread' forces "
            "today's one-_ThreadRequest-per-call semantics everywhere "
            "(the escape hatch, and the honest pre/post bench toggle).  "
            "Worlds without the engine always take the thread path.  "
            "MPI_TPU_NBC seeds the default")
        _CVARS["nbc_fold_workers"] = (
            lambda: _nbc._FOLD_WORKERS, _set_nbc_fold_workers,
            "width of the per-world fold pool that advances collective "
            "state machines (mpi_tpu/nbc.py): receive completions "
            "enqueue the machine, a pool worker applies its folds/"
            "copies and posts the sends they unlock — so reductions "
            "never run on the engine thread.  2 (default) keeps one "
            "worker free while another blocks in a ring-full forward.  "
            "Read at a world's first state machine; "
            "MPI_TPU_NBC_FOLD_WORKERS seeds the default")
        _CVARS["nbc_sm_max_bytes"] = (
            lambda: _nbc._SM_MAX_BYTES, _set_nbc_sm_max,
            "payload ceiling of the state-machine i-collective path "
            "(mpi_tpu/nbc.py): reductions whose working buffer — or "
            "ialltoall calls whose largest block — exceeds this many "
            "bytes keep the threaded blocking algorithms, whose "
            "SEGMENTED pipelines own the bandwidth regime, while "
            "latency-bound calls below it ride the engine with zero "
            "per-call threads.  0 removes the cap.  Must agree across "
            "the group for the reductions (geometry-congruent plans); "
            "the alltoall gate is rank-local by design (both paths "
            "emit the identical pairwise frame sequence).  "
            "MPI_TPU_NBC_SM_MAX_BYTES seeds the default")
        _CVARS["coll_sm_arena_bytes"] = (
            lambda: _sm._ARENA_BYTES, _set_sm_arena,
            "size of the per-communicator shared-memory collective arena "
            "(mpi_tpu/coll_sm.py): P flag lines + P data slots; a rank's "
            "slot is the P-th share, the ceiling of the in-place block "
            "paths.  0 disables the arena (every sm/auto request falls "
            "back to the wire algorithms).  Read at arena creation — set "
            "it before the communicator's first sm collective")
        _CVARS["coll_sm_eager_bytes"] = (
            lambda: _sm._EAGER_BYTES, _set_sm_eager,
            "flat-path gate of the arena reductions: payloads at or "
            "below this are folded whole from every peer's slot "
            "(latency-optimal); above it allreduce switches to the "
            "chunked in-place fold and reduce stays on the binomial "
            "tree")
        def _set_wire_dtype(v):
            if v not in _compress.FORMATS:
                raise ValueError(
                    f"compress_wire_dtype must be one of "
                    f"{sorted(_compress.FORMATS)}, got {v!r}")
            _compress._WIRE_DTYPE = v

        def _set_topk_ratio(v):
            if float(v) <= 0:
                raise ValueError("compress_topk_ratio must be > 0")
            _compress._TOPK_RATIO = float(v)

        _CVARS["compress_wire_dtype"] = (
            lambda: _compress._WIRE_DTYPE, _set_wire_dtype,
            "wire encoding the plain algorithm='compressed' spelling "
            "resolves to (mpi_tpu/compress.py): 'bf16' (2 bytes/elem, "
            "RNE) or 'int8' (fp8-style per-segment max-abs scale + int8 "
            "mantissas, 1 byte/elem).  Folds stay f32 (f64 payloads "
            "f64).  Must agree across the group — the runtime "
            "verifier's collective signature carries the RESOLVED wire "
            "dtype, so skew raises CollectiveMismatchError before data "
            "moves.  Explicit 'compressed:bf16'/'compressed:int8' "
            "override per call")
        _CVARS["compress_topk_ratio"] = (
            lambda: _compress._TOPK_RATIO, _set_topk_ratio,
            "fraction of gradient entries algorithm='compressed:topk' "
            "transmits per rank (ceil(ratio*n), >= 1, clamped to n — "
            "ratios >= 1 degrade to dense).  The unsent remainder "
            "accumulates in the per-(shape,dtype,op) error-feedback "
            "residual on the communicator "
            "(mpi_tpu.compress.reset_residuals clears).  Must agree "
            "across the group: the resolved k rides the verifier "
            "signature's counts field")

        def _set_link_retry(v):
            if float(v) < 0:
                raise ValueError(
                    "link_retry_timeout_s must be >= 0 (0 = healing off)")
            _resilience._RETRY_TIMEOUT_S = float(v)

        def _set_link_window(v):
            if int(v) <= 0:
                raise ValueError("link_window_bytes must be > 0")
            _resilience._WINDOW_BYTES = int(v)

        def _set_connect_retry(v):
            if float(v) < 0:
                raise ValueError(
                    "connect_retry_timeout_s must be >= 0 "
                    "(0 = first-failure raise)")
            _resilience._CONNECT_RETRY_TIMEOUT_S = float(v)

        def _set_retain_copy(v):
            _resilience._RETAIN_COPY = int(bool(int(v)))

        def _set_recv_steering(v):
            _recvpool._STEERING = int(bool(int(v)))

        def _set_keepalive(v):
            if float(v) < 0:
                raise ValueError(
                    "link_keepalive_s must be >= 0 (0 = no probing)")
            _resilience._KEEPALIVE_S = float(v)

        def _set_epoch_grace(v):
            if float(v) < 0:
                raise ValueError("epoch_grace_s must be >= 0")
            # one knob, both byte-stream transports: the grace window
            # exists wherever an epoch stamp is compared (socket hello
            # acks, shm readiness files)
            _socket._EPOCH_GRACE_S = float(v)
            _shm._EPOCH_GRACE_S = float(v)

        _CVARS["link_retry_timeout_s"] = (
            lambda: _resilience._RETRY_TIMEOUT_S, _set_link_retry,
            "socket link-healing budget (mpi_tpu/resilience.py): a "
            "send-path OSError whose destination is NOT failure-"
            "suspected enters a reconnect loop (exponential backoff + "
            "jitter, resume handshake, retained-frame replay) bounded "
            "by this many seconds; also the no-ack-progress bound of a "
            "full retained window.  Keep it BELOW "
            "fault_detect_timeout_s so a dead peer resolves to "
            "ProcFailedError, never a masked hang.  0 disables healing "
            "(every link fault is terminal, frames stream unretained — "
            "the pre-resilience behavior; set it BEFORE the world's "
            "first send: frames sent while healing was off were never "
            "retained and cannot be replayed by a later enable).  "
            "MPI_TPU_LINK_RETRY_S seeds the default")
        _CVARS["link_window_bytes"] = (
            lambda: _resilience._WINDOW_BYTES, _set_link_window,
            "per-destination retained-frame window of the resilient "
            "socket link: sends block once this many unacked bytes "
            "are outstanding (one oversized frame may proceed alone); "
            "the window is what a reconnect replays, so it bounds both "
            "memory and replay time.  MPI_TPU_LINK_WINDOW_BYTES seeds "
            "the default")
        _CVARS["link_retain_copy"] = (
            lambda: _resilience._RETAIN_COPY, _set_retain_copy,
            "retained-window ownership mode (mpi_tpu/bufpool.py): 0 "
            "(default) retains frame bodies BY REFERENCE with "
            "copy-on-write on proven reuse — zero copies on the "
            "no-reuse hot path, but a buffer mutated outside any "
            "mpi_tpu operation while its frames are unacked needs "
            "bufpool.note_write() first (the borrow contract); 1 "
            "restores the eager per-frame snapshot (strict MPI "
            "buffered-send reusability, one memcpy per frame).  "
            "MPI_TPU_LINK_RETAIN_COPY seeds the default")
        _CVARS["recv_steering"] = (
            lambda: _recvpool._STEERING, _set_recv_steering,
            "receive-side rendezvous steering of the socket transport "
            "(mpi_tpu/recvpool.py): 1 (default) lets the reader thread "
            "recv() a matching frame's body DIRECTLY into the posted "
            "irecv's destination buffer — zero intermediate copy, "
            "priced by recv_pool_rendezvous / recv_bytes_steered; 0 "
            "forces every frame through the pool-fallback path (the "
            "honest pre/post bench toggle).  Channel accounting stays "
            "on either way, so toggling mid-run cannot desync the "
            "frame/consumer pairing.  MPI_TPU_RECV_STEERING seeds the "
            "default")
        _CVARS["link_keepalive_s"] = (
            lambda: _resilience._KEEPALIVE_S, _set_keepalive,
            "idle-link keepalive cadence of the resilient socket "
            "transport: connections that sent nothing for this long "
            "are probed with a header-only ack frame by the ack "
            "flusher, so a link torn while IDLE heals proactively "
            "instead of spiking the next send's latency.  0 disables "
            "probing; ignored entirely when link healing is off "
            "(link_retry_timeout_s = 0).  MPI_TPU_LINK_KEEPALIVE_S "
            "seeds the default")
        _CVARS["connect_retry_timeout_s"] = (
            lambda: _resilience._CONNECT_RETRY_TIMEOUT_S,
            _set_connect_retry,
            "initial server-connect retry budget of mpi_tpu.connect() "
            "/ serve.ServerClient: ConnectionRefusedError (the server "
            "is still binding) is retried with backoff + jitter for "
            "this long instead of raising on first failure.  0 "
            "restores first-failure raise.  MPI_TPU_CONNECT_RETRY_S "
            "seeds the default")
        _CVARS["epoch_grace_s"] = (
            lambda: _socket._EPOCH_GRACE_S, _set_epoch_grace,
            "grace window before an ahead-of-us membership epoch is "
            "declared EpochSkewError (socket hello acks AND shm "
            "readiness stamps): a healthy member applying a broadcast "
            "epoch transition milliseconds late keeps retrying with "
            "its own epoch re-read until the grace expires; a "
            "genuinely ousted straggler never catches up and still "
            "raises.  MPI_TPU_EPOCH_GRACE_S seeds the default")

        def _set_rejoin_timeout(v):
            if float(v) <= 0:
                raise ValueError("rejoin_timeout_s must be > 0")
            _membership._REJOIN_TIMEOUT_S = float(v)

        _CVARS["rejoin_timeout_s"] = (
            lambda: _membership._REJOIN_TIMEOUT_S, _set_rejoin_timeout,
            "default bound on an elastic-membership rejoin handshake "
            "(mpi_tpu/membership.py): claim -> admit -> epoch-stamped "
            "endpoints -> ready -> barrier, on BOTH the joiner "
            "(rejoin()) and survivor (accept_rejoin()) sides; explicit "
            "timeout= arguments override per call")
        _CVARS["tuning_table_path"] = (
            _tuning.table_path,
            lambda v: _tuning.set_table_path(str(v) if v else None),
            "path of the active per-machine tuning table (mpi_tpu/"
            "tuning): measured (transport, nranks, collective, payload-"
            "band) -> algorithm rows that algorithm='auto' consults "
            "before the built-in seed constants (tuned_table_hits / "
            "tuned_table_fallbacks pvars).  Empty = no table (seed "
            "constants only).  Writing loads + validates immediately "
            "(malformed tables raise TuningTableError); a table whose "
            "machine fingerprint does not match this host loads but "
            "never serves.  Must agree across the group, like every "
            "algorithm-steering cvar.  MPI_TPU_TUNING_TABLE / run_local("
            "tuning_table=) / launcher --tuning-table set it per world")
        _CVARS["gather_replicated_warn_bytes"] = (
            lambda: _GATHER_WARN_BYTES[0],
            lambda v: _GATHER_WARN_BYTES.__setitem__(0, int(v)),
            "SPMD gather/gatherv warn when the replicated [size, ...] "
            "stack exceeds this many bytes PER DEVICE (O(size x payload) "
            "HBM); use gather(..., sharded=True) to keep per-device HBM "
            "O(payload)")
        _builtin_done = True


# -- sessions ----------------------------------------------------------------

class Session:
    """MPI_T session: pvar reads are relative to the session's resets."""

    def __init__(self) -> None:
        self._base: Dict[str, int] = {}

    def read(self, name: str) -> int:
        return pvar_read(name) - self._base.get(name, 0)

    def reset(self, name: str) -> None:
        self._base[name] = pvar_read(name)

    def reset_all(self) -> None:
        for name in pvar_list():
            self.reset(name)


def session_create() -> Session:
    """MPI_T_pvar_session_create."""
    return Session()
