"""Transport plugin boundary (L1) + the shared message-matching engine.

SURVEY.md §1/§2: the load-bearing seam of the reference is the Communicator
plugin boundary — collectives are written against Communicator, Communicators
own a swappable Transport.  A Transport moves opaque payloads between world
ranks and supports MPI-style matching by (source, context, tag) with FIFO
ordering per (src, dst) channel [S].

The matching engine (Mailbox) is shared by every CPU transport so matching
semantics — including wildcard rules — are identical across them:
* ANY_SOURCE matches any source rank.
* ANY_TAG matches only *user* tags (>= 0); internal negative tags (used by
  collectives/barrier, see mpi_tpu/communicator.py) must be matched exactly,
  so user wildcard receives can never steal collective traffic.
"""

from __future__ import annotations

import threading
import time
from abc import ABC, abstractmethod
from typing import Any, Dict, List, Optional, Sequence, Tuple

ANY_SOURCE = -1
ANY_TAG = -1


class TransportError(RuntimeError):
    pass


class RecvTimeout(TransportError):
    pass


def payload_nbytes(obj: Any) -> Optional[int]:
    """Size of a sized payload (ndarray / bytes-like), None for opaque
    objects — the count a probe can report without consuming (Status
    applies the same rule after a receive)."""
    if hasattr(obj, "nbytes"):
        return int(obj.nbytes)
    if isinstance(obj, (bytes, bytearray, memoryview)):
        return len(obj)
    return None


class Mailbox:
    """Thread-safe matching queue of (src, ctx, tag, payload, stamp)
    messages.  ``stamp`` is the sender's vector-clock stamp under verify
    mode and None otherwise (mpi_tpu/verify/vclock.py)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._items: List[Tuple[int, int, int, Any, Any]] = []
        self._closed = False
        # lifetime delivery count: the runtime verifier's cheap progress
        # stamp (mpi_tpu/verify/deadlock.py) — a "blocked" rank whose
        # mailbox keeps receiving is matching-starved, not deadlocked,
        # and the confirm pass uses the stamp to tell the two apart
        self.deliveries = 0
        # receiver-side vector clock, attached by verify.enable(): the
        # consume scan merges each consumed stamp and runs the wildcard
        # race check against the pending alternates it can see under
        # this lock.  None outside verify mode (zero cost).
        self.clock = None

    def deliver(self, src: int, ctx: int, tag: int, payload: Any,
                stamp: Any = None) -> None:
        with self._cv:
            self._items.append((src, ctx, tag, payload, stamp))
            self.deliveries += 1
            self._cv.notify_all()

    def nudge(self) -> None:
        """Wake every waiter without delivering anything — the progress
        engine's stop path pops its parked thread out of wait_activity."""
        with self._cv:
            self._cv.notify_all()

    def wait_activity(self, seen: int, timeout: float) -> int:
        """Park until the delivery count moves past ``seen`` (or timeout);
        returns the current count.  The progress engine's doorbell on
        transports whose deliveries arrive from other threads (socket
        reader threads, local-world peer sends).  Raises TransportError
        once closed so a parked engine exits instead of spinning."""
        with self._cv:
            if self.deliveries == seen and not self._closed:
                self._cv.wait(timeout)
            if self._closed:
                raise TransportError("transport closed while parked")
            return self.deliveries

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    @staticmethod
    def _matches(item, source: int, ctx, tag: int) -> bool:
        s, c, t = item[0], item[1], item[2]
        if c != ctx:
            return False
        if source != ANY_SOURCE and s != source:
            return False
        if tag == ANY_TAG:
            return t >= 0  # wildcards never match internal (negative) tags
        return t == tag

    def _scan_locked(self, source: int, ctx, tag: int,
                     consume: bool) -> Optional[Tuple[Any, int, int]]:
        """Oldest matching message as (payload, src, tag); pops iff consume.
        Caller holds the lock."""
        for i, item in enumerate(self._items):
            if self._matches(item, source, ctx, tag):
                s, _, t, payload, stamp = item
                if consume:
                    self._items.pop(i)
                    if self.clock is not None and stamp is not None:
                        # verify mode: merge the consumed stamp; for a
                        # USER wildcard receive, every other pending
                        # message this scan could equally have matched
                        # is a race candidate (internal negative tags
                        # are exact-matched and never race)
                        wild = (source == ANY_SOURCE
                                and (tag >= 0 or tag == ANY_TAG))
                        alts = ([(it[0], it[4]) for it in self._items
                                 if self._matches(it, ANY_SOURCE, ctx, tag)
                                 and it[0] != s and it[4] is not None]
                                if wild else ())
                        self.clock.note_consume(s, t, stamp, alts, wild)
                return payload, s, t
        return None

    def _blocking_scan(self, source: int, ctx, tag: int, consume: bool,
                       timeout: Optional[float], what: str) -> Tuple[Any, int, int]:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                hit = self._scan_locked(source, ctx, tag, consume)
                if hit is not None:
                    return hit
                if self._closed:
                    raise TransportError(
                        f"transport closed while waiting for {what}"
                        f"(source={source}, ctx={ctx}, tag={tag})"
                    )
                if deadline is None:
                    self._cv.wait()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        pending = [(s, c, t) for s, c, t, _, _ in
                                   self._items[:16]]
                        raise RecvTimeout(
                            f"{what}(source={source}, ctx={ctx}, tag={tag}) timed "
                            f"out after {timeout}s; pending={pending}"
                        )
                    self._cv.wait(remaining)

    def match(
        self, source: int, ctx, tag: int, timeout: Optional[float] = None
    ) -> Tuple[Any, int, int]:
        """Block until the oldest message matching (source, ctx, tag) arrives;
        return (payload, src, tag)."""
        return self._blocking_scan(source, ctx, tag, True, timeout, "recv")

    def poll(self, source: int, ctx, tag: int) -> Optional[Tuple[Any, int, int]]:
        """Non-blocking match: pop and return the oldest matching message, or
        None if nothing matches right now (MPI_Test substrate).  Raises
        TransportError on a closed, unmatched mailbox so polling loops fail
        like blocking receives do instead of spinning forever."""
        with self._lock:
            hit = self._scan_locked(source, ctx, tag, True)
            if hit is None and self._closed:
                raise TransportError(
                    f"transport closed while polling recv(source={source}, "
                    f"ctx={ctx}, tag={tag})"
                )
            return hit

    def peek_nowait(
        self, source: int, ctx, tag: int
    ) -> Optional[Tuple[int, int, Optional[int]]]:
        """Non-blocking, non-consuming scan: (src, tag, nbytes) of the
        oldest match, or None (MPI_Iprobe substrate — keeps FIFO
        intact).  ``nbytes`` is the queued payload's size when it is a
        sized buffer (the message IS local at peek time, so the probe
        can honor the probe+get_count+recv sizing idiom — ADVICE r4
        #2), None for opaque objects."""
        with self._lock:
            hit = self._scan_locked(source, ctx, tag, False)
            if hit is None and self._closed:
                raise TransportError(
                    f"transport closed while probing (source={source}, "
                    f"ctx={ctx}, tag={tag})"
                )
            return (None if hit is None
                    else (hit[1], hit[2], payload_nbytes(hit[0])))

    def peek(self, source: int, ctx, tag: int,
             timeout: Optional[float] = None
             ) -> Tuple[int, int, Optional[int]]:
        """Like match() but WITHOUT consuming: block until a matching message
        is queued and return its (src, tag, nbytes) — MPI_Probe
        semantics (see peek_nowait for the count)."""
        p, s, t = self._blocking_scan(source, ctx, tag, False, timeout,
                                      "probe")
        return s, t, payload_nbytes(p)

    def count_matching(self, source: int, ctx, tag: int) -> int:
        """Number of queued messages matching (source, ctx, tag) right
        now — the recv-steering registry's activation BACKLOG: frames
        delivered before a user channel was activated were never
        counted, so the first posted user buffer seeds its pairing lag
        with this count (mpi_tpu/recvpool.py note_post_user)."""
        with self._lock:
            return sum(1 for item in self._items
                       if self._matches(item, source, ctx, tag))

    def pending_summary(self) -> List[Tuple[int, int, int]]:
        with self._lock:
            return [(s, c, t) for s, c, t, _, _ in self._items[:16]]

    def drain(self) -> List[Tuple[int, int, int]]:
        """Return and clear all pending (src, ctx, tag) — used by the finalize
        'unexpected message' check (sanitizer analogue, SURVEY.md §5)."""
        with self._lock:
            items = [(s, c, t) for s, c, t, _, _ in self._items]
            self._items.clear()
            return items


class Transport(ABC):
    """Moves payloads between world ranks; owns a Mailbox for incoming
    traffic.

    Fault classes (ISSUE 10): transports distinguish LINK faults (a
    connection-level hiccup between two live processes — healed
    transparently where the transport has connections to heal, see
    transport/socket.py + mpi_tpu/resilience.py) from PEER faults (the
    process on the other end is gone — surfaced as TransportError and
    wrapped into ProcFailedError by the FT layer).  Transports without
    a connection link have no link-fault class: shm's "link" is a
    mapped ring (memory does not reset mid-frame), the local transport's
    is a queue append."""

    # True only for transports that deliver payloads BY REFERENCE (the
    # in-process local transport with copy_payloads=False): callers that
    # honor MPI's buffer-reuse idiom (persistent requests) must snapshot
    # mutable payloads themselves.  Serializing transports copy anyway.
    aliases_payloads = False

    # Preferred pipeline-segment size of the segmented collective engine
    # for THIS transport's data plane (communicator._seg_exchange), used
    # when the ``collective_segment_bytes`` mpit cvar is 0 (= auto).  The
    # right value is a transport property: shm must keep window*segment
    # inside its fixed ring capacity, while loopback TCP already overlaps
    # via kernel socket buffers and instead wants few, large frames (the
    # per-frame host costs dominate it at bandwidth sizes).
    coll_segment_hint = 256 << 10

    # True only for transports whose ranks share a POSIX shared-memory
    # domain (the shm transport): unlocks the coll/sm collective arena
    # (mpi_tpu/coll_sm.py — ``algorithm="sm"`` and the ``auto`` routing).
    # Deliberately NOT inherited by wrappers like FaultyTransport, whose
    # point is to exercise the wire paths.
    supports_coll_sm = False

    # Receive-side rendezvous steering (mpi_tpu/recvpool.py, ISSUE 17):
    # True only for transports whose reader can land a frame's body
    # directly in a posted irecv's buffer (the socket transport).  Such
    # transports also expose ``recv_registry`` (a PostedRecvRegistry);
    # the communicator registers posted internal receives with it and
    # prices the recv-side store copies steering removes
    # (payload_copies).  Deliberately NOT inherited by wrappers like
    # FaultyTransport: message-level chaos rewrites delivery order, so
    # the wrapper must never advertise the inner reader's pairing.
    recv_steering = False
    recv_registry = None

    # Verify-mode vector clock (mpi_tpu/verify/vclock.py), attached by
    # verify.enable() together with mailbox.clock.  Send paths test
    # exactly ``verify_clock is None`` (the off-mode cost contract) and
    # under verify either wrap the wire ctx (remote framing) or pass
    # tick_send()'s stamp straight to mailbox.deliver (same-process
    # deliveries, which never reserialize the ctx).
    verify_clock = None

    def __init__(self, world_rank: int, world_size: int) -> None:
        self.world_rank = world_rank
        self.world_size = world_size
        self.mailbox = Mailbox()
        # Elastic-membership generation (mpi_tpu/membership.py): the
        # monotone epoch this process believes its world is in.  Bumped
        # by shrink() (in survivor lockstep, riding the agreement) and
        # by membership.survivor_transition(); stamped into transport
        # hellos so a stale-epoch straggler is rejected loudly
        # (EpochSkewError) instead of cross-wiring two generations.
        self.epoch = 0
        # world rank -> minimum endpoint epoch acceptable when (re)
        # connecting to that peer: set to the transition epoch for
        # REPLACED slots, so a survivor re-handshaking can never adopt
        # the dead incarnation's leftover endpoints.
        self.min_peer_epoch: Dict[int, int] = {}

    @abstractmethod
    def send(self, dest: int, ctx, tag: int, payload: Any) -> None:
        """Buffered (non-blocking w.r.t. the receiver) send to world rank
        ``dest``.  FIFO order per (self, dest) channel is guaranteed.
        ``ctx`` is any hashable communicator-context id (the tree-path tuples
        allocated by Communicator.split/dup — collision-free by construction)."""

    def recv(
        self, source: int, ctx, tag: int, timeout: Optional[float] = None
    ) -> Tuple[Any, int, int]:
        return self.mailbox.match(source, ctx, tag, timeout=timeout)

    # Nonblocking/probe entry points live on the Transport (not reached into
    # the mailbox by callers) so decorator transports (tracing, fault
    # injection) see every completion path.

    def poll(self, source: int, ctx, tag: int) -> Optional[Tuple[Any, int, int]]:
        return self.mailbox.poll(source, ctx, tag)

    def peek(self, source: int, ctx, tag: int,
             timeout: Optional[float] = None
             ) -> Tuple[int, int, Optional[int]]:
        return self.mailbox.peek(source, ctx, tag, timeout=timeout)

    def peek_nowait(
        self, source: int, ctx, tag: int
    ) -> Optional[Tuple[int, int, Optional[int]]]:
        return self.mailbox.peek_nowait(source, ctx, tag)

    def membership_invalidate(self, dead: Sequence[int]) -> None:
        """Epoch-transition hook (mpi_tpu/membership.py): drop every
        cached endpoint to the given world ranks so the next send
        re-handshakes against the rendezvous dir (where a replacement
        publishes fresh endpoints under the new epoch).  Base: nothing
        cached per peer.  Transports with per-peer connections/rings
        override; the override must exclude in-flight senders (take the
        per-dest send lock) before tearing an endpoint down — and a
        transport with per-peer LINK-RESILIENCE state (sequenced
        streams, retained replay windows: the socket transport,
        mpi_tpu/resilience.py) must purge that state too, because a
        replaced slot's rejoiner starts fresh streams at seq 1 and must
        never be handed the corpse's replay or dedup horizon."""

    def progress_park(self, timeout: float) -> bool:
        """Progress-engine park hook (mpi_tpu/progress.py): block until
        incoming activity or ``timeout``; True iff anything arrived.
        Base implementation parks on the Mailbox condition variable —
        correct for every transport whose deliveries are pushed by
        other threads (socket reader threads, local-world peer sends).
        Transports that need a consumer to PULL data (shm rings)
        override this to drive their own progress machinery, parked on
        a real doorbell instead of spinning.  Raises TransportError
        once the transport closes, which is the engine's exit signal."""
        seen = self.mailbox.deliveries
        return self.mailbox.wait_activity(seen, timeout) != seen

    def close(self) -> None:
        self.mailbox.close()
