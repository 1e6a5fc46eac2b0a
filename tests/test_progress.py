"""Async progress engine (ISSUE 6 tentpole — mpi_tpu/progress.py).

Four contracts:

* background completion — with ``progress=thread`` a posted ``irecv``
  completes (``req._done`` flips) with NO wait/test call from any
  caller thread, on the local AND shm transports; the collective
  family (including the segmented multi-exchange paths and the
  i-collectives) keeps exact parity and the zero-pickled-bytes wire
  contract;
* deadlock coverage — a pure-polling ``MPI_Waitany`` drain loop (the
  PR-5 verifier residual) is published on the rank's behalf by the
  engine and raises :class:`DeadlockError` from the polling path; the
  same program under ``progress=none`` documents the residual (bounded
  no-detection); a merely-SLOW peer never false-positives, polling or
  not;
* FT interplay — a rank killed mid-``ialltoall`` with the engine
  running surfaces ProcFailedError within the same derived detection
  bound as without it;
* the off-mode zero-cost contract — ``progress=none`` creates no
  engine (``comm._progress is None`` is the ONE hot-path attribute
  test) and every ``progress_*`` pvar stays exactly 0.
"""

import threading
import time

import numpy as np
import pytest

from mpi_tpu import mpit, ops, progress
from mpi_tpu.api import MPI_Waitany
from mpi_tpu.errors import DeadlockError, ProcFailedError
from mpi_tpu.transport.faulty import FaultyTransport
from mpi_tpu.transport.local import KILLED, run_local
from tests.test_shm_backend import run_shm_world

DETECT_S = 1.0


# -- background completion ---------------------------------------------------


def test_irecv_completes_in_background_local():
    """The headline semantic: req._done flips while the receiver only
    sleeps — completion is engine-owned, not caller-financed."""
    def prog(comm):
        if comm.rank == 0:
            time.sleep(0.1)
            comm.send(np.arange(8.0), 1, tag=5)
            return "sent"
        req = comm.irecv(0, 5)
        deadline = time.time() + 10
        while not req._done and time.time() < deadline:
            time.sleep(0.01)  # deliberately NO wait()/test()
        assert req._done, "engine did not complete the irecv in background"
        return req.wait()

    out = run_local(prog, 2, progress="thread")
    np.testing.assert_array_equal(out[1], np.arange(8.0))


def test_irecv_completes_in_background_shm():
    """Same semantic on the shm transport: the engine's doorbell-parked
    park hook drains the native rings while every other thread of the
    rank sleeps (no user waiter, no helper cadence dependence)."""
    def prog(comm):
        progress.enable(comm)
        comm.barrier()
        if comm.rank == 0:
            comm.send(np.arange(1 << 15, dtype=np.float64), 1, tag=9)
            comm.barrier(algorithm="dissemination")
            return "sent"
        req = comm.irecv(0, 9)
        deadline = time.time() + 10
        while not req._done and time.time() < deadline:
            time.sleep(0.01)
        assert req._done, "shm engine did not drain/complete in background"
        got = req.wait()
        comm.barrier(algorithm="dissemination")
        return float(np.asarray(got)[-1])

    out = run_shm_world(prog, 2)
    assert out[1] == float((1 << 15) - 1)


def test_park_releases_progress_lock_and_recv_completes_inline():
    """PR-6 residual (c) regression: ``ShmTransport.progress_park``
    must NOT hold the progress lock across its futex nap.  While a
    parker naps, the lock is observably FREE — so a blocking user recv
    that starts mid-park takes the inline drain path itself instead of
    waiting a thread hop behind the engine — and the recv completes
    far inside the 5s park slice."""
    import threading

    def prog(comm):
        t = comm._t
        if comm.rank == 0:
            comm.barrier(algorithm="dissemination")
            time.sleep(0.35)  # rank 1's parker is napping by now
            comm.send(np.arange(1024.0), 1, tag=7)
            comm.barrier(algorithm="dissemination")
            return None
        stop = threading.Event()

        def parker():  # stands in for the engine loop's park call
            while not stop.is_set():
                try:
                    t.progress_park(5.0)
                except Exception:  # noqa: BLE001 - teardown race
                    return

        th = threading.Thread(target=parker, daemon=True)
        comm.barrier(algorithm="dissemination")
        th.start()
        time.sleep(0.15)  # inside the nap, before rank 0's send
        lock_free = t._progress_lock.acquire(blocking=False)
        if lock_free:
            t._progress_lock.release()
        t0 = time.monotonic()
        got = comm.recv(0, tag=7)
        took = time.monotonic() - t0
        stop.set()
        # the closing barrier's arrival rings the doorbell, popping the
        # parker out of its nap to observe `stop`
        comm.barrier(algorithm="dissemination")
        th.join(7.0)
        assert not th.is_alive(), "parker never exited"
        assert lock_free, \
            "progress_park held the progress lock across its futex nap"
        assert took < 2.0, \
            f"recv waited {took:.2f}s against a parked engine"
        return float(np.asarray(got)[-1])

    out = run_shm_world(prog, 2)
    assert out[1] == 1023.0


def test_user_recv_latency_unchanged_while_engine_parked():
    """ISSUE 12 satellite — the stronger spelling of PR-6 residual (c):
    with a REAL engine attached and parked on the doorbell, a blocking
    user recv whose message arrives mid-park completes at inline-drain
    latency.  If the park ever re-held the progress lock across its
    nap, each recv would queue up to a full park slice (0.25s) behind
    the engine — the median below would jump past the bound."""
    def prog(comm):
        progress.enable(comm)
        comm.barrier(algorithm="dissemination")
        if comm.rank == 0:
            for i in range(8):
                time.sleep(0.05)  # peer is blocked in recv, engine parked
                comm.send(np.arange(256.0), 1, tag=20 + i)
            comm.barrier(algorithm="dissemination")
            return None
        lats = []
        for i in range(8):
            t0 = time.monotonic()
            comm.recv(0, tag=20 + i)
            lats.append(time.monotonic() - t0)
        comm.barrier(algorithm="dissemination")
        return sorted(lats)[len(lats) // 2]

    idle0 = mpit.pvar_read("progress_idle_parks")
    out = run_shm_world(prog, 2)
    assert mpit.pvar_read("progress_idle_parks") > idle0, \
        "engine never actually parked during the run"
    # send cadence is 50ms, so the inline-drain median sits just above
    # it; a lock-across-the-nap regression adds ~a 250ms park slice
    assert out[1] < 0.15, \
        f"median blocking-recv latency {out[1]:.3f}s against a parked engine"


def test_collective_parity_and_wire_contract_under_thread():
    """The whole family stays exact under the engine, and the ring
    allreduce's zero-pickled-bytes contract survives — engine
    completion consumes already-delivered payloads, adding no wire
    traffic and no copies."""
    base_pickled = mpit.pvar_read("bytes_pickled_sent")

    def prog(comm):
        x = np.full(1 << 14, comm.rank + 1.0, np.float32)
        r1 = comm.allreduce(x, algorithm="ring")
        r2 = comm.ialltoall(
            [np.full(8, comm.rank * 10 + d, np.float64)
             for d in range(comm.size)]).wait()
        r3 = comm.iallreduce(np.float64(comm.rank)).wait()
        comm.barrier()
        return float(r1[0]), np.asarray(r2)[:, 0].tolist(), float(r3)

    out = run_local(prog, 3, progress="thread")
    for r, (s, col, isum) in enumerate(out):
        assert s == 6.0
        assert col == [d * 10.0 + r for d in range(3)]
        assert isum == 3.0
    assert mpit.pvar_read("progress_wakeups") > 0
    assert mpit.pvar_read("bytes_pickled_sent") == base_pickled


def test_seg_window_advanced_by_engine():
    """Forced multi-segment exchanges under the engine: the credit
    window's tail sends are posted by completion callbacks
    (_SegSender.advance) — parity proves ordering held."""
    old = mpit.cvar_read("collective_segment_bytes")
    mpit.cvar_write("collective_segment_bytes", 64)
    try:
        def prog(comm):
            x = np.arange(2048, dtype=np.float64) + comm.rank
            r = comm.allreduce(x, algorithm="ring")
            h = comm.allreduce(x, algorithm="recursive_halving")
            return r, h

        out = run_local(prog, 2, progress="thread")
        want = np.arange(2048, dtype=np.float64) * 2 + 1
        for r, h in out:
            np.testing.assert_allclose(r, want)
            np.testing.assert_allclose(h, want)
    finally:
        mpit.cvar_write("collective_segment_bytes", old)


# -- deadlock coverage (the PR-5 pure-polling residual) ----------------------


@pytest.fixture
def _fast_stall():
    old = mpit.cvar_read("verify_stall_timeout_s")
    mpit.cvar_write("verify_stall_timeout_s", 1.0)
    yield
    mpit.cvar_write("verify_stall_timeout_s", old)


def _drain_loop(comm, give_up_s):
    """A pure-polling drain loop (the body MPI_Waitany spins on) over an
    irecv that can never complete (cross pattern, nobody sends) —
    bounded so the no-engine leg documents the residual instead of
    hanging the suite."""
    req = comm.irecv((comm.rank + 1) % comm.size, tag=3)
    deadline = time.time() + give_up_s
    try:
        while time.time() < deadline:
            done, _ = req.test()
            if done:
                return "completed"
            time.sleep(0.001)
        return "no-detection"
    except DeadlockError as e:
        assert len(e.ranks) == comm.size
        return "deadlocked"


def test_waitany_drain_loop_deadlock_detected(_fast_stall):
    """progress=thread: the engine publishes the OR-set on the polling
    rank's behalf, the wait-for analysis closes, and the actual
    ``MPI_Waitany`` call raises DeadlockError from its polling loop —
    the residual the ROADMAP carried since PR 5."""
    base = mpit.pvar_read("verify_deadlocks_detected")

    def prog(comm):
        req = comm.irecv((comm.rank + 1) % comm.size, tag=3)
        try:
            MPI_Waitany([req])  # blocks polling: nobody ever sends
            return "completed"
        except DeadlockError as e:
            assert len(e.ranks) == comm.size
            return "deadlocked"

    out = run_local(prog, 2, verify=True, progress="thread", timeout=60)
    assert out == ["deadlocked", "deadlocked"], out
    assert mpit.pvar_read("verify_deadlocks_detected") > base


def test_waitany_drain_loop_escapes_without_engine(_fast_stall):
    """progress=none: the same program polls forever undiagnosed — the
    documented limit of blocking-waits-only participation, and the
    contrast that proves the engine (not some other change) closed
    it."""
    out = run_local(_drain_loop, 2, args=(4.0,), verify=True,
                    progress="none", timeout=60)
    assert out == ["no-detection", "no-detection"], out


def test_slow_peer_never_false_positives(_fast_stall):
    """Polling against a peer that is merely SLOW (computing, will send)
    must complete cleanly: the analysis needs a closed picture, and the
    sender rank has no blocked/polling entry."""
    base = mpit.pvar_read("verify_deadlocks_detected")

    def prog(comm):
        if comm.rank == 0:
            time.sleep(3.0)  # well past the 1s stall bound
            comm.send(b"late", 1, tag=2)
            return "sent"
        req = comm.irecv(0, 2)
        while True:
            i, v = MPI_Waitany([req])
            if i is not None:
                return v

    out = run_local(prog, 2, verify=True, progress="thread", timeout=60)
    assert out == ["sent", b"late"]
    assert mpit.pvar_read("verify_deadlocks_detected") == base


def test_waitany_publishes_exact_request_set(_fast_stall):
    """A stalled ``MPI_Waitany`` publishes the OR-set of ITS OWN request
    list.  Rank 0 posts two tracked irecvs (from 1 and from 2) but
    drains only the first through Waitany — the published entry must
    name source 1 alone, never the {1, 2} union over every tracked
    request (which would accuse rank 2 of blocking a loop that is not
    waiting for it)."""
    def prog(comm):
        if comm.rank == 0:
            req_a = comm.irecv(1, tag=5)
            req_b = comm.irecv(2, tag=6)
            i, v = MPI_Waitany([req_a])
            assert (i, v) == (0, b"from-1")
            return req_b.wait()
        if comm.rank == 1:
            time.sleep(3.0)  # hold rank 0 in the drain past the stall
            comm.send(b"from-1", 0, tag=5)
            return "sent"
        # rank 2: watch the shared board for rank 0's drain-loop entry,
        # record the targets it names, then release the second irecv
        board = comm._t._verify_world.board
        seen = None
        deadline = time.time() + 2.5
        while time.time() < deadline:
            e = board.read_all().get(0)
            if e is not None and e.get("kind") == "waitany-poll":
                seen = list(e.get("targets", ()))
                break
            time.sleep(0.05)
        comm.send(b"from-2", 0, tag=6)
        return seen

    out = run_local(prog, 3, verify=True, progress="thread", timeout=60)
    assert out[0] == b"from-2"
    assert out[1] == "sent"
    assert out[2] == [1], out[2]


def test_posted_irecv_without_polling_never_published(_fast_stall):
    """A rank that posts an irecv and then just computes (no polls) is
    NOT a drain loop: the engine must not publish it, even while a peer
    blocks on this rank — compute-overlap programs stay clean."""
    base = mpit.pvar_read("verify_deadlocks_detected")

    def prog(comm):
        if comm.rank == 0:
            # posts an irecv it will only consume much later, computes
            req = comm.irecv(1, 7)
            time.sleep(3.0)
            comm.send(np.arange(4.0), 1, tag=8)
            return req.wait()
        got = comm.recv(0, 8)  # blocks well past the stall bound
        comm.send(np.arange(2.0), 0, 7)
        return got

    out = run_local(prog, 2, verify=True, progress="thread", timeout=60)
    np.testing.assert_array_equal(out[0], np.arange(2.0))
    np.testing.assert_array_equal(out[1], np.arange(4.0))
    assert mpit.pvar_read("verify_deadlocks_detected") == base


# -- FT interplay ------------------------------------------------------------


def test_ft_kill_mid_ialltoall_detection_bound_unchanged():
    """Rank 1 dies mid-exchange with the engine running; the survivor's
    ialltoall wait converts the detector hit into ProcFailedError
    within the same multiple of the bound the engine-less suite
    asserts."""
    old = {k: mpit.cvar_read(k) for k in ("fault_detect_timeout_s",
                                          "fault_heartbeat_interval_s")}
    mpit.cvar_write("fault_detect_timeout_s", DETECT_S)
    mpit.cvar_write("fault_heartbeat_interval_s", 0.05)
    try:
        def kill_rank1(inner):
            return (FaultyTransport(inner, kill_after_n=2)
                    if inner.world_rank == 1 else inner)

        def prog(comm):
            blocks = [np.ones(1 << 12) * d for d in range(comm.size)]
            if comm.rank == 1:
                comm.alltoall(blocks)  # dies on send 2
                return "unreachable"
            t0 = time.monotonic()
            with pytest.raises(ProcFailedError) as ei:
                comm.ialltoall(blocks).wait()
            assert time.monotonic() - t0 < 6 * DETECT_S
            assert 1 in ei.value.failed
            return "diagnosed"

        out = run_local(prog, 3, transport_wrapper=kill_rank1,
                        fault_tolerance=True, progress="thread", timeout=60)
        assert out[0] == out[2] == "diagnosed"
        assert out[1] is KILLED
    finally:
        for k, v in old.items():
            mpit.cvar_write(k, v)


# -- off-mode zero-cost contract ---------------------------------------------


def test_off_mode_zero_wakeups_and_single_attribute():
    """progress=none: no engine object anywhere (the hot paths' one
    attribute test reads None) and every progress pvar stays 0 across
    real traffic."""
    # an earlier test's stopped engine may still be in its last park,
    # which would count an idle park here: let those threads finish
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and any(
            t.name.startswith("mpi-tpu-progress-")
            for t in threading.enumerate()):
        time.sleep(0.05)
    ses = mpit.session_create()
    ses.reset_all()

    def prog(comm):
        assert comm._progress is None
        assert getattr(comm._t, "_progress_engine", None) is None
        comm.allreduce(np.arange(64.0))
        r = comm.ialltoall([np.arange(4.0)] * comm.size).wait()
        comm.irecv(comm.rank, 1)  # posted, never matched: still no engine
        comm.barrier()
        return np.asarray(r).shape

    run_local(prog, 2, progress="none")
    for p in ("progress_wakeups", "progress_completions",
              "progress_idle_parks"):
        assert ses.read(p) == 0, p


def test_mode_resolution_and_cvar():
    """Explicit arg > MPI_TPU_PROGRESS env > ``progress`` cvar; bad
    values rejected everywhere."""
    import os

    assert progress.resolve_mode("thread") == "thread"
    assert progress.resolve_mode() == "none"
    old_env = os.environ.pop("MPI_TPU_PROGRESS", None)
    try:
        mpit.cvar_write("progress", "thread")
        assert progress.resolve_mode() == "thread"
        assert mpit.cvar_read("progress") == "thread"
        os.environ["MPI_TPU_PROGRESS"] = "none"
        assert progress.resolve_mode() == "none"
        assert progress.resolve_mode("thread") == "thread"
    finally:
        mpit.cvar_write("progress", "none")
        if old_env is None:
            os.environ.pop("MPI_TPU_PROGRESS", None)
        else:
            os.environ["MPI_TPU_PROGRESS"] = old_env
    with pytest.raises(ValueError):
        progress.resolve_mode("fibers")
    with pytest.raises(ValueError):
        mpit.cvar_write("progress", "fibers")


def test_waitany_drain_detects_exited_peer(_fast_stall):
    """The engine also converts the wait-on-exited case for pollers: a
    drain loop over a peer whose program RETURNED is diagnosed (the
    exited entry closes the picture)."""
    def prog(comm):
        if comm.rank == 0:
            return "gone"  # publishes 'exited' via run_local
        return _drain_loop(comm, 20.0)

    out = run_local(prog, 2, verify=True, progress="thread", timeout=60)
    assert out[0] == "gone"
    assert out[1] == "deadlocked"
