"""Resilient socket links (ISSUE 10): sequenced frames, cumulative
acks + bounded retained-window replay, reconnect with backoff, and the
fault classification that keeps a dead peer on the ProcFailedError path
while a torn connection heals transparently.

The in-process worlds here run the REAL socket stack (threads over
loopback TCP) so the full wire path — hello/resume handshake, header
seq/ack fields, replay, flusher — is exercised without subprocess cost;
the subprocess acceptance leg lives in benchmarks/chaos.py
(``bench.py --chaos --links``) and its quick smoke in
tests/test_benchmarks.py.
"""

import os
import socket as _socketlib
import tempfile
import threading
import time

import numpy as np
import pytest

from mpi_tpu import ft, mpit, ops, progress
from mpi_tpu.communicator import P2PCommunicator
from mpi_tpu.errors import EpochSkewError, ProcFailedError
from mpi_tpu.resilience import LinkState, backoff_delays, retry_connect
from mpi_tpu.transport.base import TransportError
from mpi_tpu.transport.faulty import FaultyTransport
from mpi_tpu.transport.socket import SocketTransport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_socket_world(fn, nranks, timeout=60.0, ft_detect=None):
    """fn(comm) on nranks real socket transports in threads; optionally
    FT-enabled over a shared MemoryLiveness (no rendezvous heartbeat
    files needed in-process)."""
    rdv = tempfile.mkdtemp(prefix="mpi_tpu_res_rdv_")
    results = [None] * nranks
    errors = []
    transports = [None] * nranks
    liveness = ft.MemoryLiveness(nranks) if ft_detect else None

    def runner(r):
        try:
            t = SocketTransport(r, nranks, rdv)
            transports[r] = t
            comm = P2PCommunicator(t, range(nranks))
            if ft_detect:
                ft.enable(comm, liveness=liveness,
                          detect_timeout_s=ft_detect, heartbeat_s=0.1)
            results[r] = fn(comm)
        except BaseException as e:  # noqa: BLE001
            import traceback

            errors.append((r, e, traceback.format_exc()))

    threads = [threading.Thread(target=runner, args=(r,), daemon=True)
               for r in range(nranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    alive = [i for i, t in enumerate(threads) if t.is_alive()]
    for t in transports:
        if t is not None:
            t.close()
    if errors:
        r, e, tb = errors[0]
        raise RuntimeError(f"rank {r} failed:\n{tb}") from e
    if alive:
        raise TimeoutError(f"socket ranks did not finish: {alive}")
    return results


# -- LinkState unit layer -----------------------------------------------------


def test_backoff_delays_jittered_and_capped():
    delays = backoff_delays(base=0.01, factor=2.0, cap=0.1)
    seen = [next(delays) for _ in range(12)]
    # full jitter: every delay within the (growing, capped) ceiling
    ceiling = 0.01
    for d in seen:
        assert 0.0 <= d <= ceiling + 1e-9
        ceiling = min(0.1, ceiling * 2.0)
    assert max(seen) <= 0.1 + 1e-9


def test_linkstate_seq_ack_prune():
    ls = LinkState(2)
    for i in range(5):
        assert ls.tx_retain(1, 7, b"x" * 10) == i + 1
    assert ls.retained_bytes(1) == 50
    ls.tx_ack(1, 3)
    assert ls.retained_bytes(1) == 20
    ls.tx_ack(1, 2)  # stale (replayed header): monotone no-op
    assert ls.retained_bytes(1) == 20
    ls.tx_ack(1, 5)
    assert ls.retained_bytes(1) == 0


def test_linkstate_rx_dedup_and_gap():
    ls = LinkState(2)
    got = []
    assert ls.rx_gate(0, 1, lambda: got.append(1))
    assert ls.rx_gate(0, 2, lambda: got.append(2))
    # replay duplicates (reconnect raced a delivered frame): dropped
    assert not ls.rx_gate(0, 1, lambda: got.append("dup"))
    assert not ls.rx_gate(0, 2, lambda: got.append("dup"))
    assert got == [1, 2]
    assert ls.delivered(0) == 2
    # a GAP is a protocol violation, never silent reordering
    with pytest.raises(TransportError, match="sequence gap"):
        ls.rx_gate(0, 4, lambda: got.append("gap"))


def test_linkstate_resume_prunes_and_replays_tail():
    ls = LinkState(2)
    for i in range(4):
        ls.tx_retain(1, 7, bytes([i]) * 4)
    # peer reports it delivered up to 2: 1-2 pruned, 3-4 replayed
    pending = ls.resume(1, 2)
    assert [seq for seq, _, _ in pending] == [3, 4]
    assert ls.retained_bytes(1) == 8


def test_linkstate_generation_fences_stale_readers():
    """Review fix (PR 10): a reader thread still draining a REPLACED
    slot's old connection presents a stale stream generation — its
    piggybacked acks and frames must no-op, not poison the
    replacement's fresh streams.  (Without the fence, one stale ack of
    57 makes every real ack 1, 2, ... read as stale: the retained
    window toward the healthy rejoiner never prunes and wait_window
    declares it link-dead.)"""
    ls = LinkState(2)
    old_gen = ls.peer_gen(1)
    ls.tx_retain(1, 7, b"aaaa")
    ls.purge_peer(1)
    ls.tx_ack(1, 57, old_gen)          # stale reader's ack: dropped
    assert ls.tx_retain(1, 7, b"bb") == 1   # fresh stream from seq 1
    ls.tx_ack(1, 1, ls.peer_gen(1))    # the REAL ack must still prune
    assert ls.retained_bytes(1) == 0
    assert not ls.rx_gate(1, 57, lambda: None, old_gen)  # dropped, no gap
    assert ls.delivered(1) == 0
    assert ls.rx_gate(1, 1, lambda: None, ls.peer_gen(1))


def test_linkstate_purge_peer_clears_both_streams():
    ls = LinkState(3)
    ls.tx_retain(1, 7, b"abc")
    ls.rx_gate(1, 1, lambda: None)
    ls.purge_peer(1)
    assert ls.retained_bytes(1) == 0
    assert ls.delivered(1) == 0  # replacement's stream starts at 1
    assert ls.tx_retain(1, 7, b"d") == 1  # ... and so does ours


# -- live-world healing -------------------------------------------------------


def test_reset_between_frames_heals_with_parity():
    ses = mpit.session_create()
    ses.reset_all()

    def prog(comm):
        for i in range(12):
            if comm.rank == 0 and i in (3, 7):
                comm._t._inject_link_reset(1)
            out = comm.allreduce(np.full(512, float(comm.rank + i)),
                                 algorithm="ring")
            assert float(out[0]) == sum(r + i for r in range(comm.size))
        comm.barrier()

    run_socket_world(prog, 3)
    assert ses.read("link_reconnects") >= 2


def test_midframe_reset_replays_large_payload_bit_exact():
    ses = mpit.session_create()
    ses.reset_all()
    big = np.arange(1 << 20, dtype=np.float64)  # 8MB: multiple segments

    def prog(comm):
        inj = FaultyTransport(comm._t, link_reset_midframe_every=3)
        if comm.rank == 0:
            comm.send(big * 1.0, dest=1, tag=7)
            comm.send(big * 2.0, dest=1, tag=8)
        elif comm.rank == 1:
            a = comm.recv(source=0, tag=7)
            b = comm.recv(source=0, tag=8)
            assert np.array_equal(a, big)
            assert np.array_equal(b, big * 2.0)
        comm.barrier()
        return inj.link_midframe_resets

    res = run_socket_world(prog, 2)
    assert res[0] >= 1  # the sender actually tore a frame mid-body
    assert ses.read("link_faults_masked") >= 1
    assert ses.read("link_frames_replayed") >= 1


def test_hook_reset_storm_under_mixed_collectives():
    ses = mpit.session_create()
    ses.reset_all()

    def prog(comm):
        inj = FaultyTransport(comm._t, link_reset_every=11,
                              link_reset_midframe_every=17)
        for i in range(15):
            out = comm.allreduce(np.full(256, float(comm.rank + i)),
                                 algorithm="ring")
            assert float(out[0]) == sum(r + i for r in range(comm.size))
            got = comm.alltoall([np.full(4, float(comm.rank * 10 + d))
                                 for d in range(comm.size)])
            for s in range(comm.size):
                assert float(got[s][0]) == s * 10 + comm.rank
        comm.barrier()
        return inj.link_resets + inj.link_midframe_resets

    res = run_socket_world(prog, 3, timeout=120)
    total = sum(res)
    assert total >= 6, res
    # every sender-side reset of an established conn is healed by a
    # counted reconnect — the chaos acceptance inequality
    assert ses.read("link_reconnects") >= total
    assert ses.read("link_faults_masked") >= total


def test_reset_storm_bit_parity_with_steered_receives():
    """ISSUE 17 regression of the links-chaos acceptance: rendezvous
    steering stays BIT-exact while a reset storm tears connections —
    torn mid-steer frames are replayed onto the pool path, the fenced
    watermark keeps replays uncounted, and every recycled receive
    buffer delivers the same bytes the copy path would have.  Payloads
    are pool-class sized (>= 1MB) so both the steered and pool-staged
    receive paths run under the churn."""
    ses = mpit.session_create()
    ses.reset_all()
    n = 1 << 17  # 1MB doubles: above the recv-pool floor

    def prog(comm):
        inj = FaultyTransport(comm._t, link_reset_every=7,
                              link_reset_midframe_every=11)
        for i in range(10):
            x = np.full(n, float(comm.rank + i))
            out = comm.allreduce(x, algorithm="ring")
            want = float(sum(r + i for r in range(comm.size)))
            # bit parity, not allclose: integer-valued sums are exact
            assert np.array_equal(out, np.full(n, want)), i
        comm.barrier()
        return inj.link_resets + inj.link_midframe_resets

    res = run_socket_world(prog, 2, timeout=120)
    assert sum(res) >= 2, res
    assert ses.read("link_faults_masked") >= 2
    # the storm ran THROUGH the steering path, not around it
    assert ses.read("recv_bytes_steered") > 0


def test_accept_side_drop_retried_by_connector():
    def prog(comm):
        if comm.rank == 1:
            # the acceptor drops the next TWO incoming connections
            # after reading the hello (no ack): the connector's
            # bounded retry must win without user-visible noise
            FaultyTransport(comm._t, link_accept_drop=2)
        comm.barrier()
        if comm.rank == 0:
            comm.send(np.arange(100.0), dest=1, tag=3)
        elif comm.rank == 1:
            got = comm.recv(source=0, tag=3)
            assert np.array_equal(got, np.arange(100.0))
        comm.barrier()

    run_socket_world(prog, 2)


def test_link_stall_is_not_a_fault():
    ses = mpit.session_create()
    ses.reset_all()

    def prog(comm):
        inj = FaultyTransport(comm._t, link_stall_every=5,
                              link_stall_s=0.2)
        for i in range(8):
            out = comm.allreduce(np.full(64, 1.0), algorithm="ring")
            assert float(out[0]) == comm.size
        comm.barrier()
        return inj.link_stalls

    res = run_socket_world(prog, 2, timeout=60)
    assert sum(res) >= 1
    # a slow link heals nothing because nothing broke
    assert ses.read("link_reconnects") == 0
    assert ses.read("link_faults_masked") == 0


def test_window_bound_one_way_flood_acked_by_flusher():
    old = mpit.cvar_read("link_window_bytes")
    mpit.cvar_write("link_window_bytes", 64 << 10)
    try:
        def prog(comm):
            payload = np.ones(4096, np.float64)  # 32KB frames
            if comm.rank == 0:
                for i in range(40):  # 1.25MB >> 64KB window
                    comm.send(payload * i, dest=1, tag=4)
                # the window drains only through the peer's acks (all
                # traffic is one-way: the flusher is load-bearing)
                deadline = time.monotonic() + 10.0
                while (comm._t._link.retained_bytes(1) > 0
                       and time.monotonic() < deadline):
                    time.sleep(0.02)
                assert comm._t._link.retained_bytes(1) == 0
            elif comm.rank == 1:
                for i in range(40):
                    got = comm.recv(source=0, tag=4)
                    assert float(got[0]) == float(i)
            comm.barrier()

        run_socket_world(prog, 2, timeout=60)
    finally:
        mpit.cvar_write("link_window_bytes", old)


def test_dead_peer_resolves_to_proc_failed_not_masked():
    """The classification guard: a peer that is genuinely GONE (its
    transport closed, its heartbeat stopped) must surface
    ProcFailedError within the FT bound — the healing loop's suspect
    re-check aborts the retry, never a masked hang."""
    barrier = threading.Barrier(2)
    dead_evt = threading.Event()

    def prog(comm):
        if comm.rank == 1:
            barrier.wait()
            comm._t._ft_world.stop()   # heartbeat stops...
            comm._t.close()            # ... and the endpoints die
            dead_evt.set()
            time.sleep(4.0)            # stay resident; never answer
            return "corpse"
        barrier.wait()
        dead_evt.wait()
        t0 = time.monotonic()
        with pytest.raises(ProcFailedError):
            for i in range(200):
                comm.send(np.ones(2048), dest=1, tag=9)
                time.sleep(0.01)
        took = time.monotonic() - t0
        assert took < 8.0, f"diagnosis took {took:.1f}s"
        return "diagnosed"

    res = run_socket_world(prog, 2, timeout=60, ft_detect=1.0)
    assert res[0] == "diagnosed"


def test_engine_owned_recv_survives_reconnect():
    """progress=thread: a posted irecv completed by the ENGINE must be
    oblivious to a link teardown between post and send — delivery goes
    through the same sequenced reader/mailbox path."""

    def prog(comm):
        progress.enable(comm)
        try:
            if comm.rank == 1:
                req = comm.irecv(source=0, tag=6)
                comm.send(np.zeros(1), dest=0, tag=1)  # "posted" signal
                got = req.wait()
                assert np.array_equal(got, np.arange(64.0))
            else:
                comm.recv(source=1, tag=1)
                comm._t._inject_link_reset(1)  # tear the link first
                comm.send(np.arange(64.0), dest=1, tag=6)
            comm.barrier()
        finally:
            eng = getattr(comm._t, "_progress_engine", None)
            if eng is not None:
                eng.stop()

    run_socket_world(prog, 2)


def test_healing_disabled_streams_unretained_and_faults_terminal():
    """Review fix (PR 10): link_retry_timeout_s = 0 restores the
    pre-resilience contract end to end — frames stream directly
    (nothing snapshotted or retained, zero link_bytes_retained, no
    window stall verdicts), and a mid-frame fault is TERMINAL."""
    ses = mpit.session_create()
    ses.reset_all()
    old = mpit.cvar_read("link_retry_timeout_s")
    mpit.cvar_write("link_retry_timeout_s", 0)
    try:
        def prog(comm):
            if comm.rank == 0:
                for i in range(5):
                    comm.send(np.arange(4096.0) * i, dest=1, tag=3)
                assert comm._t._link.retained_bytes(1) == 0
            else:
                for i in range(5):
                    got = comm.recv(source=0, tag=3)
                    assert np.array_equal(got, np.arange(4096.0) * i)
            comm.barrier()

        run_socket_world(prog, 2)
        assert ses.read("link_bytes_retained") == 0
        assert ses.read("link_reconnects") == 0

        def prog2(comm):
            if comm.rank == 0:
                FaultyTransport(comm._t, link_reset_midframe_every=1)
                with pytest.raises(TransportError,
                                   match="healing disabled"):
                    comm.send(np.ones(4), dest=1, tag=1)
            return True

        assert run_socket_world(prog2, 2)[0]
    finally:
        mpit.cvar_write("link_retry_timeout_s", old)


# -- membership interplay (satellite: purge on slot replacement) --------------


def test_membership_invalidate_purges_per_dest_link_state():
    def prog(comm):
        t = comm._t
        if comm.rank == 0:
            for i in range(3):
                comm.send(np.ones(8) * i, dest=1, tag=2)
            # slot 1 is replaced under epoch 1: the corpse's retained
            # window, seq state, and delivery marks must all die with
            # it — a rejoiner starts at seq 1 and must never see a
            # stale replay
            from mpi_tpu import membership

            membership.survivor_transition(t, 1, [1])
            assert t._link.retained_bytes(1) == 0
            assert t._link.delivered(1) == 0
            assert t._link.tx_retain(1, 7, b"x") == 1
        return True

    # 2-rank world; rank 1 exits immediately (it only exists so rank
    # 0's sends have a live acceptor before the transition)
    def prog_wrap(comm):
        if comm.rank == 1:
            for i in range(3):
                comm.recv(source=0, tag=2)
            return True
        return prog(comm)

    assert all(run_socket_world(prog_wrap, 2))


# -- epoch grace cvar (satellite) --------------------------------------------


def test_epoch_grace_cvar_writes_both_transports():
    from mpi_tpu.transport import shm as shm_mod
    from mpi_tpu.transport import socket as socket_mod

    old = mpit.cvar_read("epoch_grace_s")
    try:
        mpit.cvar_write("epoch_grace_s", 0.123)
        assert socket_mod._EPOCH_GRACE_S == 0.123
        assert shm_mod._EPOCH_GRACE_S == 0.123
        with pytest.raises(ValueError):
            mpit.cvar_write("epoch_grace_s", -1)
    finally:
        mpit.cvar_write("epoch_grace_s", old)


@pytest.mark.parametrize("catches_up", [True, False])
def test_epoch_grace_tolerates_laggy_catchup_only(catches_up):
    """A peer one epoch AHEAD is tolerated while we catch up within the
    grace (the broadcast-transition race) — but a genuinely stale
    straggler (epoch never catches up) still raises EpochSkewError."""
    old = mpit.cvar_read("epoch_grace_s")
    mpit.cvar_write("epoch_grace_s", 1.5 if catches_up else 0.3)
    # rank 0 connects only once rank 1 is ahead: under load its first
    # send could otherwise handshake while both are still at epoch 0
    ahead = threading.Event()
    try:
        def prog(comm):
            t = comm._t
            if comm.rank == 1:
                t.epoch = 1  # already transitioned
                ahead.set()
                if catches_up:
                    comm.recv(source=0, tag=3)
                else:
                    time.sleep(2.5)  # outlive the connector's grace
                return True
            # rank 0 lags: its own bump lands mid-grace (or never)
            assert ahead.wait(10)
            if catches_up:
                def bump():
                    time.sleep(0.4)
                    t.epoch = 1

                threading.Thread(target=bump, daemon=True).start()
                comm.send(np.ones(4), dest=1, tag=3)  # heals mid-grace
                return True
            with pytest.raises(EpochSkewError):
                comm.send(np.ones(4), dest=1, tag=3)
            return True

        assert all(run_socket_world(prog, 2, timeout=30))
    finally:
        mpit.cvar_write("epoch_grace_s", old)


# -- client connect retry (satellite) ----------------------------------------


def test_retry_connect_waits_out_refused_then_raises_others():
    # reserve a port that is NOT yet listening
    probe = _socketlib.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()

    def late_server():
        time.sleep(0.5)
        srv = _socketlib.socket()
        srv.setsockopt(_socketlib.SOL_SOCKET, _socketlib.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", port))
        srv.listen(1)
        conn, _ = srv.accept()
        conn.close()
        srv.close()

    th = threading.Thread(target=late_server, daemon=True)
    th.start()
    t0 = time.monotonic()
    sock = retry_connect(
        lambda: _socketlib.create_connection(("127.0.0.1", port),
                                             timeout=5.0))
    sock.close()
    th.join(5.0)
    assert time.monotonic() - t0 >= 0.3  # it actually waited out a refusal

    # a zero budget restores first-failure raise
    probe = _socketlib.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()
    with pytest.raises(ConnectionRefusedError):
        retry_connect(
            lambda: _socketlib.create_connection(("127.0.0.1", dead_port),
                                                 timeout=5.0),
            timeout_s=0.0)


def test_server_client_connects_to_delayed_server():
    """satellite: mpi_tpu.connect()/ServerClient survive the
    server-still-binding race instead of first-failure raising."""
    from mpi_tpu import serve

    probe = _socketlib.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    started = []

    def late_pool():
        time.sleep(0.6)
        srv = serve.WorldServer(pool_size=2, backend="socket",
                                port=port, detect_timeout_s=2.0,
                                heartbeat_s=0.2)
        srv.start()
        started.append(srv)

    th = threading.Thread(target=late_pool, daemon=True)
    th.start()
    try:
        client = serve.connect(("127.0.0.1", port))
        got = client.run(serve.job_allreduce, 64, nranks=2, timeout=30.0)
        assert got == 3.0
        client.close()
    finally:
        th.join(10.0)
        for srv in started:
            srv.stop()


# -- serve: leases ride healed links (satellite) ------------------------------


def test_serve_lease_rides_healed_links():
    from mpi_tpu import serve

    ses = mpit.session_create()
    ses.reset_all()
    with serve.WorldServer(pool_size=2, backend="socket",
                           detect_timeout_s=2.0, heartbeat_s=0.2,
                           world_lease_timeout_s=30.0) as srv:
        client = serve.connect(srv)
        got = client.run(serve.job_allreduce_link_chaos, 256, 2,
                         nranks=2, timeout=30.0)
        assert got == 3.0
        # and the pool is still healthy for a plain lease afterwards
        assert client.run(serve.job_allreduce, 64, nranks=2,
                          timeout=30.0) == 3.0
        client.close()


# -- refcounted buffer ownership (ISSUE 11: mpi_tpu/bufpool.py) ---------------


def test_bufref_touch_snapshots_before_mutation():
    """Copy-on-write unit contract: touch() snapshots every overlapping
    retained ref BEFORE the caller's write lands, exactly once, priced
    by the cow pvars (never payload_copies)."""
    from mpi_tpu import bufpool

    ses = mpit.session_create()
    ses.reset_all()
    arr = np.arange(64, dtype=np.float64)
    ref = bufpool.BufRef([b"head", arr])
    assert bufpool.live_refs() == 1
    want = ref.tobytes()
    assert bufpool.touch(arr) == 1        # snapshot BEFORE the write
    arr[:] = -1.0
    assert ref.tobytes() == want          # a replay stays bit-exact
    assert bufpool.touch(arr) == 0        # second write: nothing to do
    assert bufpool.live_refs() == 0       # snapshotted refs leave the index
    assert ses.read("link_cow_snapshots") == 1
    assert ses.read("link_cow_bytes") == len(want)
    assert ses.read("payload_copies") == 0  # the decoupling
    ref.release()
    assert ref.tobytes() == b""


def test_bufref_pin_defers_release_and_skips_replay():
    from mpi_tpu import bufpool

    arr = np.ones(8, np.uint8)
    ref = bufpool.BufRef([arr])
    views = ref.pin()
    assert views and views[0].nbytes == 8
    ref.release()               # acked while a replay streams the views
    assert ref.pin() is None    # later replays skip the frame (dedup'd)
    ref.unpin()                 # the last pin actually frees
    assert ref.tobytes() == b""
    assert bufpool.live_refs() == 0


def test_retention_by_reference_zero_copies_on_no_reuse():
    """The ISSUE 11 decoupling: a no-reuse send stream retains every
    frame (link_bytes_retained prices the replay bound) with ZERO
    retention-attributed copies — no cow snapshots, payload_copies
    untouched by retention."""
    ses = mpit.session_create()
    ses.reset_all()

    def prog(comm):
        out = None
        if comm.rank == 0:
            for i in range(6):
                comm.send(np.full(2048, float(i)), dest=1, tag=3)
            out = (ses.read("link_bytes_retained"),
                   ses.read("link_cow_snapshots"),
                   ses.read("link_cow_bytes"))
        else:
            for i in range(6):
                got = comm.recv(source=0, tag=3)
                assert float(got[0]) == float(i)
        comm.barrier()
        return out

    res = run_socket_world(prog, 2)
    retained, snaps, cow_bytes = res[0]
    assert retained >= 6 * 2048 * 8
    assert snaps == 0 and cow_bytes == 0


def test_buffer_reuse_under_resets_is_bit_exact_with_cow():
    """The chaos leg the ISSUE names: ONE buffer reused across sends
    (note_write per the borrow contract before each off-op mutation)
    while link_reset_every tears connections — every replay must be
    bit-exact against the content AT SEND TIME, and the cow pvars must
    show reuse actually forced snapshots."""
    from mpi_tpu import bufpool

    ses = mpit.session_create()
    ses.reset_all()
    base = np.arange(4096.0)

    def prog(comm):
        inj = None
        if comm.rank == 0:
            inj = FaultyTransport(comm._t, link_reset_every=3)
            buf = np.empty(4096, np.float64)
            for i in range(10):
                bufpool.note_write(buf)   # the documented borrow contract
                buf[:] = base + float(i)
                comm.send(buf, dest=1, tag=5)
        else:
            for i in range(10):
                got = comm.recv(source=0, tag=5)
                assert np.array_equal(got, base + float(i)), i
        comm.barrier()
        return 0 if inj is None else inj.link_resets

    res = run_socket_world(prog, 2, timeout=90)
    assert res[0] >= 2                            # resets really fired
    assert ses.read("link_reconnects") >= res[0]  # ... and healed
    assert ses.read("link_cow_snapshots") >= 1    # reuse forced copies


def test_sendmsg_batches_whole_frame_into_one_syscall():
    """Vectored sends: a multi-segment raw frame (header + meta + 6
    segment bodies = 8 wire parts) goes out in ONE sendmsg syscall —
    the fewer-syscalls-per-frame acceptance, counter-asserted via the
    link_send_syscalls pvar."""
    ses = mpit.session_create()
    ses.reset_all()
    segs = [np.arange(256.0) + i for i in range(6)]

    def prog(comm):
        out = None
        if comm.rank == 0:
            before = mpit.pvar_read("link_send_syscalls")
            for i in range(4):
                comm.send([s * (i + 1) for s in segs], dest=1, tag=2)
            out = mpit.pvar_read("link_send_syscalls") - before
        else:
            for i in range(4):
                got = comm.recv(source=0, tag=2)
                assert len(got) == 6
                assert np.array_equal(got[0], segs[0] * (i + 1))
        comm.barrier()
        return out

    res = run_socket_world(prog, 2)
    assert res[0] == 4, f"expected 1 syscall per frame, saw {res[0]}/4"


# -- idle-link keepalive (ISSUE 11 satellite: PR-10 residual (b)) -------------


def test_idle_link_keepalive_heals_before_next_send():
    """A link torn while IDLE (the remote endpoint hard-reset, as a
    SIGKILL of the peer's old incarnation would) is discovered and
    healed by the keepalive probe — link_reconnects ticks with NO send
    in flight — so the next real send finds a live link instead of
    paying the reconnect spike."""
    import struct as _struct

    ses = mpit.session_create()
    ses.reset_all()
    old = mpit.cvar_read("link_keepalive_s")
    mpit.cvar_write("link_keepalive_s", 0.25)
    sent = threading.Event()
    torn = threading.Event()
    try:
        def prog(comm):
            if comm.rank == 0:
                comm.send(np.arange(32.0), dest=1, tag=1)
                sent.set()
                assert torn.wait(10.0)
                deadline = time.monotonic() + 8.0
                while (ses.read("link_reconnects") < 1
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                healed_first = ses.read("link_reconnects") >= 1
                comm.send(np.arange(64.0), dest=1, tag=2)
                return healed_first
            got = comm.recv(source=0, tag=1)
            assert np.array_equal(got, np.arange(32.0))
            sent.wait(10.0)
            # hard-reset the REMOTE END of rank 0's (now idle) link:
            # rank 0's cached connection is a corpse from here on
            with comm._t._conn_lock:
                conns = list(comm._t._reader_conns.get(0, []))
            for c in conns:
                try:
                    c.setsockopt(_socketlib.SOL_SOCKET,
                                 _socketlib.SO_LINGER,
                                 _struct.pack("ii", 1, 0))
                    c.close()
                except OSError:
                    pass
            torn.set()
            got2 = comm.recv(source=0, tag=2)
            assert np.array_equal(got2, np.arange(64.0))
            return True

        res = run_socket_world(prog, 2, timeout=60)
        assert res[0], "the idle probe never healed the torn link"
    finally:
        mpit.cvar_write("link_keepalive_s", old)
