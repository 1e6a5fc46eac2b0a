"""Aux subsystem tests (SURVEY.md §5): comm tracing + matching verification,
fault injection, failure detection via recv timeouts, profiling helpers."""

import numpy as np
import pytest

import mpi_tpu
from mpi_tpu import ops
from mpi_tpu import checker
from mpi_tpu.trace import verify_run
from mpi_tpu.transport.base import RecvTimeout
from mpi_tpu.transport.faulty import FaultyTransport
from mpi_tpu.transport.local import run_local


# -- tracing / matching verification ---------------------------------------


def test_verify_run_clean_program():
    def prog(comm):
        v = comm.bcast("x" if comm.rank == 0 else None, root=0)
        s = comm.allreduce(np.float32(comm.rank))
        comm.barrier()
        return v, float(np.asarray(s))

    results, problems = verify_run(prog, 4)
    assert problems == []
    assert all(r == ("x", 6.0) for r in results)


def test_verify_run_detects_unreceived_send():
    def prog(comm):
        if comm.rank == 0:
            comm.send("orphan", dest=1, tag=7)  # rank 1 never receives

    _, problems = verify_run(prog, 2)
    assert any("never received" in p for p in problems)


def test_verify_run_traces_p2p_pattern():
    def prog(comm):
        right = (comm.rank + 1) % comm.size
        left = (comm.rank - 1) % comm.size
        return comm.sendrecv(comm.rank, dest=right, source=left)

    results, problems = verify_run(prog, 3)
    assert problems == []
    assert results == [2, 0, 1]


# -- fault injection + failure detection -----------------------------------


def test_dropped_message_surfaces_as_recv_timeout():
    def prog(comm):
        if comm.rank == 0:
            comm.send("will-be-dropped", dest=1, tag=0)
        else:
            return comm.recv(source=0, tag=0)

    with pytest.raises(RuntimeError, match="RecvTimeout|timed out"):
        run_local(prog, 2,
                  transport_wrapper=FaultyTransport.wrapper(drop_every=1),
                  recv_timeout=0.3)


def test_delay_injection_does_not_break_semantics():
    def prog(comm):
        return comm.allreduce(np.arange(4.0) + comm.rank, op=ops.SUM,
                              algorithm="ring")

    res = run_local(prog, 3,
                    transport_wrapper=FaultyTransport.wrapper(delay_s=0.002))
    expect = sum(np.arange(4.0) + r for r in range(3))
    for got in res:
        np.testing.assert_allclose(got, expect)


def test_duplicate_injection_detected_by_trace_matcher():
    """Duplicated messages leave unconsumed traffic behind — visible via the
    trace matcher (the sanitizer-style check).  The faulty layer must sit
    ABOVE tracing so the duplicate send is recorded."""
    import threading

    from mpi_tpu import checker
    from mpi_tpu.trace import TracingTransport

    traces = {}
    lock = threading.Lock()

    def wrapper(t):
        tt = TracingTransport(t)
        with lock:
            traces[t.world_rank] = tt
        return FaultyTransport(tt, duplicate_every=1)

    def prog(comm):
        if comm.rank == 0:
            comm.send("dup", dest=1, tag=0)
        else:
            comm.recv(source=0, tag=0)

    run_local(prog, 2, transport_wrapper=wrapper)
    logs = [traces[r].as_match_log() if r in traces else [] for r in range(2)]
    problems = checker.verify_matching(logs)
    assert any("never received" in p for p in problems), problems


def test_recv_timeout_reports_pending_messages():
    def prog(comm):
        if comm.rank == 0:
            comm.send("wrong-tag", dest=1, tag=5)
        else:
            comm.recv(source=0, tag=6)  # never sent

    with pytest.raises(RuntimeError, match="pending"):
        run_local(prog, 2, recv_timeout=0.3)


# -- profiling -------------------------------------------------------------


def test_comm_stats_json():
    from mpi_tpu.profiling import CommStats

    s = CommStats()
    s.record("allreduce", 4096)
    s.record("allreduce", 4096)
    s.record("bcast", 128)
    data = s.to_json()
    assert '"allreduce": 2' in data and '"bcast": 128' in data.replace("'", '"')


def test_verify_matching_flags_out_of_fifo_tag_match():
    """VERDICT r1 weak #6 / r2 weak #5 regression: a specific-tag recv
    whose tag only matches a send BEHIND the channel head must be flagged
    in strict mode (such a program deadlocks on a strict-FIFO channel
    transport), and accepted under envelope semantics."""
    logs = [
        [("send", 1, 1), ("send", 1, 2)],   # rank 0: tag 1 first, then 2
        [("recv", 0, 2), ("recv", 0, 1)],   # rank 1 pulls tag 2 FIRST
    ]
    problems = checker.verify_matching(logs)  # strict_fifo default
    assert any("out-of-FIFO" in p for p in problems), problems
    # MPI envelope semantics: legal, both matched, nothing left over
    assert checker.verify_matching(logs, strict_fifo=False) == []


def test_verify_matching_strict_passes_in_order_tags():
    """Differently-tagged traffic consumed in posted order stays clean."""
    logs = [
        [("send", 1, 1), ("send", 1, 2)],
        [("recv", 0, 1), ("recv", 0, 2)],
    ]
    assert checker.verify_matching(logs) == []
    # wildcards always take the head — clean in strict mode too
    logs = [
        [("send", 1, 7), ("send", 1, 8)],
        [("recv", -1, -1), ("recv", 0, 8)],
    ]
    assert checker.verify_matching(logs) == []


def test_verify_matching_wildcard_prefers_head_across_channels():
    """A wildcard-source recv whose tag matches another channel's HEAD is
    clean in strict mode even if the first candidate channel only matches
    deep in its queue (code-review regression: no false out-of-FIFO)."""
    logs = [
        [("send", 2, 3), ("send", 2, 5)],   # rank 0 -> 2: head tag 3
        [("send", 2, 5)],                   # rank 1 -> 2: head tag 5
        [("recv", -1, 5), ("recv", 0, 3), ("recv", 0, 5)],
    ]
    assert checker.verify_matching(logs) == []


# -- MPI_COMM_SELF + MPI_Get_count (round 3) --------------------------------


def test_comm_self_is_size_one_and_cached():
    import mpi_tpu
    from mpi_tpu import api

    s1 = api.MPI_COMM_SELF()
    assert s1.size == 1 and s1.rank == 0
    assert api.MPI_COMM_SELF() is s1
    assert mpi_tpu.COMM_SELF is s1
    # collectives are identities; p2p to self works
    assert s1.allreduce(5) == 5
    s1.send("x", dest=0, tag=3)
    assert s1.recv(source=0, tag=3) == "x"


def test_get_count_and_elements():
    import numpy as np

    from mpi_tpu import Status, api
    from mpi_tpu import datatypes as dt
    from mpi_tpu.transport.local import run_local

    def prog(comm):
        if comm.rank == 0:
            comm.send(np.zeros(12, np.float64), dest=1)
            comm.send({"opaque": True}, dest=1)
            return None
        st = Status()
        comm.recv(source=0, status=st)
        pair = dt.type_contiguous(2, np.float64).commit()
        counts = (api.MPI_Get_count(st, np.float64),
                  api.MPI_Get_count(st, pair),
                  api.MPI_Get_count(st, np.float32),
                  api.MPI_Get_elements(st, pair))
        st2 = Status()
        comm.recv(source=0, status=st2)
        return counts, api.MPI_Get_count(st2, np.float64)

    res = run_local(prog, 2)
    (n64, npair, n32, nelem), opaque = res[1]
    assert n64 == 12 and npair == 6 and nelem == 12
    assert n32 == 24  # 96 bytes / 4
    assert opaque is None  # pickled dict: MPI_UNDEFINED


def test_comm_split_type_shared():
    from mpi_tpu import api

    def prog(comm):
        node = api.MPI_Comm_split_type(comm=comm)
        assert node.size == comm.size  # single-host worlds: whole comm
        assert node.allreduce(1) == comm.size
        with pytest.raises(ValueError, match="split_type"):
            api.MPI_Comm_split_type("numa", comm=comm)
        return True

    assert all(run_local(prog, 3))


def test_comm_split_type_shared_spmd_by_host(monkeypatch):
    """On the SPMD backend COMM_TYPE_SHARED splits by jax process
    (ADVICE r3 #4): a mesh whose axis spans two hosts yields per-host
    sub-communicators, not the whole comm."""
    from types import SimpleNamespace

    import numpy as np_

    from mpi_tpu.tpu import TpuCommunicator, default_mesh

    mesh = default_mesh(8)
    comm = TpuCommunicator("world", mesh)
    # all-CPU devices are one process: degenerates to the whole comm
    assert comm.split_type().size == 8
    # simulate a 2-host mesh (4 devices per process)
    fake = np_.array([SimpleNamespace(process_index=i // 4, id=i)
                      for i in range(8)])
    monkeypatch.setattr(comm, "mesh",
                        SimpleNamespace(axis_names=("world",), devices=fake,
                                        shape={"world": 8}))
    node = comm.split_type()
    assert node.size == 4
    assert node.axis_index_groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    with pytest.raises(ValueError, match="split_type"):
        comm.split_type("numa")


def test_probe_reports_queued_count_not_stale(tmp_path):
    """probe/iprobe set count_bytes to the QUEUED message's real size
    (ADVICE r4 #2 — the canonical probe+get_count+recv buffer-sizing
    idiom), overwriting any stale count from a prior recv on a reused
    Status (the ADVICE r3 #1 leak stays fixed: the probed count is the
    probed MESSAGE's, never the previous receive's)."""
    import numpy as np_

    import mpi_tpu

    def prog(comm):
        if comm.rank == 0:
            comm.send(np_.zeros(16, np_.float64), 1, tag=5)
            comm.send(np_.zeros(4, np_.float64), 1, tag=6)
            comm.send({"opaque": True}, 1, tag=7)
            return True
        st = mpi_tpu.Status()
        comm.recv(0, tag=5, status=st)
        assert st.count_bytes == 128
        comm.probe(0, tag=6, status=st)
        # the queued tag-6 message's size — NOT the stale 128
        assert st.count_bytes == 32
        assert st.tag == 6
        # iprobe path too
        st2 = mpi_tpu.Status()
        st2.count_bytes = 999
        assert comm.iprobe(0, tag=6, status=st2)
        assert st2.count_bytes == 32
        # probe does not consume; recv agrees with the probed count
        comm.recv(0, tag=6, status=st)
        assert st.count_bytes == 32
        # opaque payloads still probe as MPI_UNDEFINED (None)
        comm.probe(0, tag=7, status=st)
        assert st.count_bytes is None
        comm.recv(0, tag=7)
        return True

    assert all(run_local(prog, 2))


def test_spawn_cleanup_preserves_live_child_world_dirs(tmp_path):
    """The parent's atexit cleanup must not delete a child WORLD's
    rendezvous dir while children still run (ADVICE r3 #3); the bridge
    dir (dead with the parent) always goes."""
    from types import SimpleNamespace

    from mpi_tpu import spawn as sp

    bridge = tmp_path / "bridge"; bridge.mkdir()
    child = tmp_path / "child"; child.mkdir()
    monkeypatch_state = (list(sp._spawned), list(sp._bridge_dirs),
                         list(sp._child_dirs))
    try:
        sp._spawned[:] = [SimpleNamespace(poll=lambda: None)]  # alive
        sp._bridge_dirs[:] = [str(bridge)]
        sp._child_dirs[:] = [str(child)]
        sp._cleanup()
        assert not bridge.exists()   # bridge reaped
        assert child.exists()        # child world preserved
        sp._spawned[:] = [SimpleNamespace(poll=lambda: 0)]  # all exited
        sp._cleanup()
        assert not child.exists()    # now safe to reap
    finally:
        sp._spawned[:], sp._bridge_dirs[:], sp._child_dirs[:] = \
            monkeypatch_state
