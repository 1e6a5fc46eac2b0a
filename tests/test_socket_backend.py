"""Socket/pickle transport tests (SURVEY.md §2 component #2; §4: loopback TCP
makes every test 'multi-node' in the sense that matters to a socket
transport).  Fast paths run the real socket stack in threads within one
process; one end-to-end test goes through the launcher with real rank
processes (component #1)."""

import os
import subprocess
import sys
import tempfile
import textwrap
import threading

import numpy as np
import pytest

from mpi_tpu import ops
from mpi_tpu.communicator import P2PCommunicator
from mpi_tpu.transport.socket import SocketTransport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_socket_world(fn, nranks, timeout=60.0):
    """Run fn(comm) on nranks socket transports living in threads (real TCP)."""
    rdv = tempfile.mkdtemp(prefix="mpi_tpu_test_rdv_")
    results = [None] * nranks
    errors = []
    transports = [None] * nranks

    def runner(r):
        try:
            t = SocketTransport(r, nranks, rdv)
            transports[r] = t
            comm = P2PCommunicator(t, range(nranks))
            results[r] = fn(comm)
        except BaseException as e:  # noqa: BLE001
            import traceback

            errors.append((r, e, traceback.format_exc()))

    threads = [threading.Thread(target=runner, args=(r,), daemon=True) for r in range(nranks)]
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


def test_socket_p2p_roundtrip():
    def prog(comm):
        if comm.rank == 0:
            comm.send(np.arange(1000), dest=1, tag=3)
            return comm.recv(source=1, tag=4)
        got = comm.recv(source=0, tag=3)
        comm.send(got.sum(), dest=0, tag=4)
        return None

    res = run_socket_world(prog, 2)
    assert res[0] == np.arange(1000).sum()


def test_socket_large_message_framing():
    big = np.random.RandomState(0).bytes(3 * 1024 * 1024)  # multi-frame sendall

    def prog(comm):
        if comm.rank == 0:
            comm.send(big, dest=1)
            return None
        return comm.recv(source=0)

    res = run_socket_world(prog, 2)
    assert res[1] == big


def test_socket_self_send():
    def prog(comm):
        comm.send("to-myself", dest=comm.rank, tag=1)
        return comm.recv(source=comm.rank, tag=1)

    assert run_socket_world(prog, 2) == ["to-myself", "to-myself"]


@pytest.mark.parametrize("algo", ["ring", "recursive_halving"])
def test_socket_allreduce(algo):
    data = np.random.RandomState(1).randn(4, 50)

    def prog(comm):
        return comm.allreduce(data[comm.rank], op=ops.SUM, algorithm=algo)

    for got in run_socket_world(prog, 4):
        np.testing.assert_allclose(got, data.sum(axis=0), rtol=1e-10)


def test_socket_bcast_alltoall_barrier():
    def prog(comm):
        v = comm.bcast("payload" if comm.rank == 0 else None, root=0)
        blocks = comm.alltoall([(comm.rank, d) for d in range(comm.size)])
        comm.barrier()
        return v, blocks

    res = run_socket_world(prog, 3)
    for dst, (v, blocks) in enumerate(res):
        assert v == "payload"
        assert blocks == [(src, dst) for src in range(3)]


def test_socket_split():
    def prog(comm):
        sub = comm.split(color=comm.rank % 2, key=comm.rank)
        return sub.allreduce(comm.rank)

    res = run_socket_world(prog, 4)
    assert res == [2, 4, 2, 4]


@pytest.mark.slow
def test_launcher_end_to_end(tmp_path):
    """Full L0 path: launcher spawns real rank processes; ranks talk over
    loopback TCP and write their allreduce result to files."""
    script = tmp_path / "prog.py"
    out = tmp_path / "out"
    out.mkdir()
    script.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import numpy as np
        import mpi_tpu

        comm = mpi_tpu.init()
        got = comm.allreduce(np.full(10, comm.rank + 1.0))
        (rank_total := got.sum())
        with open({str(out)!r} + f"/rank{{comm.rank}}.txt", "w") as f:
            f.write(str(float(rank_total)))
        mpi_tpu.finalize()
    """))
    from mpi_tpu.launcher import launch

    rc = launch(3, [str(script)], timeout=90.0)
    assert rc == 0
    expect = 10 * (1.0 + 2.0 + 3.0)
    for r in range(3):
        assert float((out / f"rank{r}.txt").read_text()) == expect


@pytest.mark.slow
def test_launcher_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(7)\n")
    from mpi_tpu.launcher import launch

    assert launch(2, [str(script)], timeout=60.0) == 7


def test_launcher_refuses_ranks_sharing_a_chip(tmp_path, monkeypatch):
    """A chip belongs to one process: asking for the accelerator platform
    with more than one rank raises before anything is spawned."""
    import subprocess

    from mpi_tpu.launcher import cpu_pinned_env, launch

    env = cpu_pinned_env({"MPI_TPU_RANK_JAX_PLATFORMS": "tpu"}, nprocs=1)
    assert env == {"JAX_PLATFORMS": "tpu"}
    assert cpu_pinned_env({}, nprocs=4) == {"JAX_PLATFORMS": "cpu"}
    spawned = []
    monkeypatch.setattr(subprocess, "Popen",
                        lambda *a, **k: spawned.append(a))
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        launch(2, [str(tmp_path / "x.py")],
               env_extra={"MPI_TPU_RANK_JAX_PLATFORMS": "tpu"})
    assert spawned == []
