"""Native shared-memory transport tests (mpi_tpu/native/shmring.cpp +
mpi_tpu/transport/shm.py): the C++ SPSC ring itself, the transport over it
(real shm segments, transports living in threads), and one launcher-spawned
multi-process end-to-end run."""

import ctypes
import os
import struct
import tempfile
import textwrap
import threading

import numpy as np
import pytest

from mpi_tpu import ops
from mpi_tpu.communicator import P2PCommunicator
from mpi_tpu.native import load_shmring
from mpi_tpu.transport.shm import ShmTransport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the native ring itself ------------------------------------------------


def test_ring_roundtrip_small():
    lib = load_shmring()
    name = b"/mt_test_ring_rt"
    ring_c = lib.shmring_create(name, 4096)
    assert ring_c
    ring_p = lib.shmring_open(name, 5.0)
    assert ring_p
    msg = b"hello, ring"
    assert lib.shmring_write(ring_p, msg, len(msg), 5.0) == 0
    assert lib.shmring_avail(ring_c) == len(msg)
    buf = ctypes.create_string_buffer(len(msg))
    assert lib.shmring_read(ring_c, buf, len(msg), 5.0) == 0
    assert buf.raw == msg
    lib.shmring_close(ring_p)
    lib.shmring_close(ring_c)
    lib.shmring_unlink(name)


def test_ring_streams_frames_larger_than_capacity():
    """A frame bigger than the ring must stream through (writer and reader
    chunk concurrently) — the no-deadlock property the transport relies on."""
    lib = load_shmring()
    name = b"/mt_test_ring_big"
    cap = 64 * 1024
    ring_c = lib.shmring_create(name, cap)
    ring_p = lib.shmring_open(name, 5.0)
    payload = np.random.RandomState(0).bytes(cap * 4 + 12345)
    out = ctypes.create_string_buffer(len(payload))
    err = []

    def reader():
        if lib.shmring_read(ring_c, out, len(payload), 30.0) != 0:
            err.append("read timeout")

    t = threading.Thread(target=reader)
    t.start()
    assert lib.shmring_write(ring_p, payload, len(payload), 30.0) == 0
    t.join(30.0)
    assert not err and not t.is_alive()
    assert out.raw == payload
    lib.shmring_close(ring_p)
    lib.shmring_close(ring_c)
    lib.shmring_unlink(name)


def test_ring_write_timeout_when_full():
    lib = load_shmring()
    name = b"/mt_test_ring_full"
    ring_c = lib.shmring_create(name, 1024)
    ring_p = lib.shmring_open(name, 5.0)
    data = bytes(1024)
    assert lib.shmring_write(ring_p, data, len(data), 5.0) == 0  # fills it
    assert lib.shmring_write(ring_p, b"x", 1, 0.2) == -1  # nobody drains
    lib.shmring_close(ring_p)
    lib.shmring_close(ring_c)
    lib.shmring_unlink(name)


# -- the transport over real shm segments ----------------------------------


def run_shm_world(fn, nranks, timeout=60.0):
    """Run fn(comm) on nranks ShmTransports living in threads (real shm)."""
    rdv = tempfile.mkdtemp(prefix="mpi_tpu_shm_test_")
    results = [None] * nranks
    errors = []
    transports = [None] * nranks

    def runner(r):
        try:
            t = ShmTransport(r, nranks, rdv, ring_bytes=256 * 1024)
            transports[r] = t
            comm = P2PCommunicator(t, range(nranks))
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
        raise TimeoutError(f"shm ranks did not finish: {alive}")
    return results


def test_shm_p2p_roundtrip():
    def prog(comm):
        if comm.rank == 0:
            comm.send(np.arange(1000), dest=1, tag=3)
            return comm.recv(source=1, tag=4)
        got = comm.recv(source=0, tag=3)
        comm.send(got.sum(), dest=0, tag=4)
        return None

    res = run_shm_world(prog, 2)
    assert res[0] == np.arange(1000).sum()


def test_shm_large_message_through_small_ring():
    big = np.random.RandomState(0).bytes(3 * 1024 * 1024)  # 12x the test ring

    def prog(comm):
        if comm.rank == 0:
            comm.send(big, dest=1)
            return None
        return comm.recv(source=0)

    res = run_shm_world(prog, 2)
    assert res[1] == big


def test_shm_self_send():
    def prog(comm):
        comm.send("to-myself", dest=comm.rank, tag=1)
        return comm.recv(source=comm.rank, tag=1)

    assert run_shm_world(prog, 2) == ["to-myself", "to-myself"]


@pytest.mark.parametrize("algo", ["ring", "recursive_halving"])
def test_shm_allreduce(algo):
    data = np.random.RandomState(1).randn(4, 50)

    def prog(comm):
        return comm.allreduce(data[comm.rank], op=ops.SUM, algorithm=algo)

    for got in run_shm_world(prog, 4):
        np.testing.assert_allclose(got, data.sum(axis=0), rtol=1e-10)


def test_shm_split_and_rma():
    def prog(comm):
        sub = comm.split(color=comm.rank % 2, key=comm.rank)
        win = comm.win_create(np.zeros(1))
        if comm.rank != 0:
            win.accumulate(np.array([1.0]), 0)
        win.fence()
        return sub.allreduce(comm.rank), float(win.local[0])

    res = run_shm_world(prog, 4)
    assert [r[0] for r in res] == [2, 4, 2, 4]
    assert [r[1] for r in res] == [3.0, 0.0, 0.0, 0.0]


def test_shm_segments_cleaned_up():
    rdv = tempfile.mkdtemp(prefix="mpi_tpu_shm_gc_")
    session = os.path.basename(rdv)
    t0 = ShmTransport(0, 2, rdv, ring_bytes=64 * 1024)
    t1 = ShmTransport(1, 2, rdv, ring_bytes=64 * 1024)
    # 2 directed rings + 2 doorbells
    assert len([f for f in os.listdir("/dev/shm")
                if f.startswith(f"mt_{session}_")]) == 4
    t0.close()
    t1.close()
    assert not [f for f in os.listdir("/dev/shm")
                if f.startswith(f"mt_{session}_")]


@pytest.mark.slow
def test_shm_launcher_end_to_end(tmp_path):
    """Full L0 path over the native data plane: real rank processes, shm
    rings between them."""
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
        with open({str(out)!r} + f"/rank{{comm.rank}}.txt", "w") as f:
            f.write(str(float(got.sum())))
        mpi_tpu.finalize()
    """))
    from mpi_tpu.launcher import launch

    rc = launch(3, [str(script)], timeout=90.0, backend="shm")
    assert rc == 0
    expect = 10 * (1.0 + 2.0 + 3.0)
    for r in range(3):
        assert float((out / f"rank{r}.txt").read_text()) == expect


def test_shm_symmetric_big_sendrecv_no_deadlock():
    """Regression: both ranks sendrecv frames bigger than the ring's free
    space at once.  Without a dedicated drainer (the buffered-send
    invariant of communicator.py), both would block in their sends."""
    big = np.arange(300_000, dtype=np.float64)  # ~2.4MB through 256KB rings

    def prog(comm):
        peer = 1 - comm.rank
        got = comm.sendrecv(big * (comm.rank + 1), peer)
        return float(got[-1])

    res = run_shm_world(prog, 2, timeout=60.0)
    assert res[0] == big[-1] * 2 and res[1] == big[-1]


def test_shm_random_frame_sizes_roundtrip():
    """Frame sizes straddling the tiny-concat threshold, the ring capacity,
    and multiples thereof all roundtrip bit-exactly (framing property)."""
    rng = np.random.RandomState(7)
    sizes = [1, 100, 8191, 8192, 8193, 100_000, 256 * 1024 - 8,
             256 * 1024, 256 * 1024 + 1, 700_000]
    payloads = [rng.bytes(s) for s in sizes]

    def prog(comm):
        if comm.rank == 0:
            for p in payloads:
                comm.send(p, dest=1)
            ok = comm.recv(source=1)
            return ok
        got = [comm.recv(source=0) for _ in payloads]
        comm.send(all(g == p for g, p in zip(got, payloads)), dest=0)
        return True

    res = run_shm_world(prog, 2)
    assert res[0] is True


def test_native_build_is_keyed_on_source_hash(tmp_path, monkeypatch):
    """A cached .so is reused only when its key (hash of source + build
    command) matches; a stale .so with a newer mtime is rebuilt."""
    import shutil

    from mpi_tpu.native import build

    src = tmp_path / "shmring.cpp"
    shutil.copy(build._SRC, src)
    monkeypatch.setattr(build, "_DIR", str(tmp_path))
    monkeypatch.setattr(build, "_SRC", str(src))
    monkeypatch.setattr(build, "_SO", str(tmp_path / "_shmring.so"))
    so = build.ensure_built()
    key = (tmp_path / "_shmring.so.key").read_text()
    assert so == str(tmp_path / "_shmring.so") and key == build._key("")
    # a foreign .so, newer than the source, without a matching key
    (tmp_path / "_shmring.so").write_bytes(b"stale")
    (tmp_path / "_shmring.so.key").write_text("0" * 64)
    build.ensure_built()
    assert (tmp_path / "_shmring.so").read_bytes() != b"stale"
    # an unchanged source and key: no rebuild
    mtime = os.path.getmtime(so)
    build.ensure_built()
    assert os.path.getmtime(so) == mtime
    # a source edit changes the key
    src.write_text(src.read_text() + "\n// edit\n")
    assert build._key("") != key
