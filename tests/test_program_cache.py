"""The compiled SPMD programs ``run_spmd`` keeps across calls: a repeated
call runs the kept executable without a trace, a call whose function,
argument types, keyword values, mesh or trace config differ traces anew,
an unhashable keyword keeps nothing, the cache evicts its least recently
used program, and the ``run`` span and the two pvars say which calls
traced.  CPU, 8 virtual devices (conftest)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mpi_tpu
from mpi_tpu import mpit, telemetry
from mpi_tpu.tpu import runner

STAGES = {"trace", "lower", "compile"}


@pytest.fixture(autouse=True)
def _no_recorder(monkeypatch):
    monkeypatch.delenv("MPI_TPU_TRACE", raising=False)
    monkeypatch.setattr(telemetry, "REC", None)
    monkeypatch.setattr(telemetry, "_LAST", None)
    yield
    telemetry.disable()


@pytest.fixture
def ses():
    s = mpit.session_create()
    s.reset_all()
    return s


def _prog(comm, x, s, k=1):
    return comm.allreduce(x * s * (comm.rank + k)) + comm.shift(x, offset=1)


def _prog_copy(comm, x, s, k=1):
    return comm.allreduce(x * s * (comm.rank + k)) + comm.shift(x, offset=1)


X = jnp.arange(16.0)
S = jnp.float32(2.0)


def _run(fn=_prog, x=X, s=S, nranks=4, k=1, **kw):
    return jax.block_until_ready(
        mpi_tpu.run(fn, x, s, backend="tpu", nranks=nranks, k=k, **kw))


def _on_device_1():
    """``X``, uncommitted, on device 1: the same type, another placement."""
    with jax.default_device(jax.devices()[1]):
        return jnp.arange(16.0)


def _counts(ses):
    return ses.read("tpu_program_traces"), ses.read("tpu_program_reuses")


def test_repeated_call_reuses_the_program(ses):
    first = _run()
    for _ in range(3):
        assert np.array_equal(_run(), first)
    assert _counts(ses) == (1, 3)
    assert len(runner._PROGRAMS) == 1


def test_hit_skips_the_python_body(ses):
    """As on a ``jax.jit`` cache hit, ``fn`` runs on a trace only."""
    seen = []

    def prog(comm, x):
        seen.append(comm.size)
        return comm.allreduce(x)

    for _ in range(3):
        jax.block_until_ready(mpi_tpu.run(prog, X, backend="tpu", nranks=2))
    assert seen == [2]
    assert _counts(ses) == (1, 2)


# each: the keyword arguments of a call that differs from _run()'s in one
# part of the key
CHANGED = {
    "shape": lambda: dict(x=jnp.arange(8.0)),
    "dtype": lambda: dict(x=jnp.arange(16, dtype=jnp.int32)),
    "weak_type": lambda: dict(s=2.0),
    "keyword_value": lambda: dict(k=2),
    "keyword_type": lambda: dict(k=1.0),
    "nranks": lambda: dict(nranks=2),
    "mesh": lambda: dict(nranks=None,
                         mesh=Mesh(np.array(jax.devices()[4:8]), ("world",))),
    "function": lambda: dict(fn=_prog_copy),
    "partial": lambda: dict(fn=functools.partial(_prog, k=1)),
    "sharding": lambda: dict(x=jax.device_put(X, NamedSharding(
        Mesh(np.array(jax.devices()[:4]), ("world",)), P()))),
    "device": lambda: dict(x=_on_device_1()),
    "axis_name": lambda: dict(axis_name="ranks"),
    "check_vma": lambda: dict(check_vma=False),
}


@pytest.mark.parametrize("part", sorted(CHANGED) + ["matmul_precision"])
def test_changed_key_traces_anew(ses, part):
    base = _run()
    if part == "matmul_precision":
        with jax.default_matmul_precision("highest"):
            got = _run()
    else:
        got = _run(**CHANGED[part]())
    assert _counts(ses) == (2, 0)
    assert len(runner._PROGRAMS) == 2
    if part in ("keyword_type", "function", "partial", "sharding", "device",
                "matmul_precision", "check_vma"):
        assert np.array_equal(np.asarray(got), np.asarray(base))
    # the first program is still kept
    assert np.array_equal(_run(), base)
    assert _counts(ses) == (2, 1)


@pytest.mark.parametrize("k", [[1, 2], np.array([1, 2])], ids=["list", "array"])
def test_unhashable_keyword_traces_every_call(ses, k):
    def prog(comm, x, k):
        return comm.allreduce(x * k[1])

    outs = [np.asarray(mpi_tpu.run(prog, X, backend="tpu", nranks=4, k=k))
            for _ in range(3)]
    assert _counts(ses) == (3, 0)
    assert len(runner._PROGRAMS) == 0
    assert all(np.array_equal(o, outs[0]) for o in outs)


def test_jit_false_keeps_nothing(ses):
    want = np.asarray(_run())
    runner.clear_programs()
    ses.reset_all()
    for _ in range(2):
        assert np.array_equal(np.asarray(_run(jit=False)), want)
    assert ses.read("tpu_program_reuses") == 0
    assert ses.read("tpu_program_traces") >= 2
    assert len(runner._PROGRAMS) == 0


def test_hit_bit_equal_to_a_fresh_trace():
    x = jax.random.normal(jax.random.key(3), (4, 64), jnp.float32)

    def prog(comm, x):
        y = jnp.tanh(x @ x.T) / 3.0
        return comm.allreduce(y) + comm.shift(jnp.cumsum(y, 0), offset=1)

    def call():
        return np.asarray(mpi_tpu.run(prog, x, backend="tpu", nranks=4))

    fresh = call()
    hit = call()
    runner.clear_programs()
    retraced = call()
    assert np.array_equal(hit, fresh) and np.array_equal(retraced, fresh)


def test_least_recently_used_evicted(ses, monkeypatch):
    monkeypatch.setattr(runner, "_PROGRAMS_MAX", 2)
    a, b, c = (dict(x=jnp.arange(float(n))) for n in (8, 16, 32))
    _run(**a)
    _run(**b)
    _run(**a)  # a hit: b is now the least recently used
    _run(**c)  # evicts b
    assert _counts(ses) == (3, 1)
    assert len(runner._PROGRAMS) == 2
    _run(**a)  # kept
    _run(**b)  # evicted, traced anew (evicting c)
    _run(**c)
    assert _counts(ses) == (5, 2)


def test_every_call_has_a_run_span_only_misses_stages(ses):
    telemetry.enable()
    shapes = (8, 8, 16, 8, 16, 16)
    for n in shapes:
        _run(x=jnp.arange(float(n)))
    children = {}
    rec = telemetry.recorder()
    for e in rec.find("tpu"):
        if "parent" in e["attrs"]:
            children.setdefault(e["attrs"]["parent"], set()).add(e["name"])
    runs = rec.find("tpu", "run")
    assert len(runs) == len(shapes)
    stages = [children.get(r["attrs"]["seq"], set()) for r in runs]
    assert stages == [STAGES, set(), STAGES, set(), set(), set()]


def test_traces_plus_reuses_equal_calls(ses):
    calls = [dict(), dict(), dict(k=2), dict(), dict(k=2),
             dict(x=jnp.arange(4.0)), dict(nranks=2), dict(nranks=2)]
    for kw in calls:
        _run(**kw)
    jax.block_until_ready(mpi_tpu.run(
        lambda c, x, k: c.allreduce(x * k[0]), X, backend="tpu", nranks=4,
        k=[3]))
    traces, reuses = _counts(ses)
    assert (traces, reuses) == (5, 4)
    assert traces + reuses == len(calls) + 1


def test_clear_programs_drops_every_program(ses):
    _run()
    _run(nranks=2)
    assert len(runner._PROGRAMS) == 2
    runner.clear_programs()
    assert len(runner._PROGRAMS) == 0
    _run()
    assert _counts(ses) == (3, 0)
