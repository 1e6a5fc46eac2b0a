"""Host spans of the TPU entry (``run_spmd``: ``run`` → ``trace``,
``lower``, ``compile``), their two switches (the flight recorder and a
JAX profiler session), their clock (the profiler trace's), the always-on
``tpu_program_traces`` and ``tpu_program_reuses`` pvars, and the names
the program gives its collectives on the device side.  CPU, 8 virtual
devices (conftest)."""

import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mpi_tpu
from mpi_tpu import mpit, ops, telemetry
from mpi_tpu.tpu import runner

STAGES = ("trace", "lower", "compile")


@pytest.fixture(autouse=True)
def _no_recorder(monkeypatch):
    """Each test starts with no recorder, active or kept, and no
    ``MPI_TPU_TRACE``; none leaks into the rest of the run."""
    monkeypatch.delenv("MPI_TPU_TRACE", raising=False)
    monkeypatch.setattr(telemetry, "REC", None)
    monkeypatch.setattr(telemetry, "_LAST", None)
    yield
    telemetry.disable()


def _prog(comm, x):
    return comm.allreduce(x * (comm.rank + 1)) + comm.shift(x, offset=1)


def _run(x=None, **kw):
    x = jnp.arange(16.0) if x is None else x
    return mpi_tpu.run(_prog, x, backend="tpu", nranks=4, **kw)


def _window(rec):
    """→ [(run event, {stage: child event})] of every ``run`` span."""
    children = {}
    for e in rec.find("tpu"):
        if "parent" in e["attrs"]:
            children.setdefault(e["attrs"]["parent"], {})[e["name"]] = e
    return [(r, children.get(r["attrs"]["seq"], {}))
            for r in rec.find("tpu", "run")]


def _profiled(tmp_path, calls=1, fresh=False):
    """Run ``calls`` programs under a profiler session → its ProfileData.
    ``fresh``: the kept program is dropped, so the first call traces."""
    from jax.profiler import ProfileData

    _run()  # compile outside the session: what is traced is the entry
    if fresh:
        runner.clear_programs()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(calls):
            jax.block_until_ready(_run())
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return ProfileData.from_file(path[-1])


def _host_events(pd, name):
    return [ev for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events if ev.name == name]


def test_off_no_event_no_recorder():
    """Neither switch on: the entry emits nothing and creates nothing."""
    before = mpit.pvar_read("trace_events")
    jax.block_until_ready(_run())
    assert telemetry.REC is None and telemetry.recorder() is None
    assert mpit.pvar_read("trace_events") == before
    # one shared no-op context, whatever the span
    assert telemetry.span("run", seq=1) is telemetry.span("compile")


def test_recorder_run_spans_with_stage_children():
    telemetry.enable()
    ses = mpit.session_create()
    ses.reset_all()
    for _ in range(3):
        jax.block_until_ready(_run())
    runs = _window(telemetry.recorder())
    assert len(runs) == 3
    seqs = [r["attrs"]["seq"] for r, _ in runs]
    assert seqs == sorted(set(seqs))  # a per-process counter
    assert all(run["attrs"]["nranks"] == 4 for run, _ in runs)
    (run, kids), reused = runs[0], runs[1:]
    assert set(kids) == set(STAGES)
    t0, t1 = run["t_ns"], run["t_ns"] + run["dur_ns"]
    for k in kids.values():
        assert k["attrs"]["parent"] == run["attrs"]["seq"]
        assert t0 <= k["t_ns"] and k["t_ns"] + k["dur_ns"] <= t1
    order = [kids[s]["t_ns"] for s in STAGES]
    assert order == sorted(order)
    # the later calls run the kept program: a run span with no stages
    assert all(kids == {} for _, kids in reused)
    # one trace, then two reuses, counted whether or not traced
    assert ses.read("tpu_program_traces") == 1
    assert ses.read("tpu_program_reuses") == 2


def test_traces_counted_with_tracing_off():
    ses = mpit.session_create()
    ses.reset_all()
    for _ in range(2):
        jax.block_until_ready(_run())
    assert ses.read("tpu_program_traces") == 1
    assert ses.read("tpu_program_reuses") == 1
    assert telemetry.REC is None


def test_jit_false_gets_only_the_run_span():
    telemetry.enable()
    jax.block_until_ready(_run(jit=False))
    [(run, kids)] = _window(telemetry.recorder())
    assert kids == {} and run["attrs"]["nranks"] == 4


def test_env_switch_is_read_at_the_first_call(monkeypatch):
    from mpi_tpu.tpu import runner

    monkeypatch.setenv("MPI_TPU_TRACE", "1")
    runner._trace_from_env.cache_clear()
    try:
        jax.block_until_ready(_run())
        assert telemetry.REC is not None
        assert len(telemetry.REC.find("tpu", "run")) == 1
    finally:
        runner._trace_from_env.cache_clear()


def test_profiler_session_alone_turns_spans_on(tmp_path):
    """A profiler session with no recorder: the first span starts one, and
    the spans also lie on the trace's host plane: a run span for each
    call, stages for the one that traced."""
    pd = _profiled(tmp_path, calls=2, fresh=True)
    rec = telemetry.recorder()
    assert rec is not None
    runs = _window(rec)
    assert [set(k) for _, k in runs] == [set(STAGES), set()]
    assert len(_host_events(pd, "mpi_tpu.run")) == 2
    for name in ("mpi_tpu." + s for s in STAGES):
        assert len(_host_events(pd, name)) == 1, name


def test_recorder_and_profiler_share_a_clock(tmp_path):
    """The recorder's ``run`` start and the trace's ``mpi_tpu.run`` start
    (the session's ``profile_start_time`` plus the event's offset) agree
    within 100 µs."""
    pd = _profiled(tmp_path, calls=3)
    env = [p for p in pd.planes if p.name == "Task Environment"]
    assert env, [p.name for p in pd.planes]
    start = dict(env[0].stats)["profile_start_time"]
    traced = sorted(ev.start_ns for ev in _host_events(pd, "mpi_tpu.run"))
    recorded = sorted(r["t_ns"] for r, _ in _window(telemetry.recorder()))
    assert len(traced) == len(recorded) == 3
    for t, r in zip(traced, recorded):
        assert abs(start + t - r) < 100_000, (start + t - r)


def test_results_identical_with_tracing_on_and_off(tmp_path):
    x = jax.random.normal(jax.random.key(7), (64,), jnp.float32)
    off = np.asarray(_run(x))
    telemetry.enable()
    on = np.asarray(_run(x))
    assert telemetry.REC.find("tpu", "run")
    assert np.array_equal(off, on)


def test_chrome_export_renders_spans(tmp_path):
    telemetry.enable()
    jax.block_until_ready(_run())
    doc = telemetry.recorder().chrome_trace()
    spans = {e["name"]: e for e in doc["traceEvents"] if e.get("cat") == "tpu"}
    assert set(spans) == {"run"} | set(STAGES)
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in spans.values())


# (collective call, the scope it must carry)
SCOPED = {
    "allreduce_fused": (lambda c, x: c.allreduce(x), "mpi_tpu.allreduce.fused"),
    "allreduce_ring": (lambda c, x: c.allreduce(x, algorithm="ring"),
                       "mpi_tpu.allreduce.ring"),
    "allreduce_max": (lambda c, x: c.allreduce(x, op=ops.MAX),
                      "mpi_tpu.allreduce.fused"),
    "bcast": (lambda c, x: c.bcast(x, root=1), "mpi_tpu.bcast.fused"),
    "reduce": (lambda c, x: c.reduce(x), "mpi_tpu.reduce.tree"),
    "allgather": (lambda c, x: c.allgather(x), "mpi_tpu.allgather.fused"),
    "alltoall": (lambda c, x: c.alltoall(x.reshape(4, -1)),
                 "mpi_tpu.alltoall.fused"),
    "reduce_scatter": (lambda c, x: c.reduce_scatter(x.reshape(4, -1)),
                       "mpi_tpu.reduce_scatter.fused"),
    "shift": (lambda c, x: c.shift(x, offset=1), "mpi_tpu.shift.ppermute"),
    "exchange": (lambda c, x: c.exchange(x, [(0, 1), (1, 0)]),
                 "mpi_tpu.exchange.ppermute"),
}


@pytest.mark.parametrize("case", sorted(SCOPED))
def test_collective_named_scope_in_op_metadata(monkeypatch, case):
    """Lowered through ``run_spmd``, each collective's ops carry
    ``mpi_tpu.<op>.<resolved algorithm>`` in their metadata."""
    fn, scope = SCOPED[case]
    lowered = []
    real = jax.stages.Traced.lower

    def spy(self, *a, **k):
        lowered.append(real(self, *a, **k))
        return lowered[-1]

    monkeypatch.setattr(jax.stages.Traced, "lower", spy)
    mpi_tpu.run(fn, jnp.arange(16.0), backend="tpu", nranks=4)
    [low] = lowered
    assert scope in low.as_text(debug_info=True)
