"""The readers of the program's own spans: the entry layer's four
(``jacobi.entry_*_ms``, ``jacobi.traces_per_call``) on a recorder filled
by the benchmark's own window of real ``mpi_tpu.run`` jobs (profiler on,
4 virtual CPU devices, tiny sizes), and ``attn.kernel_roofline`` on a
reduced trace; and each one's None where it finds nothing to read."""

import os
import time
from types import SimpleNamespace

import jax
import pytest

import harness
import program_spans
import tiny
from mpi_tpu import telemetry

ENTRY = ("jacobi.entry_trace_ms", "jacobi.entry_lower_ms",
         "jacobi.entry_compile_ms", "jacobi.traces_per_call")


@pytest.fixture(autouse=True)
def _no_recorder(monkeypatch):
    monkeypatch.delenv("MPI_TPU_TRACE", raising=False)
    monkeypatch.setattr(telemetry, "REC", None)
    monkeypatch.setattr(telemetry, "_LAST", None)
    yield
    telemetry.disable()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def bench(root):
    return os.path.join(root, "chipbench")


def _window(root, tmp_path, record_warmup=False):
    """The Jacobi cell's warm-up and a traced window → the metrics' ctx
    (``trace`` stands in for the reduction, which needs TPU planes)."""
    cell = harness.resolve_cell(harness.load_spec(root), "jacobi.f32.n2800",
                                root)
    driver = harness.build_driver(cell, 2**31 + 11, jax.devices())
    if record_warmup:
        telemetry.enable()  # as MPI_TPU_TRACE=1 would, before the warm-up
    harness.warm(driver)
    win = harness.window(driver, 0.3, 5, 2, trace_dir=str(tmp_path))
    return SimpleNamespace(root=cell.root, trace=object(), calls=win.calls,
                           issue_s=win.issue_s), cell


def _read(ctx, name):
    return harness.metric_reader(name, ctx.root).read(ctx)


@pytest.mark.parametrize("record_warmup", [False, True],
                         ids=["profiler_alone", "warmup_recorded"])
def test_entry_readers_read_the_window(root, tmp_path, record_warmup):
    ctx, cell = _window(root, tmp_path, record_warmup)
    assert set(ENTRY) <= {m["name"] for m in cell.per_layer}
    got = {n: _read(ctx, n) for n in ENTRY}
    assert got["jacobi.traces_per_call"] == 1.0
    stages = [got[n] for n in ENTRY[:3]]
    assert all(v > 0 for v in stages)
    # the stages lie inside the benchmark's own span around the call
    run_call_ms = _read(ctx, "jacobi.run_call_ms")
    assert sum(stages) < run_call_ms
    runs = [r for r, _ in program_spans.window_runs(ctx)]
    assert len(runs) == ctx.calls
    run_ms = sum(r["dur_ns"] for r in runs) / ctx.calls / 1e6
    assert sum(stages) <= run_ms <= run_call_ms


def test_entry_readers_none_without_trace(root, tmp_path):
    ctx, _ = _window(root, tmp_path)
    ctx.trace = None
    assert all(_read(ctx, n) is None for n in ENTRY)


def test_entry_readers_none_without_spans(bench):
    """A program without the spans (no recorder, or fewer ``run`` spans
    than calls) gives no reading."""
    ctx = SimpleNamespace(root=bench, trace=object(), calls=3)
    assert all(_read(ctx, n) is None for n in ENTRY)
    rec = telemetry.enable()
    rec.emit("tpu", "run", dur_ns=10, attrs={"seq": 1, "nranks": 1})
    assert all(_read(ctx, n) is None for n in ENTRY)


def _reduced(by_name, chips=4):
    chip = SimpleNamespace(by_name=by_name)
    return SimpleNamespace(chips=[chip] * chips)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
UNITS = {"flops": 1.97e12, "bytes": 1e6}  # 10 ms at the bf16 peak


def test_kernel_roofline_reads_the_named_kernels(bench):
    ms = 1e6
    trace = _reduced({"jvp_mpi_tpu_ring_attention_fwd_.1": 5 * ms,
                      "transpose_jvp_mpi_tpu_ring_attention_bwd__.1": 15 * ms,
                      "fusion.3": 30 * ms})
    ctx = SimpleNamespace(root=bench, trace=trace, calls=2, units=UNITS,
                          peaks=PEAKS)
    # 2 steps x 10 ms at peak over 4 chips x 20 ms of kernel time
    assert _read(ctx, "attn.kernel_roofline") == pytest.approx(25.0)


def test_kernel_roofline_none_without_named_kernels(bench):
    """The parent program's kernels carry no such names, and an untraced
    run has no trace."""
    ctx = SimpleNamespace(root=bench, trace=_reduced({"jvp__.1": 1e6}),
                          calls=2, units=UNITS, peaks=PEAKS)
    assert _read(ctx, "attn.kernel_roofline") is None
    ctx.trace = None
    assert _read(ctx, "attn.kernel_roofline") is None


def test_run_cell_untraced_reports_no_entry_metric(root):
    cell = harness.resolve_cell(harness.load_spec(root), "jacobi.f32.n2800",
                                root)
    res = harness.run_cell(cell, 7, 0.2, False, jax.devices(),
                           time.perf_counter())
    assert res["correct"] and not set(ENTRY) & set(res["metrics"])
