"""mpi_tpu.telemetry — the observability layer (ISSUE 13 tentpole).

Three pieces, MPI_T + Score-P/Chrome-trace shaped:

* the per-rank **flight recorder** (:mod:`.recorder`): a fixed-size
  ring of timestamped binary events instrumented at the existing seams
  — collective begin/end with resolved algorithm + bytes
  (communicator.py), socket frame send/recv + link reconnect/replay/
  heal (transport/socket.py + resilience.py), nonblocking-collective
  state-machine transitions (nbc.py), arena hit/fallback (coll_sm.py),
  lease lifecycle (serve.py), FT suspicion + membership epoch bumps
  (ft.py / membership.py).  Exported as Chrome-trace/Perfetto JSON;
  ``tools/tracecat.py`` merges the per-rank files onto one aligned
  timeline.
* **histogram pvars** (mpi_tpu/mpit.py ``hist_record`` /
  ``pvar_hist_read`` / ``hist_quantile``): log-bucketed latency
  distributions — collective latency, lease acquire, link heal —
  beside the scalar counters.
* the **serve metrics endpoint** (:mod:`.metrics` + serve.py
  ``--metrics-port``): ``client.stats()`` grew worlds/s + lease
  p50/p99 + aggregated worker pvars, and the server optionally serves
  the same document as Prometheus text over HTTP.

Enablement mirrors verify/progress exactly: ``MPI_TPU_TRACE=1`` (init),
``run_local(..., trace=True)``, ``launcher --trace-dir``, or
:func:`enable` directly.  Off = the module singleton :data:`REC` is
``None`` and every instrumented seam is one attribute test — zero
events (``trace_events`` pvar), unchanged wire accounting
(``bench.py --verify-overhead --trace`` asserts it).

The TPU layer's host spans (:func:`span`: ``mpi_tpu.run``'s ``run``,
``trace``, ``lower``, ``compile``) are recorded as kind ``"tpu"`` and,
while a JAX profiler session records, also written into its trace as
``TraceAnnotation("mpi_tpu.<name>")``.  A profiler session alone turns
them on: the first span then starts the recorder.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional

from .recorder import Recorder, WAIT_MIN_NS

__all__ = [
    "Recorder", "REC", "WAIT_MIN_NS", "enable", "disable", "enabled",
    "recorder", "export_chrome", "env_enabled", "env_trace_dir", "span",
]

# THE off-mode gate: every instrumentation seam in the library reads
# this module attribute and returns when it is None.  Process-wide on
# purpose (like the mpit counters): local-backend rank threads share
# one recorder and are told apart by tid; process worlds each own one.
REC: Optional[Recorder] = None

_LAST: Optional[Recorder] = None  # kept after disable() for export
_lock = threading.Lock()


def enable(rank: Optional[int] = None, capacity: int = 0,
           trace_dir: Optional[str] = None) -> Recorder:
    """Start (or return) the process flight recorder.  Idempotent like
    ft/verify enable: re-enabling an active recorder returns it
    unchanged (rank/capacity of the first call win)."""
    global REC, _LAST
    with _lock:
        if REC is None:
            REC = _LAST = Recorder(capacity=capacity, rank=rank,
                                   trace_dir=trace_dir)
        return REC


def disable() -> Optional[Recorder]:
    """Stop recording.  The recorder object (and its events) survives
    as :func:`recorder`'s return value so a just-finished traced run
    can still be exported/inspected — only NEW events stop."""
    global REC
    with _lock:
        rec, REC = REC, None
        return rec


def enabled() -> bool:
    return REC is not None


def recorder() -> Optional[Recorder]:
    """The active recorder, or the most recently disabled one."""
    return REC if REC is not None else _LAST


def export_chrome(path: str, rec: Optional[Recorder] = None) -> str:
    """Export the active (or last) recorder as Chrome-trace JSON."""
    rec = rec or recorder()
    if rec is None:
        raise RuntimeError("no recorder: enable tracing first "
                           "(MPI_TPU_TRACE=1 / run_local(trace=True) / "
                           "telemetry.enable())")
    return rec.export_chrome(path)


# -- environment enablement (init() / worker processes) ----------------------


def env_enabled() -> bool:
    return os.environ.get("MPI_TPU_TRACE", "") not in ("", "0")


def env_trace_dir() -> Optional[str]:
    return os.environ.get("MPI_TPU_TRACE_DIR") or None


def enable_from_env(rank: Optional[int] = None) -> Optional[Recorder]:
    """init()-time enablement: ``MPI_TPU_TRACE=1`` starts the recorder,
    ``MPI_TPU_TRACE_DIR`` (launcher ``--trace-dir``) makes it export at
    process exit — atexit rather than finalize-only, because chaos/
    bench rank programs routinely ``sys.exit`` without a finalize and
    their trace is exactly the one worth keeping."""
    if not env_enabled():
        return None
    rec = enable(rank=rank, trace_dir=env_trace_dir())
    if rec.trace_dir:
        import atexit

        atexit.register(rec.export_to_dir)
    return rec


# -- host spans of the TPU layer ---------------------------------------------

_OFF = contextlib.nullcontext()  # the shared no-op span


def _never() -> bool:
    return False


def _bind_profiler() -> bool:
    """The first check binds :func:`_profiler_recording` to the flag JAX's
    own ``TraceAnnotation`` reads (jaxlib's ``TraceMe.is_enabled``), so
    every later check is one C call and this module never imports JAX on
    its own.  Where the installed jaxlib lacks it, only the recorder
    switch gates."""
    global _profiler_recording
    try:
        from jax._src.lib import _profiler

        _profiler_recording = _profiler.TraceMe.is_enabled
    except (ImportError, AttributeError):
        _profiler_recording = _never
    return _profiler_recording()


_profiler_recording = _bind_profiler


class _Span:
    """One ``"tpu"`` recorder event from enter to exit, inside a
    ``TraceAnnotation`` while a profiler session records."""

    __slots__ = ("name", "attrs", "t0", "annotation")

    def __init__(self, name: str, attrs: dict) -> None:
        self.name = name
        self.attrs = attrs
        self.annotation = None

    def __enter__(self) -> "_Span":
        if _profiler_recording():
            from jax.profiler import TraceAnnotation

            self.annotation = TraceAnnotation("mpi_tpu." + self.name)
            self.annotation.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.time_ns() - self.t0
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        (REC or enable()).emit("tpu", self.name, dur_ns=dur,
                               attrs=self.attrs or None, start_ns=self.t0)


def span(name: str, **attrs):
    """Context manager: a host span of the TPU layer, named ``name``.

    Off (no recorder and no JAX profiler session recording) it returns
    one shared no-op context.  On, the span is recorded as a ``"tpu"``
    event carrying ``attrs`` and, while a profiler session records, is
    also the ``TraceAnnotation("mpi_tpu.<name>")`` of that trace, so it
    lies on the host plane beside the device's ops.  A profiler session
    with no recorder starts one (:func:`enable`), as ``MPI_TPU_TRACE=1``
    would.  Timestamps are ``time.time_ns()``, the profiler trace's
    clock."""
    if REC is None and not _profiler_recording():
        return _OFF
    return _Span(name, attrs)
