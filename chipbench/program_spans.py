"""The program's own host spans of a traced window, for the entry layer's
metrics: the ``run`` spans of ``mpi_tpu.run`` (``run_spmd``) and their
stage children (``trace``, ``lower``, ``compile``), as the program's
flight recorder (``mpi_tpu.telemetry``) holds them.

A profiler session turns the program's spans on, and the profiler starts
at the window (``harness.window``), so the recorder holds the window's
calls.  Taking the last ``ctx.calls`` ``run`` spans also holds where
``MPI_TPU_TRACE=1`` recorded the warm-up too.
"""


def window_runs(ctx):
    """→ [(run span, {stage name: child span})] of the window's calls, or
    None where the run was not traced, there is no recorder, or it holds
    fewer ``run`` spans than the window made calls (a program without
    them)."""
    if ctx.trace is None:
        return None
    from mpi_tpu import telemetry

    rec = telemetry.recorder()
    if rec is None:
        return None
    events = rec.find("tpu")
    runs = [e for e in events if e["name"] == "run"]
    if len(runs) < ctx.calls:
        return None
    children = {}
    for e in events:
        if "parent" in e["attrs"]:
            children.setdefault(e["attrs"]["parent"], {})[e["name"]] = e
    return [(r, children.get(r["attrs"]["seq"], {}))
            for r in runs[len(runs) - ctx.calls:]]


def stage_ms(ctx, stage: str):
    """The window's ``stage`` child spans, summed, over its calls (ms), or
    None where :func:`window_runs` finds nothing."""
    runs = window_runs(ctx)
    if runs is None:
        return None
    total = sum(kids[stage]["dur_ns"] for _, kids in runs if stage in kids)
    return total / ctx.calls / 1e6
