"""Share of the window's ``mpi_tpu.run`` calls that traced their program:
the program's ``run`` spans with a ``trace`` child, over the window's
calls.  1 where every call re-traces; a program that reuses its compiled
executable takes it toward 0."""

import program_spans


def read(ctx):
    runs = program_spans.window_runs(ctx)
    if runs is None:
        return None
    return sum(1 for _, kids in runs if "trace" in kids) / ctx.calls
