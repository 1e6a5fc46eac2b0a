"""Host time per job inside ``mpi_tpu.run`` spent tracing the SPMD
program: the program's ``trace`` spans, children of the window's ``run``
spans, summed, over the window's calls (ms)."""

import program_spans


def read(ctx):
    return program_spans.stage_ms(ctx, "trace")
