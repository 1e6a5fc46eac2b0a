"""Host time per job inside ``mpi_tpu.run`` spent lowering the traced
program: the program's ``lower`` spans, children of the window's ``run``
spans, summed, over the window's calls (ms)."""

import program_spans


def read(ctx):
    return program_spans.stage_ms(ctx, "lower")
