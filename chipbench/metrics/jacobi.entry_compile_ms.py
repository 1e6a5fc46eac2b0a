"""Host time per job inside ``mpi_tpu.run`` spent compiling the lowered
program, or loading it from the persistent compilation cache: the
program's ``compile`` spans, children of the window's ``run`` spans,
summed, over the window's calls (ms)."""

import program_spans


def read(ctx):
    return program_spans.stage_ms(ctx, "compile")
