"""Share of the attention kernels' device time that the sliding-window
kernels take: the device time of ops named for ``mpi_tpu_swa_attention_*``
over that of ops named for any attention kernel
(``mpi_tpu_swa_attention_*`` or ``mpi_tpu_ring_attention_*``), summed over
the chips (%).  Whether the window mechanism does most of the kernel
work.  None where the program has no windowed kernels."""

SWA = "mpi_tpu_swa_attention_"
ALL = (SWA, "mpi_tpu_ring_attention_")


def read(ctx):
    if ctx.trace is None:
        return None
    swa = every = 0
    for chip in ctx.trace.chips:
        for name, ns in chip.by_name.items():
            if SWA in name:
                swa += ns
            if any(k in name for k in ALL):
                every += ns
    if swa <= 0:
        return None
    return 100.0 * swa / every
