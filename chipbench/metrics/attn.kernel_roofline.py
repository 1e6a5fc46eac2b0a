"""Share of the roofline the ring-attention kernels reach while they run:
the least time of a training step (the larger of its causal GQA FLOPs at
the bf16 peak and its least HBM bytes at the HBM peak) x steps, over the
device time of the ops named for the forward and backward kernels, summed
over the chips (%).  The kernels are found by the names the program gives
them (``mpi_tpu_ring_attention_fwd`` / ``_bwd``) anywhere in an op's name,
since autodiff prefixes its transforms (``transpose_jvp_...``)."""

KERNELS = ("mpi_tpu_ring_attention_fwd", "mpi_tpu_ring_attention_bwd")


def read(ctx):
    if ctx.trace is None:
        return None
    busy_ns = sum(ns for chip in ctx.trace.chips
                  for name, ns in chip.by_name.items()
                  if any(k in name for k in KERNELS))
    if busy_ns <= 0:
        return None
    least = max(ctx.units["flops"] / ctx.peaks["bf16_flops_per_s"],
                ctx.units["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.calls / (busy_ns / 1e9)
