"""Share of the roofline the causal attention kernels reach while they run
the full layers of a mixed period: the full layers' least time a training
step (the larger of their FLOPs at the bf16 peak and their least HBM bytes
at the HBM peak) x steps, over the device time of the ops named for the
causal forward and backward kernels (``mpi_tpu_ring_attention_fwd`` /
``_bwd``, anywhere in an op's name, since autodiff and ``lax.scan``
prefix and suffix it), summed over the chips (%).  None where the program
has no such kernels or the driver counts no full layers."""

KERNELS = ("mpi_tpu_ring_attention_fwd", "mpi_tpu_ring_attention_bwd")


def read(ctx):
    if ctx.trace is None or "full_flops" not in ctx.units:
        return None
    busy_ns = sum(ns for chip in ctx.trace.chips
                  for name, ns in chip.by_name.items()
                  if any(k in name for k in KERNELS))
    if busy_ns <= 0:
        return None
    least = max(ctx.units["full_flops"] / ctx.peaks["bf16_flops_per_s"],
                ctx.units["full_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.calls / (busy_ns / 1e9)
