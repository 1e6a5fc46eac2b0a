"""backend=tpu — MPI semantics over a jax.sharding.Mesh (SURVEY.md §7 M1-M2).

Public surface:
* :func:`run_spmd` / :func:`default_mesh` — run a portable MPI program as one
  SPMD trace over the device mesh; :func:`enable_compile_cache` for chip
  programs.
* :class:`TpuCommunicator` — the Communicator bound to a mesh axis; fused XLA
  collectives plus hand-scheduled ppermute algorithms (ring /
  recursive-halving / tree / doubling / pairwise).
* :func:`pallas_ring_attention` — fused long-context ring attention (K/V
  circulate as in-kernel RDMAs; pallas_attention.py).
"""

from .communicator import SpmdSemanticsError, TpuCommunicator
from .runner import default_mesh, enable_compile_cache, run_spmd
from . import collectives


def __getattr__(name: str):
    # PEP 562 lazy re-export: ``import mpi_tpu.tpu`` stays light (pallas
    # loads only when used) and callers get the GENUINE function object
    # — real signature, real docstring (review round 4)
    if name == "pallas_ring_attention":
        from .pallas_attention import pallas_ring_attention

        return pallas_ring_attention
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "TpuCommunicator",
    "SpmdSemanticsError",
    "run_spmd",
    "default_mesh",
    "enable_compile_cache",
    "collectives",
    "pallas_ring_attention",
]
