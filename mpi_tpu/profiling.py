"""Per-op communication counters (SURVEY.md §5: tracing row).

:class:`CommStats` counts collectives and their payload bytes by op; the
flight recorder (mpi_tpu/telemetry) fills one per traced run — every
traced collective records (op, payload bytes) — and :func:`comm_stats`
returns it.  Traces come from the recorder and, for the TPU path, from
``jax.profiler`` itself (``telemetry.span`` writes into its trace).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict


@dataclass
class CommStats:
    """Structured per-op counters (counts + bytes), JSON-able for logs.
    The live instance of a traced run hangs off the flight recorder
    (``telemetry.Recorder.stats``); :func:`comm_stats` fetches it."""

    ops: Dict[str, int] = field(default_factory=dict)
    bytes: Dict[str, int] = field(default_factory=dict)

    def record(self, op: str, nbytes: int = 0) -> None:
        self.ops[op] = self.ops.get(op, 0) + 1
        self.bytes[op] = self.bytes.get(op, 0) + nbytes

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "bytes": self.bytes})


def comm_stats() -> "CommStats | None":
    """The per-op counters of the active (or last) traced run — filled
    by every collective while the flight recorder is enabled
    (``MPI_TPU_TRACE=1`` / ``run_local(trace=True)`` /
    ``telemetry.enable()``).  None when nothing was ever traced."""
    from . import telemetry as _telemetry

    rec = _telemetry.recorder()
    return rec.stats if rec is not None else None
