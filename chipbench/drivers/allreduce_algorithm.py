"""Driver: the ``allreduce`` driver with the traffic mix's ``algorithm``
in place of the configuration's, so one deployment is measured through
another of ``TpuCommunicator.allreduce``'s algorithms (``pallas_ring``:
mpi_tpu's own Pallas RDMA ring) with its inputs, loop and check."""

import harness

allreduce = harness.load_module("drivers", "allreduce")


class Driver(allreduce.Driver):

    def __init__(self, cfg, mix, seed, devices, ref):
        super().__init__(dict(cfg, algorithm=mix["algorithm"]), mix, seed,
                         devices, ref)
