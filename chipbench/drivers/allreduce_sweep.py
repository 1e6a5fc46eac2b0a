"""Driver: one caller, closed loop of f32 SUM allreduces through
``TpuCommunicator.allreduce`` over a 1-D mesh of the cell's chips, cycling
through ``mix["sizes_per_chip"]``: one ``jax.jit(jax.shard_map(...))``
program per size, all compiled at set-up (the harness warms every input).

Inputs: one global buffer per size, integer-valued f32 made on the device
from the seed (|x| < 2**12, so every sum of four is exact); call i
reduces size i mod sizes through that size's program.
``units["bus_bytes"]`` is the cycle's mean bus bytes a call.  The check is
the ``allreduce`` driver's: each kept output, on every chip, against the
configuration's plain reference sum of that call's per-chip inputs.
"""

import harness
import work

LANES = 128
allreduce = harness.load_module("drivers", "allreduce")


class Driver(allreduce.Driver):

    def __init__(self, cfg, mix, seed, devices, ref):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from mpi_tpu.tpu import TpuCommunicator

        self.cfg, self.mix, self.ref = cfg, mix, ref
        chips = cfg["chips"]
        sizes = mix["sizes_per_chip"]
        self.mesh = Mesh(np.array(devices[:chips]), ("world",))
        comm = TpuCommunicator("world", self.mesh)
        algorithm = cfg["algorithm"]
        self.interpret = algorithm == "pallas_ring" and comm._pallas_interp
        sharding = NamedSharding(self.mesh, P("world"))
        self.fns = [jax.jit(jax.shard_map(
            lambda x: comm.allreduce(x, algorithm=algorithm),
            mesh=self.mesh, in_specs=P("world"), out_specs=P("world")))
            for _ in sizes]
        self.fn = self.fns[-1]
        shapes = [(chips * (n // (4 * LANES)), LANES) for n in sizes]
        # the largest size, for a compile without the chip (aot.py)
        self.arg_shapes = (jax.ShapeDtypeStruct(shapes[-1], jnp.float32,
                                                sharding=sharding),)
        bound = cfg["value_bound"]
        make = jax.jit(
            lambda key, shape: jax.random.randint(
                key, shape, -bound, bound, dtype=jnp.int32).astype(jnp.float32),
            static_argnums=1, out_shardings=sharding)
        self.inputs = [] if seed is None else [
            make(harness.prng_key(seed, j), s) for j, s in enumerate(shapes)]
        bus = [work.allreduce_bus_bytes(n, chips) for n in sizes]
        self.units = {"bytes_per_chip": sum(sizes) / len(sizes),
                      "bus_bytes": sum(bus) / len(bus)}

    def call(self, i):
        n = i % len(self.fns)
        return self.fns[n](self.inputs[n])

    def free(self):
        self.fn = self.fns = None
