"""Driver: one training step, forward plus backward, of the attention
cores of one whole period of a model whose ``layer_types`` mix sliding-
window and full layers: each sliding layer through
``pallas_ring_attention(q, k, v, "sp", 1, causal=True,
window=sliding_window)`` and each full layer with ``window=None``, inside
one ``jax.jit(jax.shard_map(...))`` over a one-chip ``sp`` mesh.  The
layers of one kind are stacked and run under one ``lax.scan``, so each
kind's kernel pair is lowered and compiled once.

Inputs: ``mix["inputs"]`` sets of q, dO [n, Hq, S, d] and k, v
[n, Hkv, S, d] per layer kind (n layers of that kind) in the
configuration's dtype, made on the device from the seed; step i uses set
i mod inputs.  The check compares each kept step's out, dq, dk and dv of
every layer with the configuration's plain float32 reference, and fails
where the program's ``attention_window`` counter did not rise at set-up
(the sliding layers did not take the windowed tile ranges).
"""

import numpy as np

import harness
import work

KINDS = (("sliding_attention", True), ("full_attention", False))


def window_pairs(seq, window):
    """Pairs a causal sliding window keeps: query i sees keys
    max(0, i - window + 1)..i."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


class Driver:
    issue_span = harness.ISSUE_SPAN

    def __init__(self, cfg, mix, seed, devices, ref):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from mpi_tpu import mpit
        from mpi_tpu.tpu.pallas_attention import pallas_ring_attention

        self.cfg, self.mix, self.ref = cfg, mix, ref
        seq = mix["seq_len"]
        hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        self.window = cfg["sliding_window"]
        types = cfg["layer_types"]
        # per kind: (window, number of layers), sliding first
        self.kinds = [(self.window if sliding else None, types.count(kind))
                      for kind, sliding in KINDS if kind in types]
        dtype = jnp.dtype(cfg["dtype"])
        self.mesh = Mesh(np.array(devices[:1]), ("sp",))
        self.interpret = devices[0].platform == "cpu"
        interpret = self.interpret
        self.window_traces = mpit.pvar_read("attention_window")

        def group(window, q, k, v, do):
            """One kind's layers, stacked on axis 0, under one scan."""
            def layer(_, x):
                q_, k_, v_, do_ = x
                out, vjp = jax.vjp(
                    lambda a, b, c: pallas_ring_attention(
                        a, b, c, "sp", 1, causal=True, window=window,
                        interpret=interpret), q_, k_, v_)
                return None, (out,) + tuple(vjp(do_))

            return jax.lax.scan(layer, None, (q, k, v, do))[1]

        def step(*xs):
            outs = ()
            for n, (window, _) in enumerate(self.kinds):
                outs += group(window, *xs[4 * n:4 * n + 4])
            return outs

        spec = P(None, None, "sp", None)
        n_arrays = 4 * len(self.kinds)
        self.fn = jax.jit(jax.shard_map(step, mesh=self.mesh,
                                        in_specs=(spec,) * n_arrays,
                                        out_specs=(spec,) * n_arrays,
                                        check_vma=False))

        def make(key):
            arrays = ()
            for kkey, (_, n) in zip(jax.random.split(key, len(self.kinds)),
                                    self.kinds):
                kq, kk, kv, kd = jax.random.split(kkey, 4)
                arrays += (jax.random.normal(kq, (n, hq, seq, d), dtype),
                           jax.random.normal(kk, (n, hkv, seq, d), dtype),
                           jax.random.normal(kv, (n, hkv, seq, d), dtype),
                           jax.random.normal(kd, (n, hq, seq, d), dtype))
            return arrays

        sharding = NamedSharding(self.mesh, spec)
        make = jax.jit(make, out_shardings=(sharding,) * n_arrays)
        self.arg_shapes = jax.eval_shape(make, jax.random.key(0))
        self.inputs = [] if seed is None else [
            make(harness.prng_key(seed, j)) for j in range(mix["inputs"])]
        layers = sum(n for _, n in self.kinds)
        swa_layers = sum(n for w, n in self.kinds if w is not None)
        swa_pairs = swa_layers * window_pairs(seq, self.window)
        pairs = swa_pairs + (layers - swa_layers) * work.causal_pairs(seq)
        # least HBM traffic of a layer: read q, k, v, dO, write out, dq,
        # dk, dv
        layer_bytes = 2.0 * dtype.itemsize * d * seq * (2 * hq + 2 * hkv)
        # per pair and query head: forward QK^T and PV (4d FLOPs),
        # backward dV, dP, dQ, dK (8d)
        self.units = {
            "tokens": seq,
            "flops": 12.0 * d * hq * pairs,
            "bytes": layers * layer_bytes,
            "swa_flops": 12.0 * d * hq * swa_pairs,
            "swa_bytes": swa_layers * layer_bytes,
        }

    def call(self, i):
        return self.fn(*self.inputs[i % len(self.inputs)])

    def free(self):
        self.fn = None

    def check(self, kept, control=False):
        """One reading per kept step: the worst, over the period's layers,
        of the relative L2 and max errors of out, dq, dk and dv against
        the float32 reference; and whether the sliding layers took the
        windowed kernels' tile ranges.  ``control``: the reference
        computed in float8 stands in for the program's output."""
        from mpi_tpu import mpit

        engaged = mpit.pvar_read("attention_window") > self.window_traces
        readings = []
        for i, got in kept:
            xs = self.inputs[i % len(self.inputs)]
            worst = {}
            for n, (window, count) in enumerate(self.kinds):
                for layer in range(count):
                    ins = tuple(x[layer] for x in xs[4 * n:4 * n + 4])
                    outs = (None if control else
                            tuple(y[layer] for y in got[4 * n:4 * n + 4]))
                    for key, value in self.ref.check_layer(
                            ins, outs, window).items():
                        worst[key] = max(worst.get(key, 0.0), value)
            worst["window_not_engaged"] = int(not engaged)
            readings.append(worst)
        return readings
