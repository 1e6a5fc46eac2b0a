"""Long-context TRAINING over the fused ring-attention kernels.

SURVEY.md §2 strategy table says long-context is first-class, and
``examples/ring_attention.py`` proves the forward; this example proves
the TRAINING story end to end: one transformer block (qkv projection →
causal ring attention → output projection → MLP) over a
sequence-sharded ``sp`` mesh, where BOTH attention passes run the
fused Pallas ring kernels — the forward's credit-flow K/V circulation
and the backward's [K, V, dK, dV] full-cycle ring
(``mpi_tpu.tpu.pallas_attention``, round 5).  Every weight gradient is
synchronized with a psum (weights are replicated; activations are
sequence-sharded), so a training step's communication is exactly: the
two attention rings + one gradient allreduce — nothing touches a
global [S, S] score matrix, and per-device activation memory is
O(S/P).

The loss and gradients are checked (tests/test_long_context.py)
against the same block trained on ONE device with dense attention — a
bitwise-independent oracle for the whole step, fused backward
included.

    JAX_PLATFORMS=cpu python examples/long_context_training.py -n 8 --seq-per-rank 64
    python examples/long_context_training.py -n 1 --seq-per-rank 8192   # one chip
"""

import argparse
import math
import os
import re
import sys

try:
    import mpi_tpu  # noqa: F401  (path check only)
except ModuleNotFoundError:  # running from a fresh checkout
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def init_params(d: int, hidden: int, seed: int = 0):
    """One transformer block's weights (replicated on every device)."""
    rng = np.random.RandomState(seed)

    def w(*shape):
        return jnp.asarray(rng.randn(*shape) * (1.0 / math.sqrt(shape[0])),
                           jnp.float32)

    return {"wq": w(d, d), "wk": w(d, d), "wv": w(d, d), "wo": w(d, d),
            "w1": w(d, hidden), "w2": w(hidden, d)}


def block_forward(params, x, attention_fn):
    """The block on a [rows, d] slice; ``attention_fn(q, k, v)`` is the
    only non-local op — dense on one device, a ring over ``sp``."""
    q, k, v = x @ params["wq"], x @ params["wk"], x @ params["wv"]
    att = attention_fn(q, k, v)
    h = x + att @ params["wo"]
    return h + jax.nn.relu(h @ params["w1"]) @ params["w2"]


def loss_fn(params, x, y, attention_fn):
    pred = block_forward(params, x, attention_fn)
    return jnp.mean((pred - y) ** 2)


def sharded_train_step(size: int, axis_name: str = "sp",
                       interpret: bool = True,
                       vmem_limit_bytes=None):
    """→ step(params, x_block, y_block) for one sp-sharded device:
    (loss, grads), attention on the fused ring kernels, grads psum'd.
    Wrap in shard_map over a mesh with ``axis_name`` (check_vma=False:
    the kernel leg must not take the interpreter's vma fallback)."""
    from mpi_tpu.tpu.pallas_attention import pallas_ring_attention

    def attention_fn(q, k, v):
        return pallas_ring_attention(q, k, v, axis_name, size,
                                     causal=True, interpret=interpret,
                                     vmem_limit_bytes=vmem_limit_bytes)

    def step(params, xb, yb):
        def local_loss(p):
            # mean-of-block-means == global mean (equal block sizes)
            return jax.lax.pmean(
                loss_fn(p, xb, yb, attention_fn), axis_name)

        loss, grads = jax.value_and_grad(local_loss)(params)
        # weights are replicated but each device's AD yields only the
        # PARTIAL gradient of the terms its shard computed (the
        # classic replicated-params trap).  pmean — not psum — is the
        # right sync: differentiating the pmean'd loss hands every
        # device cotangent 1 (the psum transpose of the 1/P factors),
        # so each partial is d(Σ_r L_r)/dp restricted to this shard's
        # terms and their average is dL_global/dp.  This is the one
        # gradient allreduce of the whole step.
        grads = jax.tree.map(
            lambda g: jax.lax.pmean(g, axis_name), grads)
        return loss, grads

    return step


def dense_train_step():
    """The single-device oracle: same block, dense causal attention."""
    def attention_fn(q, k, v):
        s = (q @ k.T) / math.sqrt(q.shape[-1])
        n = s.shape[0]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v

    def step(params, x, y):
        return jax.value_and_grad(
            lambda p: loss_fn(p, x, y, attention_fn))(params)

    return step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", type=int, default=8)
    ap.add_argument("--seq-per-rank", type=int, default=64)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--lr", type=float, default=1e-2)
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # an n-device virtual CPU mesh, set before any backend starts
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.n}"
        ).strip()
    platform = jax.devices()[0].platform
    from jax.sharding import PartitionSpec as P

    from mpi_tpu.tpu import default_mesh

    mesh = default_mesh(args.n, axis_name="sp")
    S = args.n * args.seq_per_rank
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(S, args.d), jnp.float32)
    y = jnp.asarray(rng.randn(S, args.d), jnp.float32)
    params = init_params(args.d, 2 * args.d)

    # interpret follows the platform (bench.py's convention): the CPU
    # tier runs the kernels' serial interpreter data path; a real
    # accelerator runs the COMPILED fused kernels
    interp = platform == "cpu"
    step = sharded_train_step(args.n, interpret=interp)
    jstep = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P("sp"), P("sp")),
        out_specs=(P(), P()), check_vma=False))
    mode = "serial-interpreter" if interp else "compiled"
    for i in range(args.steps):
        loss, grads = jstep(params, x, y)
        params = jax.tree.map(lambda p, g: p - args.lr * g, params, grads)
        print(f"step {i}: loss={float(loss):.6f} "
              f"(S={S} over {args.n} sp shards, fused fwd+bwd rings, "
              f"{mode})")


if __name__ == "__main__":
    main()
