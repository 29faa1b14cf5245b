"""Quickstart: Hyft softmax as a drop-in, its gradient, and the kernels.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import HYFT16, HYFT32, hyft_softmax, get_softmax
from repro.kernels import ops

key = jax.random.PRNGKey(0)
z = jax.random.normal(key, (4, 64), jnp.float32) * 3.0

# 1. the accelerator emulation vs exact softmax
s_hyft = hyft_softmax(z, HYFT32)
s_ref = jax.nn.softmax(z, -1)
print("Hyft32 vs exact: mean|err| =",
      float(jnp.mean(jnp.abs(s_hyft - s_ref))))

# 2. training through the accelerator's own backward datapath
w = jax.random.normal(jax.random.PRNGKey(1), (64,))
g = jax.grad(lambda x: jnp.sum(hyft_softmax(x, HYFT32) * w))(z)
print("hyft-grad norm:", float(jnp.linalg.norm(g)))

# 3. the Pallas kernel (interpreted on CPU, compiled by Mosaic on a TPU)
s_kernel = ops.hyft_softmax(z, HYFT16)
print("kernel == emulation:",
      bool(jnp.all(s_kernel == hyft_softmax(z, HYFT16))))

# 4. every registry implementation on one row
for name in ["exact", "hyft16", "hyft32", "base2", "koca"]:
    s = get_softmax(name)(z[:1]).astype(jnp.float32)
    print(f"{name:8s} first-row max prob = {float(s.max()):.4f} "
          f"sum = {float(s.sum()):.4f}")

# 5. fused flash attention with Hyft numerics
q = jax.random.normal(key, (1, 4, 128, 32))
k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 128, 32))
v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 128, 32))
o = ops.hyft_attention(q, k, v, HYFT32, causal=True)
print("flash-hyft attention out:", o.shape, o.dtype)
