"""Gradient compression: int8-quantized cross-pod all-reduce with error
feedback.

The expensive collective at multi-pod scale is the once-per-step
gradient reduction over the 'pod' axis (DCN-class links).  Quantizing
the summand to int8 with per-chunk scales cuts that traffic 2x vs bf16 /
4x vs f32; the residual (quantization error) is fed back into the next
step's gradient so the *accumulated* update stays unbiased (standard
error-feedback/EF-SGD argument — convergence is preserved while each
individual step is approximate).

``quantize``/``dequantize`` are pure and tested numerically;
``compressed_psum`` wires them around a shard_map psum over a named
axis.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

CHUNK = 1024


def quantize(x: jnp.ndarray, chunk: int = CHUNK
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: flat f32 [N] -> (int8 [N], per-chunk scales [N/chunk])."""
    n = x.shape[0]
    pad = (-n) % chunk
    xp = jnp.pad(x, (0, pad)).reshape(-1, chunk)
    scale = jnp.max(jnp.abs(xp), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xp / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, n: int,
               chunk: int = CHUNK) -> jnp.ndarray:
    xq = q.reshape(-1, chunk).astype(jnp.float32) * scale[:, None]
    return xq.reshape(-1)[:n]


def ef_quantize(x: jnp.ndarray, error: jnp.ndarray,
                chunk: int = CHUNK):
    """Error-feedback quantization: compress (x + carried error); return
    (q, scale, new_error)."""
    target = x + error
    q, scale = quantize(target, chunk)
    recon = dequantize(q, scale, x.shape[0], chunk)
    return q, scale, target - recon


def compressed_psum(x: jnp.ndarray, error: jnp.ndarray, mesh: Mesh,
                    axis: str = "pod", chunk: int = CHUNK):
    """Mean-reduce flat f32 x over ``axis`` with int8 wire payload +
    error feedback.  Returns (reduced_mean, new_error).

    Members quantize independently (per-chunk scales), so payloads are
    not summable in transit; the collective is an int8 all-gather —
    (g-1)/g x N x 1B on the wire vs 2 (g-1)/g x N x 4B for an f32
    all-reduce, a ~8x traffic cut — followed by a local dequantize-sum.
    """
    n = x.shape[0]

    def f(xl, el):
        q, scale, new_err = ef_quantize(xl, el, chunk)
        qg = jax.lax.all_gather(q, axis)          # int8 on the wire
        sg = jax.lax.all_gather(scale, axis)      # tiny f32 scales
        deq = jax.vmap(lambda qi, si: dequantize(qi, si, n, chunk))(qg, sg)
        g = deq.shape[0]
        return jnp.sum(deq, axis=0) / g, new_err

    spec = P()
    return shard_map(f, mesh=mesh, in_specs=(spec, spec),
                     out_specs=(spec, spec), check_vma=False)(x, error)
