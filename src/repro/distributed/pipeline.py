"""Optional GPipe-style pipeline parallelism (shard_map + ppermute).

Stages live on a 'stage' mesh axis; microbatches stream through with the
classic (n_micro + S - 1)-step schedule.  The communication pattern is a
single ppermute per step — jax-native collective-permute rather than
emulated send/recv.  Used for the PP feature demonstration + tests; the
production configs default to DP x TP (+ ZeRO/SP), where PP is not
required to fit any assigned architecture.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(block_fn: Callable, stage_weights, x, mesh: Mesh,
                   n_microbatches: int):
    """Apply ``block_fn(w_s, h)`` for stages s = 0..S-1 in pipeline.

    stage_weights: [S, ...] (stage-major stacked weights, sharded on
    'stage'); x: [B, ...] input batch (replicated).  Returns the output
    of the final stage for the whole batch.
    """
    S = mesh.shape["stage"]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches
    xr = x.reshape(n_microbatches, mb, *x.shape[1:])
    n_steps = n_microbatches + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def stage_fn(w, xs):
        w = w[0]                                   # local stage's weights
        sid = jax.lax.axis_index("stage")
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def step(carry, t):
            buf, outs = carry
            inp = jnp.where(sid == 0,
                            xs[jnp.clip(t, 0, n_microbatches - 1)], buf)
            h = block_fn(w, inp)
            nxt = jax.lax.ppermute(h, "stage", perm)
            m = t - (S - 1)                        # microbatch finishing now
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, h, jnp.clip(m, 0, n_microbatches - 1), 0)
            take = jnp.logical_and(sid == S - 1, m >= 0)
            outs = jnp.where(take, upd, outs)
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs),
                                      jnp.arange(n_steps))
        # replicate the last stage's result to all stages
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), "stage")
        return outs

    f = shard_map(stage_fn, mesh=mesh,
                  in_specs=(P("stage"), P()),
                  out_specs=P(), check_vma=False)
    outs = f(stage_weights, xr)
    return outs.reshape(B, *x.shape[1:])
