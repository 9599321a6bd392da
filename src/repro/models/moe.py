"""Mixture-of-Experts FFN with sort-based token dispatch.

Dispatch avoids the classic GShard one-hot (T,E,C) tensor — infeasible at
1M tokens — by sorting (token, slot) pairs by expert id and
gathering/scattering through a capacity-bounded expert buffer
[E, C, D].  All shapes are static (capacity-dropped tokens fall into an
overflow row), so the same code lowers for the dry-run at 778B scale and
runs the CPU smoke tests.

Sharding: the expert buffer and expert weights carry a
``with_sharding_constraint`` placing E on the 'model' axis (expert
parallelism); token arrays stay batch-sharded on 'data'.  The baseline
lets XLA pick the dispatch collectives (gather across data shards); the
§Perf hillclimb replaces this with an explicit shard_map all-to-all —
both paths are kept selectable (``ep_mode``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init


def init_moe_params(key, cfg, dtype=jnp.bfloat16) -> Dict[str, Any]:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (D, E), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (E, D, F), in_axis=1, dtype=dtype),
        "w_up": dense_init(ks[2], (E, D, F), in_axis=1, dtype=dtype),
        "w_down": dense_init(ks[3], (E, F, D), in_axis=1, dtype=dtype),
    }


def capacity(T: int, cfg) -> int:
    c = int(math.ceil(T * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)   # round up to a multiple of 8, >= 8


def moe_ffn(params, x, cfg, constrain=None):
    """x: [B, S, D] -> [B, S, D].  ``constrain(tensor, spec)`` applies
    sharding constraints (no-op when None)."""
    if constrain is None:
        constrain = lambda t, spec: t
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(T, cfg)
    xt = x.reshape(T, D)

    # ---- router ----
    logits = (xt.astype(jnp.float32) @ params["router"])        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # [T, K]
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # ---- sort-based dispatch ----
    flat_expert = expert_idx.reshape(-1)                        # [T*K]
    order = jnp.argsort(flat_expert)                            # stable
    sorted_expert = flat_expert[order]
    sorted_token = order // K
    counts = jnp.zeros((E,), jnp.int32).at[flat_expert].add(1)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K, dtype=jnp.int32) - offsets[sorted_expert]
    keep = pos < C
    slot = jnp.where(keep, sorted_expert * C + pos, E * C)      # overflow row

    buf = jnp.zeros((E * C + 1, D), x.dtype).at[slot].set(xt[sorted_token])
    ebuf = constrain(buf[:E * C].reshape(E, C, D), P("model", None, None))

    # ---- expert FFN (einsum over per-expert weights, E on 'model') ----
    g = jnp.einsum("ecd,edf->ecf", ebuf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", ebuf, params["w_up"])
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    y = constrain(y, P("model", None, None))

    # ---- combine ----
    ypad = jnp.concatenate([y.reshape(E * C, D),
                            jnp.zeros((1, D), y.dtype)], axis=0)
    contrib = ypad[slot]                                        # [T*K, D]
    gates_sorted = (gate_vals.reshape(-1)[order] *
                    keep.astype(jnp.float32))                   # [T*K]
    out = jnp.zeros((T, D), jnp.float32).at[sorted_token].add(
        contrib.astype(jnp.float32) * gates_sorted[:, None])
    return out.reshape(B, S, D).astype(x.dtype)


def moe_ffn_ep(params, x, cfg, mesh):
    """Expert-parallel MoE via shard_map (§Perf variant).

    The baseline ``moe_ffn`` traces global [T_global, ...] dispatch
    arrays and lets GSPMD shard them — at 1M tokens the partitioner
    falls back to replicated sort/scatter buffers (hundreds of GiB, the
    dominant collective term in the moonshot/llama4 baselines).  Here
    every device dispatches its LOCAL tokens to its LOCAL experts
    directly:

      * activations arrive batch-sharded over ('pod','data') and
        replicated over 'model' — each model shard sees every local
        token and simply filters for its own experts (no all-to-all
        needed at this replication layout);
      * the per-device expert buffer is [E/TP, C_local, D];
      * one psum over 'model' recombines expert outputs — the same
        collective shape as a Megatron MLP.
    """
    from jax import shard_map

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape.get("model", 1)
    assert E % tp == 0
    e_loc = E // tp
    dp = 1
    for a in axes:
        dp *= mesh.shape[a]
    t_loc = (B // dp) * S
    C = capacity(t_loc, cfg)

    def f(xl, router, wg, wu, wd):
        b_loc = xl.shape[0]
        xt = xl.reshape(b_loc * S, D)
        T = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

        e0 = jax.lax.axis_index("model") * e_loc
        fe = expert_idx.reshape(-1)                      # [T*K]
        mine = (fe >= e0) & (fe < e0 + e_loc)
        sort_key = jnp.where(mine, fe - e0, e_loc)       # strangers last
        order = jnp.argsort(sort_key)
        s_fe = sort_key[order]
        s_tok = order // K
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[sort_key].add(1)
        offsets = jnp.cumsum(counts) - counts
        pos = jnp.arange(T * K, dtype=jnp.int32) - offsets[s_fe]
        keep = (s_fe < e_loc) & (pos < C)
        slot = jnp.where(keep, s_fe * C + pos, e_loc * C)

        buf = jnp.zeros((e_loc * C + 1, D), xl.dtype).at[slot].set(
            xt[s_tok])
        ebuf = buf[:e_loc * C].reshape(e_loc, C, D)
        g = jnp.einsum("ecd,edf->ecf", ebuf, wg)
        u = jnp.einsum("ecd,edf->ecf", ebuf, wu)
        h = jax.nn.silu(g) * u
        y = jnp.einsum("ecf,efd->ecd", h, wd)

        ypad = jnp.concatenate([y.reshape(e_loc * C, D),
                                jnp.zeros((1, D), y.dtype)], axis=0)
        contrib = ypad[slot]
        gates_sorted = (gate_vals.reshape(-1)[order] *
                        keep.astype(jnp.float32))
        out = jnp.zeros((T, D), jnp.float32).at[s_tok].add(
            contrib.astype(jnp.float32) * gates_sorted[:, None])
        out = jax.lax.psum(out, "model")                 # combine experts
        return out.reshape(b_loc, S, D).astype(xl.dtype)

    fn = shard_map(
        f, mesh=mesh,
        in_specs=(P(axes, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P(axes, None, None), check_vma=False)
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])


def aux_load_balance_loss(params, x, cfg):
    """Switch-style load-balancing auxiliary loss (fraction*prob form)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    logits = xt.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, cfg.n_experts), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac * mean_prob)
