"""Mixture-of-Experts FFN: a router over every expert, and the part of the
result that the experts held here give.

Covers both assigned MoE archs:
  * Mixtral-8x7B      — 8 experts, top-2 renormalised, no shared experts.
  * DeepSeek-V2-Lite  — 64 fine-grained routed experts, softmax top-6 kept
                        unnormalised, + 2 shared experts, first layer dense.

The router keeps its full width (``MoEConfig.router_width``) and its top-k;
the layer holds ``n_experts`` of the routed experts from ``expert_offset``
and adds only their part (the rest lies on other chips), plus the shared
expert.  Two dispatches:

* :func:`moe_ffn` (training): position-in-expert scatter into a static
  (E, C, D) buffer (not the GShard (T,E,C) one-hot einsum), so peak memory
  is O(E*C*D); rows past the capacity (capacity_factor 1.25) are dropped.
  Expert-parallel sharding partitions the leading E axis over the "model"
  mesh axis; XLA inserts the dispatch all-to-alls.
* :func:`moe_ffn_dropless` (serving): every (row, held expert) pair is
  computed — the pairs are sorted by expert and run through grouped
  matmuls (:func:`repro.kernels.ops.grouped_matmul`: a Pallas kernel on
  the TPU that keeps each expert's weights in VMEM over all its rows,
  ``jax.lax.ragged_dot`` elsewhere), so nothing is dropped and the work
  follows the rows each expert really receives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels import ops
from repro.kernels.grouped_matmul import TM, row_tiles
from repro.models.layers import _he, swiglu, swiglu_init

__all__ = ["moe_init", "moe_ffn", "moe_ffn_dropless", "route",
           "expert_capacity"]


def expert_capacity(n_tokens: int, cfg: MoEConfig) -> int:
    cap = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(8, -(-cap // 8) * 8)  # round up to 8


def moe_init(key, d_model: int, cfg: MoEConfig, dtype=jnp.bfloat16):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": _he(k1, (d_model, cfg.router_width), jnp.float32),
        "w1": _he(k2, (cfg.n_experts, d_model, cfg.d_expert), dtype),
        "w3": _he(k3, (cfg.n_experts, d_model, cfg.d_expert), dtype),
        "w2": _he(k4, (cfg.n_experts, cfg.d_expert, d_model), dtype,
                  fan_in=cfg.d_expert),
    }
    if cfg.n_shared:
        d_sh = cfg.d_shared or cfg.n_shared * cfg.d_expert
        p["shared"] = swiglu_init(k5, d_model, d_sh, dtype)
    return p


def moe_ffn(params, x: jax.Array, cfg: MoEConfig):
    """x: (..., D) -> (..., D), plus the router aux (load-balancing) loss.

    With ``cfg.dispatch_groups = G > 1`` and 3D input (B, S, D), the token
    axis splits into B*G groups of S/G tokens.  Because activations are
    sharded (batch=data, seq=model), every group lives inside ONE shard, so
    the position cumsum / scatter / gather of the dispatch run shard-locally
    via vmap — no cross-shard prefix sums, no involuntary resharding
    (EXPERIMENTS.md §Perf hillclimb B).
    """
    G = cfg.dispatch_groups
    if G >= 1 and x.ndim == 3 and x.shape[1] % G == 0:
        B, S, D = x.shape
        xg = x.reshape(B * G, S // G, D)
        yg, aux = jax.vmap(
            lambda xs: _moe_ffn_single(params, xs, cfg)
        )(xg)
        out = yg.reshape(B, S, D)
        aux = jnp.mean(aux)
    else:
        flat = x.reshape(-1, x.shape[-1])
        out, aux = _moe_ffn_single(params, flat, cfg)
        out = out.reshape(x.shape)
    if "shared" in params:
        out = out + swiglu(params["shared"], x)
    return out, aux


def route(params, x: jax.Array, cfg: MoEConfig):
    """x (T, D) -> (probs (T, router_width), weights (T, K), ids (T, K)).

    Softmax over every routed expert in float32, greedy top-k; the top-k
    weights are renormalised only where ``cfg.norm_topk_prob`` says so.
    """
    probs = jax.nn.softmax(x.astype(jnp.float32) @ params["router"], axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)  # (T, K)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return probs, top_w, top_i


def _held(top_i: jax.Array, cfg: MoEConfig):
    """Local ids (into the experts held here) and whether each is held."""
    local = top_i - cfg.expert_offset
    return local, (local >= 0) & (local < cfg.n_experts)


def _moe_ffn_single(params, x: jax.Array, cfg: MoEConfig):
    T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = expert_capacity(T, cfg)

    probs, top_w, top_i = route(params, x, cfg)

    # Aux load-balancing loss (Switch-style): E * sum_e f_e * P_e, over
    # every routed expert.
    Er = cfg.router_width
    me = jnp.mean(probs, axis=0)  # (Er,)
    assign = jax.nn.one_hot(top_i, Er, dtype=jnp.float32)  # (T, K, Er)
    ce = jnp.mean(jnp.sum(assign, axis=1), axis=0) / K  # fraction per expert
    aux_loss = cfg.router_aux_weight * Er * jnp.sum(me * ce)

    # Position-in-expert via cumsum over flattened (T*K) assignments to the
    # experts held here; the others' rows go to other chips.
    local, held = _held(top_i, cfg)
    flat_e = jnp.where(held, local, 0).reshape(-1)  # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32) \
        * held.reshape(-1, 1)  # (T*K, E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # (T*K,)
    keep = (pos < C) & held.reshape(-1)
    slot = jnp.where(keep, pos, 0)

    token_idx = jnp.repeat(jnp.arange(T), K)
    xk = x[token_idx]  # (T*K, D)
    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[flat_e, slot].add(jnp.where(keep[:, None], xk, 0))

    # Batched expert FFN on the (E, C, D) buffer.
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, params["w1"])
    ) * jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    y = jnp.einsum("ecf,efd->ecd", h, params["w2"])  # (E, C, D)

    out_k = y[flat_e, slot] * keep[:, None]  # (T*K, D)
    out_k = out_k * top_w.reshape(-1)[:, None].astype(x.dtype)
    out = jnp.zeros((T, D), x.dtype).at[token_idx].add(out_k)
    return out, aux_loss


def moe_ffn_dropless(params, x: jax.Array, cfg: MoEConfig):
    """x (..., D) -> (y (..., D), rows (n_experts,) int32, row tiles ()
    int32): the served expert layer, which computes every (row, held
    expert) pair.

    The T*K routed pairs are sorted by held expert (pairs for experts held
    elsewhere sort last and are left out) and run through three grouped
    matmuls (:func:`repro.kernels.ops.grouped_matmul`); each pair's
    output is weighted by its router weight and summed back into its row.
    ``rows[e]`` counts the pairs held expert ``e`` received.  On the
    Pallas kernel the sort's index is padded to a multiple of its row tile
    with pairs keyed "elsewhere", and the row tiles one grouped matmul
    visits are counted (0 on the XLA path).  Scopes under the caller's:
    ``moe_router``, ``moe_experts`` (dispatch, grouped matmuls, combine)
    and ``moe_shared``.
    """
    D = x.shape[-1]
    flat = x.reshape(-1, D)
    T, E, K = flat.shape[0], cfg.n_experts, cfg.top_k
    pallas = ops.resolve(None) == "pallas"
    pad = -(T * K) % TM if pallas else 0
    with jax.named_scope("moe_router"):
        _, top_w, top_i = route(params, flat, cfg)
        local, held = _held(top_i, cfg)
        key = jnp.where(held, local, E).reshape(-1)  # (T*K,) E = elsewhere
        if pad:
            key = jnp.pad(key, (0, pad), constant_values=E)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
        tiles = row_tiles(sizes) if pallas else jnp.zeros((), jnp.int32)
    with jax.named_scope("moe_experts"):
        # the padding's pairs gather past the rows (the fill value); they
        # sort after every group and are never visited
        xs = jnp.take(flat, order // K, axis=0)  # (T*K + pad, D), by expert

        def gmm(lhs, w):
            return ops.grouped_matmul(lhs, w, sizes)

        h = jax.nn.silu(gmm(xs, params["w1"])) * gmm(xs, params["w3"])
        y = gmm(h, params["w2"])  # (T*K + pad, D)
        # rows past the held groups are not computed (the kernel leaves
        # them unwritten, NaN possibly): zero them
        computed = jnp.take(key, order) < E
        y = jnp.where(computed[:, None], y, 0)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.take(y, inv[: T * K], axis=0).reshape(T, K, D)
        w = jnp.where(held, top_w, 0.0).astype(y.dtype)
        out = jnp.einsum("tkd,tk->td", y, w)
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            out = out + swiglu(params["shared"], flat)
    return out.reshape(x.shape), sizes, tiles
