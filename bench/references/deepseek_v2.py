"""Plain float32 reference of DeepSeek-V2's decoder: latent attention and
sparse experts (arXiv 2405.04434; the published ``config.json`` and
``modeling_deepseek.py`` of DeepSeek-V2-Lite).

The architecture the configuration's ``decoder`` group describes: token
embedding; per layer RMSNorm -> multi-head latent attention -> residual,
RMSNorm -> FFN -> residual; a final RMSNorm; an untied output head, of
which the first ``vocab`` columns (the SID tokens) are scored.

* Attention (MLA, no query LoRA): ``q = h W_q`` split into a no-rope part
  and a rotary part; ``[c, k_pe] = h W_kv_a``, ``c`` RMS-normalised; keys
  and values expanded from ``c`` through ``W_kv_b`` at every position, the
  rotary key shared by the heads; YaRN rotary frequencies, and the softmax
  scale ``(nope + rope)^-1/2`` times ``mscale(factor, mscale_all_dim)^2``.
* FFN: the first ``first_dense_layers`` layers a SwiGLU of ``d_ff_dense``;
  the others experts: a float32 softmax over all ``n_routed`` experts,
  greedy top-``top_k``, the weights kept as the softmax gives them (no
  renormalisation, scale 1), and the shared SwiGLU added for every row.

Computed in float32 with ``highest`` matmul precision, with no cache, no
absorption and no batching tricks, layer by layer so that it fits one chip
after the served program has been freed.  It takes no array from the
program.

Departures, each also the program's:

* Expert share: like the program, the reference holds the ``n_experts``
  experts from ``expert_offset`` of each layer and adds only their part;
  what the experts held on other chips would add is left out (a deployment
  divides each layer over chips by experts).  The router keeps its full
  width and top-k.
* Rotary pairs: the interleaved pairs ``(2i, 2i+1)`` are rotated by
  frequency ``i``.  DeepSeek's code first permutes each rotary vector so
  that those pairs sit ``d/2`` apart and rotates halves; the permutation
  is the same for queries and keys, so every score is unchanged.

Weights are random data from the seed, made by this module's generator: an
embedding of N(0, 0.02^2), He-normal matrices over their rows (the router
in float32, the rest in the served dtype), RMSNorm scales of one.
:func:`weights_from_key` makes all of them for the system under test
(``bench/system.py`` places them by :func:`placement`); the reference draws
the same values again, layer by layer, and widens them to float32.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale before it is multiplied.

:func:`logprobs` returns, for each request and each of its served beams,
the log-softmax over the SID vocabulary at every level of that beam's
prefix: level 0 after the history, level ``l`` after the beam's first ``l``
tokens.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8 e4m3
ATTN = ("wq", "w_kv_a", "w_kv_b", "wo")
FFN = ("w1", "w3", "w2")


def _stacks(dec):
    """(program stack, drawn group, first layer, end) of each stack."""
    n_dense = dec["moe"]["first_dense_layers"]
    return [s for s in (("dense_layers", "dense", 0, n_dense),
                        ("moe_layers", "moe", n_dense, dec["n_layers"]))
            if s[3] > s[2]]


def _shapes(dec, group):
    """One layer's drawn matrices, by name, in drawing order."""
    D, H, m = dec["d_model"], dec["n_heads"], dec["moe"]
    r, nope, rope = (dec["kv_lora_rank"], dec["qk_nope_head_dim"],
                     dec["qk_rope_head_dim"])
    out = {"wq": (D, H * (nope + rope)), "w_kv_a": (D, r + rope),
           "w_kv_b": (r, H * (nope + dec["v_head_dim"])),
           "wo": (H * dec["v_head_dim"], D)}
    if group == "dense":
        F = m["d_ff_dense"]
        return dict(out, w1=(D, F), w3=(D, F), w2=(F, D))
    E, F, S = m["n_experts"], m["d_expert"], m["d_shared"]
    Er = m.get("n_routed") or E
    return dict(out, router=(D, Er), w1=(E, D, F), w3=(E, D, F),
                w2=(E, F, D), shared_w1=(D, S), shared_w3=(D, S),
                shared_w2=(S, D))


def placement(dec: dict):
    """Where the program keeps each drawn weight (``bench/system.py``
    ``place``): both layer stacks, the embedding and the untied head."""
    leaves = {("emb",): ("emb",), ("unemb",): ("unemb",)}
    ones = {("final_norm", "scale")}
    for stack, group, _, _ in _stacks(dec):
        leaves.update({(stack, "attn", n): (group, n) for n in ATTN})
        ones |= {(stack, "ln_attn", "scale"), (stack, "ln_ffn", "scale"),
                 (stack, "attn", "kv_norm", "scale")}
        if group == "dense":
            leaves.update({(stack, "ffn", n): (group, n) for n in FFN})
        else:
            leaves.update({(stack, "moe", n): (group, n)
                           for n in ("router",) + FFN})
            leaves.update({(stack, "moe", "shared", n): (group, "shared_" + n)
                           for n in FFN})
    return leaves, ones


def _served_dtype(dec):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dec["dtype"]]


def _draw_layer(key, layer, dec, group):
    """Layer ``layer``'s matrices (of stack ``group``): He-normal over their
    rows, the router in float32 and the rest in the served dtype."""
    dtype = _served_dtype(dec)
    key = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {n: (jax.random.normal(jax.random.fold_in(key, i), s)
                * (2.0 / s[-2]) ** 0.5).astype(
                    jnp.float32 if n == "router" else dtype)
            for i, (n, s) in enumerate(_shapes(dec, group).items())}


def _draw_embedding(key, dec):
    V, D = dec["vocab_size"], dec["d_model"]
    return (jax.random.normal(jax.random.fold_in(key, 0), (V, D))
            * 0.02).astype(_served_dtype(dec))


def _draw_head(key, dec):
    V, D = dec["vocab_size"], dec["d_model"]
    return (jax.random.normal(jax.random.fold_in(key, 2), (D, V))
            * (2.0 / D) ** 0.5).astype(_served_dtype(dec))


@functools.partial(jax.jit, static_argnums=1)
def _weights(key, dec_json):
    dec = json.loads(dec_json)
    w = {"emb": _draw_embedding(key, dec), "unemb": _draw_head(key, dec)}
    for _, group, lo, hi in _stacks(dec):
        w[group] = jax.vmap(lambda i: _draw_layer(key, i, dec, group))(
            jnp.arange(lo, hi))
    return w


def _static(dec: dict) -> str:
    return json.dumps(dec, sort_keys=True)


def weights_from_key(dec: dict, key) -> dict:
    """Every weight of the decoder from the seed's key
    (``jax.random.key(seed)``), in one jitted call: ``emb`` (vocab_size,
    D), ``unemb`` (D, vocab_size), and each stack's matrices (``dense``,
    ``moe``) stacked over its layers.  RMSNorm scales are one and are not
    drawn."""
    return _weights(key, _static(dec))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_weights(key, layer, dec_json, group):
    """Layer ``layer`` of :func:`weights_from_key`, widened to float32."""
    w = _draw_layer(key, layer, json.loads(dec_json), group)
    return {n: a.astype(jnp.float32) for n, a in w.items()}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _vocabulary(key, dec_json, V):
    """The embedding, and the head's first ``V`` columns, in float32."""
    dec = json.loads(dec_json)
    return (_draw_embedding(key, dec).astype(jnp.float32),
            _draw_head(key, dec)[:, :V].astype(jnp.float32))


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mscale(factor, mscale):
    """DeepSeek-V2's ``yarn_get_mscale``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn_inv_freq(dim, theta, y):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse
    frequencies."""
    def correction_dim(rotations):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]
    mask = 1.0 - ramp
    return jnp.asarray(inter * (1.0 - mask) + extra * mask, jnp.float32)


def _rope(x, pos, inv_freq, m, heads):
    """x (..., P, heads, d) or, without ``heads``, (..., P, d); pos (P,):
    rotate interleaved pairs, cos and sin scaled by ``m``."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq  # (P, d/2)
    if heads:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _swiglu(h, w1, w3, w2, fp8):
    a = _mm("...d,df->...f", h, w1, fp8)
    b = _mm("...d,df->...f", h, w3, fp8)
    return _mm("...f,fd->...d", jax.nn.silu(a) * b, w2, fp8)


def _experts(w, h, m, fp8):
    """The held experts' part of an expert layer, plus the shared expert,
    for rows h (..., D)."""
    probs = jax.nn.softmax(_mm("...d,de->...e", h, w["router"], fp8),
                           axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m["top_k"])
    held = m.get("expert_offset", 0) + jnp.arange(m["n_experts"])
    gate = jnp.sum(jnp.where(top_i[..., None] == held, top_w[..., None],
                             0.0), axis=-2)  # (..., E)
    a = _mm("...d,edf->...ef", h, w["w1"], fp8)
    b = _mm("...d,edf->...ef", h, w["w3"], fp8)
    y = _mm("...ef,efd->...ed", jax.nn.silu(a) * b, w["w2"], fp8)
    out = jnp.sum(gate[..., None] * y, axis=-2)
    return out + _swiglu(h, w["shared_w1"], w["shared_w3"], w["shared_w2"],
                         fp8)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(w, xh, xs, dec_json, fp8):
    """One layer over the histories xh (N, S, D) and the beams' SID tokens
    xs (N, M, T, D), which sit at positions S .. S+T-1 after the history."""
    dec = json.loads(dec_json)
    N, S, D = xh.shape
    _, M, T, _ = xs.shape
    H, r = dec["n_heads"], dec["kv_lora_rank"]
    nope, rope, vd = (dec["qk_nope_head_dim"], dec["qk_rope_head_dim"],
                      dec["v_head_dim"])
    eps, theta = float(dec["norm_eps"]), float(dec["rope_theta"])
    y = dec["rope_scaling"]
    inv_freq = _yarn_inv_freq(rope, theta, y)
    m_rope = (_mscale(y["factor"], y["mscale"])
              / _mscale(y["factor"], y["mscale_all_dim"]))
    scale = ((nope + rope) ** -0.5
             * _mscale(y["factor"], y["mscale_all_dim"]) ** 2)

    def project(x, pos):
        """q (..., P, H, nope+rope), k (..., P, H, nope+rope), v."""
        h = _rms(x, eps)
        q = _mm("...d,de->...e", h, w["wq"], fp8)
        q = q.reshape(x.shape[:-1] + (H, nope + rope))
        q = jnp.concatenate(
            [q[..., :nope],
             _rope(q[..., nope:], pos, inv_freq, m_rope, True)], -1)
        kv_a = _mm("...d,de->...e", h, w["w_kv_a"], fp8)
        c = _rms(kv_a[..., :r], eps)
        k_pe = _rope(kv_a[..., r:], pos, inv_freq, m_rope, False)
        kv = _mm("...l,le->...e", c, w["w_kv_b"], fp8)
        kv = kv.reshape(x.shape[:-1] + (H, nope + vd))
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[..., None, :], kv.shape[:-1] + (rope,))],
            -1)
        return q, k, kv[..., nope:]

    def ffn(x):
        h = _rms(x, eps)
        if "router" in w:
            return _experts(w, h, dec["moe"], fp8)
        return _swiglu(h, w["w1"], w["w3"], w["w2"], fp8)

    q, k, v = project(xh, jnp.arange(S))
    s = _mm("nqhd,nkhd->nhqk", q, k, fp8) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("nhqk,nkhd->nqhd", p, v, fp8).reshape(N, S, H * vd)
    xh_out = xh + _mm("nse,ed->nsd", o, w["wo"], fp8)
    xh_out = xh_out + ffn(xh_out)

    qs, ks, vs = project(xs, S + jnp.arange(T))
    s_hist = _mm("nmthd,nshd->nmhts", qs, k, fp8) * scale
    s_own = _mm("nmthd,nmuhd->nmhtu", qs, ks, fp8) * scale
    own = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s_own = jnp.where(own, s_own, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s_hist, s_own], axis=-1), axis=-1)
    o = (_mm("nmhts,nshd->nmthd", p[..., :S], v, fp8)
         + _mm("nmhtu,nmuhd->nmthd", p[..., S:], vs, fp8))
    xs = xs + _mm("nmte,ed->nmtd", o.reshape(N, M, T, H * vd), w["wo"], fp8)
    xs = xs + ffn(xs)
    return xh_out, xs


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(head, xh_last, xs, eps, fp8):
    """Level log-probs (N, M, T+1, V) from the final hidden states."""
    lp0 = jax.nn.log_softmax(_mm("nd,dv->nv", _rms(xh_last, eps), head, fp8))
    lps = jax.nn.log_softmax(_mm("nmtd,dv->nmtv", _rms(xs, eps), head, fp8))
    N, M = xs.shape[:2]
    lp0 = jnp.broadcast_to(lp0[:, None, None, :], (N, M, 1, head.shape[1]))
    return jnp.concatenate([lp0, lps], axis=2)


def logprobs(cfg: dict, seed: int, prompts: np.ndarray, beams: np.ndarray,
             precision: str = "f32") -> np.ndarray:
    """(N, M, L, V) float32 log-probs over the SID vocabulary at every level
    of every served beam, for prompts (N, S) and served beams (N, M, L)."""
    dec = cfg["decoder"]
    dec_json = _static(dec)
    fp8 = {"f32": False, "fp8": True}[precision]
    key = jax.random.key(seed)
    with jax.default_matmul_precision("highest"):
        emb, head = _vocabulary(key, dec_json, cfg["vocab"])
        xh = jnp.take(emb, jnp.asarray(prompts, jnp.int32), axis=0)
        xs = jnp.take(emb, jnp.asarray(beams[:, :, :-1], jnp.int32), axis=0)
        del emb
        for _, group, lo, hi in _stacks(dec):
            for layer in range(lo, hi):
                w = _layer_weights(key, layer, dec_json, group)
                xh, xs = _layer(w, xh, xs, dec_json, fp8)
                del w
        out = _head(head, xh[:, -1], xs, float(dec["norm_eps"]), fp8)
        return np.asarray(jax.device_get(out))
