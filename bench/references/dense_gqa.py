"""Plain float32 reference of a dense GQA decoder with a tied embedding.

The architecture the configuration's ``decoder`` group describes: token
embedding; per layer RMSNorm -> grouped-query attention with interleaved
rotary embeddings -> residual, RMSNorm -> SwiGLU -> residual; a final
RMSNorm; logits against the embedding matrix, of which the first ``vocab``
(the SID tokens) are scored.  Everything is computed in float32 with
``highest`` matmul precision, layer by layer, so that it fits one chip
after the served program has been freed.

Weights are random data made from the seed by this module's generator
(an embedding of N(0, 0.02^2); He-normal matrices over their rows; RMSNorm
scales of one).  :func:`weights` makes all of them in the served dtype for
the system under test (``bench/system.py`` places them in the program's
parameter tree by :func:`placement`); the reference draws the same values again, layer by layer,
and widens them to float32.  It takes no array from the program.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale before it is multiplied.

:func:`logprobs` returns, for each request and each of its served beams,
the log-softmax over the SID vocabulary at every level of that beam's
prefix: level 0 after the history, level ``l`` after the beam's first ``l``
tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8 e4m3

# the program's parameter leaves, by path, and the drawn weight each holds,
# by its path in weights_from_key's tree; RMSNorm scales hold ones
PROGRAM_LEAVES = {
    ("emb",): ("emb",),
    ("dense_layers", "attn", "wq", "w"): ("layers", "wq"),
    ("dense_layers", "attn", "wk", "w"): ("layers", "wk"),
    ("dense_layers", "attn", "wv", "w"): ("layers", "wv"),
    ("dense_layers", "attn", "wo", "w"): ("layers", "wo"),
    ("dense_layers", "ffn", "w1"): ("layers", "w1"),
    ("dense_layers", "ffn", "w3"): ("layers", "w3"),
    ("dense_layers", "ffn", "w2"): ("layers", "w2"),
}
NORM_SCALES = {("final_norm", "scale"), ("dense_layers", "ln_attn", "scale"),
               ("dense_layers", "ln_ffn", "scale")}


def placement(dec: dict):
    """Where the program keeps each drawn weight (``bench/system.py``
    ``place``): the same map for every configuration of this family."""
    return PROGRAM_LEAVES, NORM_SCALES


def _served_dtype(dec):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dec["dtype"]]


def _dims(dec):
    """The static arguments of the weight generators."""
    return (dec["n_layers"], dec["vocab_size"], dec["d_model"], dec["n_heads"],
            dec["n_kv_heads"], dec["head_dim"], dec["d_ff"],
            _served_dtype(dec))


def _shapes(D, H, KV, hd, F):
    return {"wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd),
            "wo": (H * hd, D), "w1": (D, F), "w3": (D, F), "w2": (F, D)}


def _draw_layer(key, layer, D, H, KV, hd, F, dtype):
    """One layer's matrices: He-normal over their rows, in ``dtype``."""
    key = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {n: (jax.random.normal(jax.random.fold_in(key, i), s)
                * (2.0 / s[0]) ** 0.5).astype(dtype)
            for i, (n, s) in enumerate(_shapes(D, H, KV, hd, F).items())}


def _draw_embedding(key, V, D, dtype):
    return (jax.random.normal(jax.random.fold_in(key, 0), (V, D))
            * 0.02).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _weights(key, n_layers, V, D, H, KV, hd, F, dtype):
    layers = jax.vmap(lambda i: _draw_layer(key, i, D, H, KV, hd, F, dtype))(
        jnp.arange(n_layers))
    return {"emb": _draw_embedding(key, V, D, dtype), "layers": layers}


def weights_from_key(dec: dict, key) -> dict:
    """Every weight of the decoder, in the served dtype, from the seed's
    key (``jax.random.key(seed)``), in one jitted call: ``emb``
    (vocab_size, D) and ``layers``, each matrix stacked over the layers.
    RMSNorm scales are one and are not drawn."""
    return _weights(key, *_dims(dec))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _layer_weights(key, layer, D, H, KV, hd, F, dtype):
    """Layer ``layer`` of :func:`weights`, widened to float32."""
    w = _draw_layer(key, layer, D, H, KV, hd, F, dtype)
    return {n: a.astype(jnp.float32) for n, a in w.items()}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _embedding(key, V, D, dtype):
    return _draw_embedding(key, V, D, dtype).astype(jnp.float32)


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


def _rope(x, pos, theta):
    """x (..., P, heads, hd), pos (P,): rotate interleaved pairs."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None, None] * freqs  # (P, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _swiglu(w, h, fp8):
    a = _mm("...d,df->...f", h, w["w1"], fp8)
    b = _mm("...d,df->...f", h, w["w3"], fp8)
    return _mm("...f,fd->...d", jax.nn.silu(a) * b, w["w2"], fp8)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _layer(w, xh, xs, H, KV, theta, eps, fp8):
    """One layer over the histories xh (N, S, D) and the beams' SID tokens
    xs (N, M, T, D), which sit at positions S .. S+T-1 after the history."""
    N, S, D = xh.shape
    _, M, T, _ = xs.shape
    hd = w["wq"].shape[1] // H
    G = H // KV
    scale = hd ** -0.5

    h = _rms(xh, eps)
    q = _rope(_mm("nsd,de->nse", h, w["wq"], fp8).reshape(N, S, H, hd),
              jnp.arange(S), theta)
    k = _rope(_mm("nsd,de->nse", h, w["wk"], fp8).reshape(N, S, KV, hd),
              jnp.arange(S), theta)
    v = _mm("nsd,de->nse", h, w["wv"], fp8).reshape(N, S, KV, hd)
    kr, vr = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = _mm("nqhd,nkhd->nhqk", q, kr, fp8) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("nhqk,nkhd->nqhd", p, vr, fp8).reshape(N, S, H * hd)
    xh = xh + _mm("nse,ed->nsd", o, w["wo"], fp8)
    xh = xh + _swiglu(w, _rms(xh, eps), fp8)

    pos = S + jnp.arange(T)
    h = _rms(xs, eps)
    q = _rope(_mm("nmtd,de->nmte", h, w["wq"], fp8).reshape(N, M, T, H, hd),
              pos, theta)
    ks = _rope(_mm("nmtd,de->nmte", h, w["wk"], fp8).reshape(
        N, M, T, KV, hd), pos, theta)
    vs = _mm("nmtd,de->nmte", h, w["wv"], fp8).reshape(N, M, T, KV, hd)
    ksr, vsr = jnp.repeat(ks, G, axis=3), jnp.repeat(vs, G, axis=3)
    s_hist = _mm("nmthd,nshd->nmhts", q, kr, fp8) * scale
    s_own = _mm("nmthd,nmuhd->nmhtu", q, ksr, fp8) * scale
    own = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s_own = jnp.where(own, s_own, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s_hist, s_own], axis=-1), axis=-1)
    o = (_mm("nmhts,nshd->nmthd", p[..., :S], vr, fp8)
         + _mm("nmhtu,nmuhd->nmthd", p[..., S:], vsr, fp8))
    xs = xs + _mm("nmte,ed->nmtd", o.reshape(N, M, T, H * hd), w["wo"], fp8)
    xs = xs + _swiglu(w, _rms(xs, eps), fp8)
    return xh, xs


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(emb, xh_last, xs, V, eps, fp8):
    """Level log-probs (N, M, T+1, V) from the final hidden states."""
    w = emb[:V].T
    lp0 = jax.nn.log_softmax(_mm("nd,dv->nv", _rms(xh_last, eps), w, fp8))
    lps = jax.nn.log_softmax(_mm("nmtd,dv->nmtv", _rms(xs, eps), w, fp8))
    N, M = xs.shape[:2]
    lp0 = jnp.broadcast_to(lp0[:, None, None, :], (N, M, 1, V))
    return jnp.concatenate([lp0, lps], axis=2)


def logprobs(cfg: dict, seed: int, prompts: np.ndarray, beams: np.ndarray,
             precision: str = "f32") -> np.ndarray:
    """(N, M, L, V) float32 log-probs over the SID vocabulary at every level
    of every served beam, for prompts (N, S) and served beams (N, M, L)."""
    dec = cfg["decoder"]
    fp8 = {"f32": False, "fp8": True}[precision]
    D, H, KV = dec["d_model"], dec["n_heads"], dec["n_kv_heads"]
    hd, F, V = dec["head_dim"], dec["d_ff"], cfg["vocab"]
    theta, eps = float(dec["rope_theta"]), float(dec["norm_eps"])
    dtype = _served_dtype(dec)
    key = jax.random.key(seed)
    with jax.default_matmul_precision("highest"):
        emb = _embedding(key, dec["vocab_size"], D, dtype)
        xh = jnp.take(emb, jnp.asarray(prompts, jnp.int32), axis=0)
        xs = jnp.take(emb, jnp.asarray(beams[:, :, :-1], jnp.int32), axis=0)
        for layer in range(dec["n_layers"]):
            w = _layer_weights(key, layer, D, H, KV, hd, F, dtype)
            xh, xs = _layer(w, xh, xs, H, KV, theta, eps, fp8)
            del w
        out = _head(emb, xh[:, -1], xs, V, eps, fp8)
        return np.asarray(jax.device_get(out))
