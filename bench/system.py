"""The system under test, built by the program's own serving builders.

A configuration file names the program's decoder config it starts from
(``decoder.base``) and every field it sets; the engine comes from the
traffic mix.  The weights and the constraint SIDs are the benchmark's data,
drawn from the seed: the weights by the reference's own generator, placed
leaf by leaf in the program's parameter tree (:data:`PROGRAM_LEAVES`), so a
change of the program's layout fails here loudly and cannot shift what the
reference computes.  The index, policy, retriever and engine come from
``repro.launch.serve`` with their defaults, so a cell measures whatever path
the program chooses by default.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

# the program's parameter leaves, by path, and the benchmark weight each
# holds; RMSNorm scales hold ones
PROGRAM_LEAVES = {
    ("emb",): "emb",
    ("dense_layers", "attn", "wq", "w"): "wq",
    ("dense_layers", "attn", "wk", "w"): "wk",
    ("dense_layers", "attn", "wv", "w"): "wv",
    ("dense_layers", "attn", "wo", "w"): "wo",
    ("dense_layers", "ffn", "w1"): "w1",
    ("dense_layers", "ffn", "w3"): "w3",
    ("dense_layers", "ffn", "w2"): "w2",
}
# the one draw that fixes the trie's shape; the seed relabels its tokens
SHAPE_SEED = [0, 0]
NORM_SCALES = {("final_norm", "scale"), ("dense_layers", "ln_attn", "scale"),
               ("dense_layers", "ln_ffn", "scale")}


def constraint_sids(cfg: dict, seed: int) -> np.ndarray:
    """The cell's constraint set: ``constraint_sids`` SIDs whose trie has
    the same shape for every seed (the same nodes per level and the same
    widest branching, so one compiled step serves every seed): one fixed
    draw, with the tokens of each level relabeled by a permutation of the
    vocabulary drawn from the seed."""
    V, L = cfg["vocab"], cfg["sid_length"]
    shape = np.random.default_rng(SHAPE_SEED).integers(
        0, V, (cfg["constraint_sids"], L))
    rng = np.random.default_rng([seed, 0])
    perms = np.stack([rng.permutation(V) for _ in range(L)])
    return perms[np.arange(L), shape]


def program_config(cfg: dict):
    """The program's decoder config with the file's fields applied."""
    from repro.configs import get_bundle

    dec = dict(cfg["decoder"])
    base = get_bundle(dec.pop("base")).config
    return dataclasses.replace(base, **dec)


def place(spec, w: dict):
    """The program's parameter tree ``spec`` (shapes) filled from the
    benchmark's weights ``w``; a leaf of either side left unmatched, or of
    another shape or dtype, is an error."""
    import jax
    import jax.numpy as jnp

    drawn = dict(w["layers"], emb=w["emb"])
    used = set()

    def leaf(path, s):
        p = tuple(k.key for k in path)
        if p in NORM_SCALES:
            return jnp.ones(s.shape, s.dtype)
        if p not in PROGRAM_LEAVES:
            raise KeyError(f"program parameter {'/'.join(p)} has no "
                           "benchmark weight")
        a = drawn[PROGRAM_LEAVES[p]]
        if a.shape != s.shape or a.dtype != s.dtype:
            raise ValueError(f"program parameter {'/'.join(p)} is "
                             f"{s.dtype}{list(s.shape)}, the benchmark's "
                             f"{a.dtype}{list(a.shape)}")
        used.add(PROGRAM_LEAVES[p])
        return a

    out = jax.tree_util.tree_map_with_path(leaf, spec)
    if used != set(drawn):
        raise KeyError(f"benchmark weights {sorted(set(drawn) - used)} have "
                       "no place in the program")
    return out


def params(cfg: dict, seed: int):
    """The served weights, made on the device by one jitted call."""
    import jax

    from repro.models import transformer

    ref = importlib.import_module(f"bench.references.{cfg['reference']}")
    spec = transformer.param_specs(program_config(cfg))
    return jax.jit(lambda key: place(spec, ref.weights_from_key(
        cfg["decoder"], key)))(jax.random.key(seed))


@dataclasses.dataclass
class System:
    engine: object
    retriever: object
    slots: int  # requests per chip: the batch


def build(cfg: dict, engine: str, seed: int, sids: np.ndarray) -> System:
    from repro.launch import serve

    pcfg = program_config(cfg)
    geo = serve.Geometry(
        vocab=cfg["vocab"], sid_length=cfg["sid_length"], beam=cfg["beam"],
        batch=int(cfg["requests_per_chip"][engine]), history=cfg["history"],
        dense_d=cfg["dense_d"], constraints=len(sids))
    tm = serve.build_index(sids, geo)
    retriever = serve.build_retriever(params(cfg, seed), pcfg,
                                      serve.build_policy(tm), geo)
    eng = serve.build_engine(engine, retriever, geo)
    return System(eng, retriever, geo.batch)
