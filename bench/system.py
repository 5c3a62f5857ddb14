"""The system under test, built by the program's own serving builders.

A configuration file names the program's decoder config it starts from
(``decoder.base``) and every field it sets; the engine comes from the
traffic mix.  The weights and the constraint SIDs are the benchmark's data,
drawn from the seed: the weights by the generator of the configuration's
reference (``bench/references/<reference>.py``), placed leaf by leaf in the
program's parameter tree by the map that reference declares
(``placement``), so a change of the program's layout fails here loudly and
cannot shift what the reference computes.  The index, policy, retriever
and engine come from ``repro.launch.serve`` with their defaults, so a cell
measures whatever path the program chooses by default.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

# the one draw that fixes the trie's shape; the seed relabels its tokens
SHAPE_SEED = [0, 0]


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


def _replace(obj, fields: dict, where: str):
    """``obj`` (a dataclass) with ``fields`` set; a dict sets the fields of
    the nested dataclass it names.  A key that names no field, or a dict for
    a field that holds no dataclass, is an error."""
    known = {f.name for f in dataclasses.fields(obj)}
    out = {}
    for k, v in fields.items():
        if k not in known:
            raise KeyError(f"decoder field {where}{k} names no field of "
                           f"{type(obj).__name__}")
        if isinstance(v, dict):
            group = getattr(obj, k)
            if not dataclasses.is_dataclass(group):
                raise TypeError(f"decoder field {where}{k} is a group, but "
                                f"{type(obj).__name__}.{k} holds {group!r}")
            v = _replace(group, v, f"{where}{k}.")
        out[k] = v
    return dataclasses.replace(obj, **out)


def program_config(cfg: dict):
    """The program's decoder config with the file's fields applied."""
    from repro.configs import get_bundle

    dec = dict(cfg["decoder"])
    base = get_bundle(dec.pop("base")).config
    return _replace(base, dec, "")


def reference(cfg: dict):
    """The configuration's plain reference module, found by its name."""
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def place(spec, w, leaves: dict, ones: set):
    """The program's parameter tree ``spec`` (shapes) filled from the
    benchmark's weights ``w``: the program leaf at path ``p`` holds the
    weight at path ``leaves[p]`` of ``w``, and a leaf in ``ones`` (an
    RMSNorm scale) holds ones.  A leaf of either side left unmatched, or of
    another shape or dtype, is an error."""
    import jax
    import jax.numpy as jnp

    def path(kp):
        return tuple(k.key for k in kp)

    drawn = {path(kp): a for kp, a in jax.tree_util.tree_leaves_with_path(w)}
    used = set()

    def leaf(kp, s):
        p = path(kp)
        if p in ones:
            return jnp.ones(s.shape, s.dtype)
        if p not in leaves:
            raise KeyError(f"program parameter {'/'.join(p)} has no "
                           "benchmark weight")
        q = leaves[p]
        if q not in drawn:
            raise KeyError(f"program parameter {'/'.join(p)} is mapped to "
                           f"{'/'.join(q)}, which the reference does not draw")
        a = drawn[q]
        if a.shape != s.shape or a.dtype != s.dtype:
            raise ValueError(f"program parameter {'/'.join(p)} is "
                             f"{s.dtype}{list(s.shape)}, the benchmark's "
                             f"{'/'.join(q)} {a.dtype}{list(a.shape)}")
        used.add(q)
        return a

    out = jax.tree_util.tree_map_with_path(leaf, spec)
    if used != set(drawn):
        left = sorted("/".join(q) for q in set(drawn) - used)
        raise KeyError(f"benchmark weights {left} have no place in the "
                       "program")
    return out


def params(cfg: dict, seed: int):
    """The served weights, made on the device by one jitted call."""
    import jax

    from repro.models import transformer

    ref = reference(cfg)
    spec = transformer.param_specs(program_config(cfg))
    leaves, ones = ref.placement(cfg["decoder"])
    return jax.jit(lambda key: place(spec, ref.weights_from_key(
        cfg["decoder"], key), leaves, ones))(jax.random.key(seed))


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
