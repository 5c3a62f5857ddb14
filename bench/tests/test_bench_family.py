"""A decoder family enters the harness through its own files alone.

``bench/tests/_mla_moe.py`` stands for the files a new family adds (its
reference with its weight map, and its counts), registered by name the way
the harness finds a configuration's ``reference``: the harness builds the
program's config with a nested ``moe`` group set from the file, places every
weight in both layer stacks and the untied head, and counts the work by the
family's own counts, with no edit to its modules.
"""
import dataclasses
import json
import math
import sys

import jax
import numpy as np
import pytest

from bench import spec, system, work
from bench.tests import _mla_moe, _tiny

SEED = 2**31 + 101


@pytest.fixture
def family(monkeypatch):
    for package in ("bench.references", "bench.counts"):
        monkeypatch.setitem(sys.modules, f"{package}.{_mla_moe.NAME}",
                            _mla_moe)
    return _mla_moe.CONFIG


def _spec(cfg):
    from repro.models import transformer

    return transformer.param_specs(system.program_config(cfg))


def test_program_config_sets_a_nested_group_from_the_file():
    base = system.program_config({"decoder": {"base": "deepseek-v2-lite-16b"}})
    pcfg = system.program_config(_mla_moe.CONFIG)
    assert pcfg.attention == "mla" and pcfg.n_layers == 2
    assert type(pcfg.moe) is type(base.moe)
    assert dataclasses.asdict(pcfg.moe) == dict(
        dataclasses.asdict(base.moe), **_mla_moe.CONFIG["decoder"]["moe"])


@pytest.mark.parametrize("decoder, error, name", [
    ({"base": "deepseek-v2-lite-16b", "n_layer": 2}, KeyError, "n_layer"),
    ({"base": "deepseek-v2-lite-16b", "moe": {"n_expert": 8}}, KeyError,
     "moe.n_expert"),
    ({"base": "deepseek-v2-lite-16b", "rope_theta": {"base": 1e4}},
     TypeError, "rope_theta"),
    ({"base": "static-gr", "moe": {"n_experts": 8}}, TypeError, "moe"),
])
def test_a_key_that_names_no_field_is_an_error(decoder, error, name):
    with pytest.raises(error, match=name):
        system.program_config({"decoder": decoder})


@pytest.mark.parametrize("cfg", [_tiny.CONFIG, _mla_moe.CONFIG],
                         ids=["dense_gqa", "mla_moe"])
def test_every_leaf_is_the_drawn_weight(family, cfg):
    """Each program leaf holds, bit for bit, the weight the map names, and
    every RMSNorm scale holds ones."""
    ref = system.reference(cfg)
    dec = cfg["decoder"]
    p = system.params(cfg, SEED)
    w = ref.weights_from_key(dec, jax.random.key(SEED))
    leaves, ones = ref.placement(dec)
    got = {tuple(k.key for k in kp): a
           for kp, a in jax.tree_util.tree_leaves_with_path(p)}
    assert set(got) == set(leaves) | (ones & set(got))
    for path, a in got.items():
        if path in ones:
            assert np.all(np.asarray(a) == 1), path
            continue
        want = w
        for k in leaves[path]:
            want = want[k]
        assert a.dtype == want.dtype and np.array_equal(
            np.asarray(a, "f4"), np.asarray(want, "f4")), path


def test_two_stacks_with_the_same_names_are_placed_apart(family):
    p = system.params(family, SEED)
    assert set(p) == {"emb", "unemb", "final_norm", "dense_layers",
                      "moe_layers"}
    dense = np.asarray(p["dense_layers"]["attn"]["wq"], "f4")
    sparse = np.asarray(p["moe_layers"]["attn"]["wq"], "f4")
    assert dense.shape == sparse.shape and not np.array_equal(dense, sparse)
    assert p["moe_layers"]["moe"]["router"].dtype == np.float32
    assert p["moe_layers"]["moe"]["w1"].shape == (1, 8, 64, 32)


@pytest.mark.parametrize("edit, error, name", [
    ("drop", KeyError, "unemb"),
    ("add", KeyError, "moe_layers/moe/bias"),
    ("widen", ValueError, "moe_layers/attn/w_kv_b"),
])
def test_a_changed_layout_of_another_family_fails_loudly(edit, error, name):
    cfg = _mla_moe.CONFIG
    spec_ = _spec(cfg)
    w = _mla_moe.weights_from_key(cfg["decoder"], jax.random.key(SEED))
    leaves, ones = _mla_moe.placement(cfg["decoder"])
    system.place(spec_, w, leaves, ones)  # the layout as it stands places
    spec_ = jax.tree.map(lambda s: s, spec_)
    if edit == "drop":
        del spec_["unemb"]
    elif edit == "add":
        spec_["moe_layers"]["moe"]["bias"] = jax.ShapeDtypeStruct(
            (1, 8), "float32")
    else:
        attn = spec_["moe_layers"]["attn"]
        attn["w_kv_b"] = jax.ShapeDtypeStruct(attn["w_kv_b"].shape, "float32")
    with pytest.raises(error, match=name):
        system.place(spec_, w, leaves, ones)


def test_a_map_to_a_weight_never_drawn_fails_loudly():
    cfg = _mla_moe.CONFIG
    w = _mla_moe.weights_from_key(cfg["decoder"], jax.random.key(SEED))
    leaves, ones = _mla_moe.placement(cfg["decoder"])
    leaves = dict(leaves)
    leaves[("unemb",)] = ("head",)
    with pytest.raises(KeyError, match="unemb.*head"):
        system.place(_spec(cfg), w, leaves, ones)


def test_work_counts_are_found_by_the_family_name(family):
    r = work.retrieval(family)
    assert isinstance(r.dec, _mla_moe.Counts)
    # one row reaches top_k of 8 experts; the level's 16 rows nearly all
    one, level = r.dec.weight_bytes_read(1), r.dec.weight_bytes_read(16)
    expert = 3 * 64 * 32 * 2
    assert one == r.dec.weight_bytes - 6 * expert
    assert level == r.dec.weight_bytes
    assert r.level_bytes(1, 2) == level + 2 * (
        r.history_kv_bytes() + 8 * r.dec.kv_bytes_per_token)
    assert r.prefill_bytes(2) == r.dec.weight_bytes + 2 * r.history_kv_bytes()
    assert r.dec.kv_bytes_per_token == 2 * (32 + 8) * 2


PROD = json.loads((spec.BENCH / "configs" / "static-gr-3b.prod.json")
                  .read_text())


@pytest.mark.parametrize("cfg", [PROD, _tiny.CONFIG, _mla_moe.CONFIG],
                         ids=["static-gr-3b.prod", "dense_gqa", "mla_moe"])
def test_counted_weights_are_the_programs(family, cfg):
    """A family's parameter and byte counts are the program's tree's."""
    leaves = jax.tree.leaves(_spec(cfg))
    r = work.retrieval(cfg)
    assert r.dec.params == sum(math.prod(s.shape) for s in leaves)
    assert r.dec.weight_bytes == sum(
        math.prod(s.shape) * s.dtype.itemsize for s in leaves)
