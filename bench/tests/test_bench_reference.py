"""The plain reference against the program, and its control, on the CPU.

At float32 the reference and the program compute the same function from the
same seed, so they agree to rounding: that pins the reference's
architecture and its copy of the weights' key tree.  At bfloat16 a sound
program stays inside the tiny cell's limits, and the float8 control (the
reference computed in the next precision down) does not.
"""
import numpy as np
import pytest

from bench import check, system
from bench.references import dense_gqa
from bench.tests import _tiny

SEED = 2**31 + 17


def _serve(cfg, seed, n=4):
    sids = system.constraint_sids(cfg, seed)
    s = system.build(cfg, "batch", seed, sids)
    rng = np.random.default_rng([seed, 9])
    prompts = rng.integers(0, cfg["vocab"], (n, cfg["history"]))
    out = [s.retriever.retrieve(prompts[i:i + 2].astype(np.int32))
           for i in range(0, n, 2)]
    beams = np.concatenate([o[0] for o in out]).astype(np.int64)
    scores = np.concatenate([o[1] for o in out])
    return check.ConstraintSet(sids), prompts, beams, scores


def test_reference_weights_are_the_programs():
    """The program serves the reference generator's weights, leaf by leaf,
    and the reference draws the same values again layer by layer."""
    import jax

    cfg = _tiny.CONFIG
    dec = cfg["decoder"]
    p = system.params(cfg, SEED)
    key = jax.random.key(SEED)
    dtype = dense_gqa._served_dtype(dec)
    for i in range(dec["n_layers"]):
        w = dense_gqa._layer_weights(key, i, 64, 4, 2, 16, 128, dtype)
        layer = jax.tree.map(lambda a: a[i].astype("f4"), p["dense_layers"])
        got = dict(layer["ffn"], **{n: layer["attn"][n]["w"]
                                    for n in ("wq", "wk", "wv", "wo")})
        assert set(got) == set(w)
        for n in w:
            assert np.array_equal(w[n], got[n]), n
    emb = dense_gqa._embedding(key, 34, 64, dtype)
    assert np.array_equal(emb, p["emb"].astype("f4"))
    scales = [a for path, a in jax.tree_util.tree_leaves_with_path(p)
              if path[-1].key == "scale"]
    assert len(scales) == 3 and all(np.all(a == 1) for a in scales)


def test_a_changed_program_layout_fails_loudly():
    import jax

    from repro.models import transformer

    cfg = _tiny.CONFIG
    spec = transformer.param_specs(system.program_config(cfg))
    w = dense_gqa.weights_from_key(cfg["decoder"], jax.random.key(SEED))
    leaves, ones = dense_gqa.placement(cfg["decoder"])
    extra = dict(spec, unemb=jax.ShapeDtypeStruct((64, 34), "bfloat16"))
    with pytest.raises(KeyError, match="unemb"):
        system.place(extra, w, leaves, ones)
    short = dict(spec)
    del short["emb"]
    with pytest.raises(KeyError, match="emb"):
        system.place(short, w, leaves, ones)
    wq = spec["dense_layers"]["attn"]["wq"]["w"]
    widened = jax.tree.map(lambda s: s, spec)
    widened["dense_layers"]["attn"]["wq"]["w"] = jax.ShapeDtypeStruct(
        wq.shape, "float32")
    with pytest.raises(ValueError, match="wq"):
        system.place(widened, w, leaves, ones)


def test_reference_is_the_program_at_float32():
    cfg = _tiny.cell("bulk", dtype="float32").config
    cs, prompts, beams, scores = _serve(cfg, SEED)
    lp = dense_gqa.logprobs(cfg, SEED, prompts, beams)
    assert check.violations(cs, beams, scores) == 0
    assert check.score_gap(beams, scores, lp) < 1e-4
    assert check.select_gap(cs, beams, lp, cfg["beam"]) < 1e-4


@pytest.mark.parametrize("seed", [SEED, 5, 2**40 + 3])
def test_control_fails_where_the_program_passes(seed):
    cfg = _tiny.CONFIG
    lim = cfg["limits"]
    cs, prompts, beams, scores = _serve(cfg, seed)
    lp = dense_gqa.logprobs(cfg, seed, prompts, beams)
    sound = {"score_gap": check.score_gap(beams, scores, lp),
             "select_gap": check.select_gap(cs, beams, lp, cfg["beam"])}
    assert check.judge(sound, {k: lim[k] for k in sound}), sound
    lp8 = dense_gqa.logprobs(cfg, seed, prompts, beams, precision="fp8")
    served8 = np.take_along_axis(lp8, beams[..., None], -1)[..., 0].sum(-1)
    control = {"score_gap": check.score_gap(beams, served8, lp),
               "select_gap": check.select_gap(cs, beams, lp, cfg["beam"],
                                              lp_pick=lp8)}
    assert not check.judge(control, {k: lim[k] for k in control}), control


def test_constraint_set_lookups():
    sids = np.array([[1, 2, 3], [1, 2, 4], [1, 5, 0], [7, 0, 0]])
    cs = check.ConstraintSet(sids)
    assert cs.contains(np.array([[1, 2, 4], [1, 2, 5]])).tolist() == [
        True, False]
    assert cs.children(()).tolist() == [1, 7]
    assert cs.children((1,)).tolist() == [2, 5]
    assert cs.children((1, 2)).tolist() == [3, 4]
    beams = np.array([[[1, 2, 3], [1, 2, 3], [9, 9, 9]]])
    assert check.violations(cs, beams, np.zeros((1, 3))) == 2
