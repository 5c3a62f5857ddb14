"""DeepSeek-V2 (latent attention, sparse experts) in the harness, on the CPU
at a tiny size: the plain reference against the served program, its
control, the weight map, and the configuration's work counts."""
import dataclasses
import json
import math
import time

import jax
import numpy as np
import pytest

from bench import check, run, spec, system, work
from bench.references import deepseek_v2

SEED = 2**31 + 23
FILE = json.loads((spec.BENCH / "configs" / "deepseek-v2-lite.ep8.json")
                  .read_text())

# Every width of the family at a tiny size: 3 layers (one dense), 2 of 8
# routed experts held from expert 2, top-3 over all 8, YaRN as published.
# Limits read from the program at this size on the CPU: sound bf16 runs
# give a score gap of 0.15-0.36 nats and a selection gap of 0-0.105 (8
# seeds); the float8 control 1.70-3.06 and 0.38-2.12.
TINY = {
    "decoder": {
        "base": "deepseek-v2-lite-16b", "n_layers": 3, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 4, "d_ff": 96, "vocab_size": 34,
        "attention": "mla", "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "tie_embeddings": False,
        "rope_theta": 10000.0,
        "rope_scaling": FILE["decoder"]["rope_scaling"],
        "norm_eps": 1e-06, "dtype": "bfloat16",
        "moe": {"n_experts": 2, "n_routed": 8, "expert_offset": 2,
                "top_k": 3, "d_expert": 32, "norm_topk_prob": False,
                "n_shared": 2, "d_shared": 32,
                "first_dense_layers": 1, "d_ff_dense": 96}},
    "reference": "deepseek_v2", "vocab": 32, "sid_length": 4, "beam": 8,
    "history": 16, "dense_d": 2, "constraint_sids": 400,
    "requests_per_chip": {"batch": 2}, "check_requests": 4,
    "limits": {"violations": 0, "score_gap": 0.8, "select_gap": 0.3},
}


def _tiny(**decoder):
    return dict(TINY, decoder=dict(TINY["decoder"], **decoder))


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


def test_reference_is_the_program_at_float32():
    cfg = _tiny(dtype="float32")
    cs, prompts, beams, scores = _serve(cfg, SEED)
    lp = deepseek_v2.logprobs(cfg, SEED, prompts, beams)
    assert check.violations(cs, beams, scores) == 0
    assert check.score_gap(beams, scores, lp) < 1e-4
    assert check.select_gap(cs, beams, lp, cfg["beam"]) < 1e-4


@pytest.mark.parametrize("seed", [2**40 + 5])
def test_control_fails_where_the_program_passes(seed):
    lim = TINY["limits"]
    cs, prompts, beams, scores = _serve(TINY, seed)
    lp = deepseek_v2.logprobs(TINY, seed, prompts, beams)
    sound = {"score_gap": check.score_gap(beams, scores, lp),
             "select_gap": check.select_gap(cs, beams, lp, TINY["beam"])}
    assert check.judge(sound, {k: lim[k] for k in sound}), sound
    lp8 = deepseek_v2.logprobs(TINY, seed, prompts, beams, precision="fp8")
    served8 = np.take_along_axis(lp8, beams[..., None], -1)[..., 0].sum(-1)
    control = {"score_gap": check.score_gap(beams, served8, lp),
               "select_gap": check.select_gap(cs, beams, lp, TINY["beam"],
                                              lp_pick=lp8)}
    assert not check.judge(control, {k: lim[k] for k in control}), control


def test_placement_fills_both_stacks_and_the_head():
    """Each program leaf holds, bit for bit, the weight the map names (the
    untied head and both layer stacks), and the reference draws the same
    values again layer by layer."""
    dec = TINY["decoder"]
    p = system.params(TINY, SEED)
    w = deepseek_v2.weights_from_key(dec, jax.random.key(SEED))
    leaves, ones = deepseek_v2.placement(dec)
    got = {tuple(k.key for k in kp): a
           for kp, a in jax.tree_util.tree_leaves_with_path(p)}
    assert {path[0] for path in leaves} == {
        "emb", "unemb", "dense_layers", "moe_layers"}
    assert set(got) == set(leaves) | ones
    for path, a in got.items():
        if path in ones:
            assert np.all(np.asarray(a) == 1), path
            continue
        want = w
        for k in leaves[path]:
            want = want[k]
        assert a.dtype == want.dtype and np.array_equal(
            np.asarray(a, "f4"), np.asarray(want, "f4")), path
    dj = deepseek_v2._static(dec)
    for layer, (group, i) in enumerate([("dense", 0), ("moe", 0),
                                        ("moe", 1)]):
        redrawn = deepseek_v2._layer_weights(jax.random.key(SEED), layer, dj,
                                             group)
        for n, a in redrawn.items():
            assert np.array_equal(a, np.asarray(w[group][n][i], "f4")), n


def test_a_run_of_the_tiny_cell_is_correct():
    cell = dataclasses.replace(spec.load("v2lite-ep8.bulk"), config=TINY)
    out = run.run_cell(cell, SEED, 1.0, False, t_start=time.monotonic())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"retrievals_per_s", "setup_s"} <= set(out["metrics"])


def test_catalog_numbers_are_the_decoders():
    """The file's copy of the published config.json and its decoder group
    state the same model; the held experts are the one cut."""
    d, m = FILE["decoder"], FILE["decoder"]["moe"]
    assert (d["n_layers"], d["d_model"], d["n_heads"], d["n_kv_heads"]) == (
        FILE["num_hidden_layers"], FILE["hidden_size"],
        FILE["num_attention_heads"], FILE["num_key_value_heads"])
    assert (d["d_ff"], d["vocab_size"], d["kv_lora_rank"]) == (
        FILE["intermediate_size"], FILE["vocab_size"], FILE["kv_lora_rank"])
    for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rope_theta", "norm_topk_prob"):
        assert d.get(k, m.get(k)) == FILE[k], k
    assert FILE["routed_scaling_factor"] == 1  # the program scales by none
    assert d["norm_eps"] == FILE["rms_norm_eps"]
    assert not d["tie_embeddings"] and not FILE["tie_word_embeddings"]
    assert FILE["q_lora_rank"] is None and FILE["topk_method"] == "greedy"
    assert {k: v for k, v in FILE["rope_scaling"].items() if k != "type"} \
        == d["rope_scaling"] and FILE["rope_scaling"]["type"] == "yarn"
    assert (m["top_k"], m["d_expert"], m["first_dense_layers"],
            m["d_ff_dense"], m["d_shared"]) == (
        FILE["num_experts_per_tok"], FILE["moe_intermediate_size"],
        FILE["first_k_dense_replace"], FILE["intermediate_size"],
        FILE["n_shared_experts"] * FILE["moe_intermediate_size"])
    assert m["n_experts"] == FILE["n_routed_experts"] == 8
    assert m["n_routed"] == FILE["published"]["n_routed_experts"] == 64
    assert "n_routed_experts" in FILE["reduced"]


def test_counts_are_the_programs_tree():
    from repro.models import transformer

    for cfg in (FILE, TINY):
        leaves = jax.tree.leaves(transformer.param_specs(
            system.program_config(cfg)))
        d = work.retrieval(cfg).dec
        assert d.params == sum(math.prod(s.shape) for s in leaves)
        assert d.weight_bytes == sum(
            math.prod(s.shape) * s.dtype.itemsize for s in leaves)


def test_configuration_counts_are_the_exact_integers():
    """The counts of ``deepseek-v2-lite.ep8``, pinned: a change of the
    yardstick shows here before it shows in a metric."""
    r = work.retrieval(FILE)
    d = r.dec
    assert d.params == 3_110_989_312
    assert d.weight_bytes == 6_228_794_368
    assert d.kv_bytes_per_token == 27 * 576 * 2 == 31_104
    # 1120 rows reach all 8 held experts of each of the 26 expert layers;
    # the vocabulary (2 x 102,400 x 2048 x 2 B) is not read whole
    assert d.weight_bytes_read(1120) == 5_389_933_568 == (
        d.weight_bytes - 2 * 102_400 * 2048 * 2)
    assert d.weight_bytes_read(1) < d.weight_bytes_read(1120)
    assert d.routed_work(1120) == (377_864_847_360, 3_598_712_832)
    assert d.latent_attention_work(16, 70, 256, 1) == (
        27 * 1120 * 2 * 16 * 257 * 1088, 31_104 * (16 * 256 + 1120))
    assert r.flops() == 1_631_099_863_040
    assert r.decoder_least_seconds(16, 197e12, 819e9) == pytest.approx(
        0.13247511577989848, rel=1e-12)


def test_expert_time_takes_the_grouped_matmuls_inside_the_levels():
    """The compiler's ragged-dot kernels carry no scope: those inside a
    decode level count with its ``moe_experts`` ops, the prefill's not."""
    from bench import trace as T
    from bench.metrics import _experts

    D, lvl = "/device:TPU:0", "jit(r)/decode_logits_L1"
    ops = [T.Op(D, 0, 1000, "%while.1", f"{lvl}/while"),
           T.Op(D, 100, 200, "%ragged-dot-none.3", "ragged-dot-none"),
           T.Op(D, 250, 100, "fusion.2",
                f"{lvl}/while/body/closed_call/ffn/moe_experts/gather"),
           T.Op(D, 400, 50, "fusion.4",
                f"{lvl}/while/body/closed_call/ffn/moe_shared/dot"),
           T.Op(D, 2000, 100, "%while.0", "jit(r)/prefill/while"),
           T.Op(D, 2010, 80, "%ragged-dot-none.1", "ragged-dot-none")]
    assert _experts.decode_seconds(T.Trace(ops, [])) == pytest.approx(
        250e-9)
