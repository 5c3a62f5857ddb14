"""Trace reduction: busy time, time per scope, idle gaps, breakdown."""
import pytest

from bench import trace as T

D = "/device:TPU:0"


def _synthetic():
    ops = [
        T.Op(D, 0, 100, "fusion.1", "jit(f)/jit(main)/prefill/dot_general"),
        T.Op(D, 50, 100, "fusion.2", "jit(f)/jit(main)/prefill/add"),
        T.Op(D, 300, 200, "fusion.3",
             "jit(f)/jit(main)/decode_logits_L1/while/body/dot_general"),
        T.Op(D, 500, 50, "fusion.4",
             "jit(f)/jit(main)/constraint_topk_L1/top_k"),
        T.Op(D, 900, 100, "copy.5", ""),
    ]
    spans = [T.Span("python", 0, 1000, "bench.window"),
             T.Span("python", 560, 300, "host_admit")]
    return T.Trace(ops, spans)


def test_busy_is_the_union_of_op_intervals():
    s = T.summarize(_synthetic(), (0.0, 2e-6), min_gap_s=1e-8)
    assert s.busy_s == pytest.approx(500e-9)  # [0,150] [300,550] [900,1000]
    assert s.window_s == pytest.approx(2e-6)


def test_scope_seconds_match_scope_patterns():
    s = T.summarize(_synthetic())
    # the two prefill ops overlap: their union, [0, 150], counts
    assert s.scope_s(r"(^|/)prefill(/|$)") == pytest.approx(150e-9)
    assert s.scope_s(r"(^|/)decode_logits_L\d+(/|$)") == pytest.approx(
        200e-9)
    assert s.scope_s(r"constraint_") == pytest.approx(50e-9)
    assert s.scope_s(r"carry_gather") == 0.0
    assert s.window_s == pytest.approx(1000e-9)


def test_gaps_are_named_by_the_innermost_host_span():
    s = T.summarize(_synthetic(), min_gap_s=1e-8)
    b = s.breakdown()
    gaps = dict(b["idle_gaps"])
    assert gaps == pytest.approx({"host: bench.window": 150e-9,
                                  "host: host_admit": 350e-9})
    ops = dict(b["device_ops"])
    assert ops["prefill"] == pytest.approx(200e-9)
    assert ops["decode_logits_L1"] == pytest.approx(200e-9)
    assert ops["copy.5"] == pytest.approx(100e-9)


def test_a_loop_op_and_its_body_count_once():
    body = "jit(f)/jit(main)/prefill/while/body/dot_general"
    ops = [T.Op(D, 0, 1000, "%while.1 = (f32[]) while(...)",
                "jit(f)/jit(main)/prefill/while"),
           T.Op(D, 100, 300, "fusion.1", body),
           T.Op(D, 500, 300, "fusion.2", body),
           T.Op(D, 1200, 100, "fusion.3", "jit(f)/jit(main)/carry_gather_L1/x")]
    s = T.summarize(T.Trace(ops, []))
    assert s.scope_s(r"(^|/)prefill(/|$)") == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(1100e-9)
    ops_s = dict(s.breakdown()["device_ops"])
    assert ops_s == pytest.approx({"prefill": 600e-9,
                                   "carry_gather_L1": 100e-9})


def test_scope_comes_from_the_hlo_module_that_holds_the_op():
    programs = {7: {"fusion.3": "jit(f)/jit(main)/decode_logits_L2/dot"}}
    modules = [(0, 5000, 7), (6000, 9000, 8)]
    hlo_text = ("%fusion.3 = (bf16[2]{0}, /*index=1*/bf16[2]{0}) "
                "fusion(bf16[2]{0} %p), kind=kLoop")
    assert T._scope(hlo_text, 10, modules, programs) == (
        "jit(f)/jit(main)/decode_logits_L2/dot")
    assert T._scope(hlo_text, 5500, modules, programs) == ""  # no module
    assert T._scope(hlo_text, 6500, modules, programs) == ""  # not in it
    assert T._scope("%fusion.9 = f32[] fusion()", 10, modules, programs) == ""


def test_recorded_chip_trace(tmp_path):
    """One batch of gr3b-prod.bulk traced on a TPU v5 lite: the trace's own
    HLO modules name the scope of most of the device time, and the readers'
    scopes split the batch as that chip run did."""
    import gzip
    import shutil

    from bench.metrics._trace import BEAM_CACHE, CONSTRAINT, DECODER

    src = T.pathlib.Path(__file__).parents[1] / "testdata"
    with gzip.open(src / "one_batch.xplane.pb.gz") as a, open(
            tmp_path / "one_batch.xplane.pb", "wb") as b:
        shutil.copyfileobj(a, b)
    trace = T.load(tmp_path)
    s = T.summarize(trace)
    assert len(trace.ops) == 24602
    leaves = T._leaves(trace.ops)
    scoped = sum(o.dur_ns for o in leaves if o.scope)
    assert scoped / sum(o.dur_ns for o in leaves) > 0.6
    assert s.busy_s == pytest.approx(1.0319, abs=1e-3)
    assert s.scope_s(DECODER) == pytest.approx(0.6934, abs=1e-3)
    assert s.scope_s(BEAM_CACHE) == pytest.approx(0.3039, abs=1e-3)
    assert s.scope_s(CONSTRAINT) == pytest.approx(9.8e-4, abs=1e-5)
    top = dict(s.breakdown()["device_ops"])
    assert top["carry_gather_L0"] == pytest.approx(0.0785, abs=1e-3)
