"""The readers of a decode level's pieces: on a Summary made by hand, and
on a one-batch chip trace recorded with the pieces' scopes."""
import pytest

from bench import trace as T
from bench.metrics._decoder import LEVEL
from bench.tests.test_bench_readers import _closed, _metric, _run

D = "/device:TPU:0"
MS = 1_000_000
PIECES = ("decode_weights_ms.bulk", "decode_attention_ms.bulk",
          "decode_kv_write_ms.bulk", "decoder_unscoped_ms.bulk")


def _level(t0, n, named=True):
    """One decode level of ``n`` at ``t0`` ns: the layer loop holds its
    pieces and a compiler copy; the prefill's layers carry the same names
    and must not count."""
    lvl = f"jit(r)/decode_logits_L{n}"
    body = f"{lvl}/while/body/closed_call"

    def name(piece):
        return f"{body}/{piece}/x" if named else f"{body}/x"

    return [
        T.Op(D, t0, 1 * MS, "a", name("embed")),
        T.Op(D, t0 + 1 * MS, 50 * MS, "while", f"{lvl}/while"),
        T.Op(D, t0 + 1 * MS, 10 * MS, "b", name("qkv_proj")),
        T.Op(D, t0 + 11 * MS, 3 * MS, "c", name("kv_write")),
        T.Op(D, t0 + 14 * MS, 7 * MS, "d", name("attention")),
        T.Op(D, t0 + 21 * MS, 4 * MS, "e", name("out_proj")),
        T.Op(D, t0 + 25 * MS, 16 * MS, "f", name("ffn")),
        T.Op(D, t0 + 41 * MS, 10 * MS, "copy_bitcast_fusion.3", ""),
        T.Op(D, t0 + 51 * MS, 2 * MS, "g", name("unembed")),
    ]


def _traced(named=True):
    ops = []
    for b in range(2):  # two batches of one prefill and two levels
        t = (100.0 + b) * 1e9
        ops.append(T.Op(D, t, 20 * MS, "p",
                        "jit(r)/prefill/while/body/ffn/dot_general"))
        ops += _level(t + 20 * MS, 1, named) + _level(t + 80 * MS, 2, named)
    run = _run(_closed(), 5.0)
    run.window.trace_span = (100.0, 102.0)
    run.trace = T.summarize(T.Trace(ops, []), run.window.trace_span)
    return run


def test_pieces_add_up_to_the_decode_levels():
    run = _traced()
    got = {m: _metric(m)(run) for m in PIECES}
    # per batch: two levels of 53 ms, the prefill's 20 ms left out
    assert got == pytest.approx({
        "decode_weights_ms.bulk": 2 * (1 + 10 + 4 + 16 + 2),
        "decode_attention_ms.bulk": 2 * 7,
        "decode_kv_write_ms.bulk": 2 * 3,
        "decoder_unscoped_ms.bulk": 2 * 10})
    assert sum(got.values()) == pytest.approx(
        1e3 * run.trace.scope_s(LEVEL) / 2)


def test_a_trace_without_the_pieces_reads_nothing():
    run = _traced(named=False)
    assert run.trace.scope_s(LEVEL) > 0
    assert all(_metric(m)(run) is None for m in PIECES)


def test_recorded_chip_trace_splits_the_decode_levels(tmp_path):
    """One batch of gr3b-prod.bulk traced on a TPU v5 lite with the pieces'
    scopes (``bench/record_one_batch.py``): every reader reads a number,
    and together they account for the decode levels' device time."""
    import gzip
    import shutil

    src = T.pathlib.Path(__file__).parents[1] / "testdata"
    with gzip.open(src / "one_batch_scoped.xplane.pb.gz") as a, open(
            tmp_path / "one_batch.xplane.pb", "wb") as b:
        shutil.copyfileobj(a, b)
    run = _run(_closed(), 5.0)
    run.window.trace_span = (100.0, 102.0)
    run.window.batches = [(100.5, 101.5)]  # the one batch it holds
    run.trace = T.summarize(T.load(tmp_path))
    got = {m: _metric(m)(run) for m in PIECES}
    assert got == pytest.approx({
        "decode_weights_ms.bulk": 71.65, "decode_attention_ms.bulk": 39.65,
        "decode_kv_write_ms.bulk": 6.71, "decoder_unscoped_ms.bulk": 550.60},
        abs=0.01)
    levels_ms = 1e3 * run.trace.scope_s(LEVEL)
    assert sum(got.values()) == pytest.approx(levels_ms, rel=0.01)
    # the breakdown names the levels' ops by piece
    labels = [k for k, _ in run.trace.breakdown(40)["device_ops"]]
    assert "decode_logits_L3/closed_call/ffn" in labels
