"""A whole run of the harness, on the CPU at the tiny size, with the served
path broken underneath: ``correct`` has to come out false for each fault a
one-chip serving cell can have (a token altered where it is produced, a
step that returns its state unchanged, half of the batch left out), and
true for the sound program.  The look for a chip is skipped; everything
after it is the harness's own code."""
import time

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import _tiny

SEED = 2**32 + 11


def _run(mix):
    return run.run_cell(_tiny.cell(mix), SEED, 1.0, False,
                        t_start=time.monotonic())


def _wrap_output(monkeypatch, cls, name, edit):
    orig = getattr(cls, name)

    def broken(self, *a, **kw):
        return edit(orig(self, *a, **kw))

    monkeypatch.setattr(cls, name, broken)


def _bump_first_token(out):
    tokens = out[0]
    tokens = tokens.at[:, 0, 0].set((tokens[:, 0, 0] + 1) % 32)
    return (tokens,) + tuple(out[1:])


def _copy_row0(out, n):
    return tuple(x.at[1:].set(x[:1]) if i < n else x
                 for i, x in enumerate(out))


def _plant(monkeypatch, fault, mix):
    from repro.models import transformer
    from repro.serving.generative_retrieval import GenerativeRetriever

    assert mix == "bulk"
    step_cls, step = GenerativeRetriever, "_retrieve_impl"
    if fault == "token":
        _wrap_output(monkeypatch, step_cls, step, _bump_first_token)
    elif fault == "half_batch":
        # row 0's work is handed to every row of the batch
        _wrap_output(monkeypatch, step_cls, step,
                     lambda out: _copy_row0(out, 2))
    elif fault == "stale_state":
        orig = transformer.decode_step

        def stale(params, cache, toks, cfg):
            logits, _ = orig(params, cache, toks, cfg)
            return logits, cache

        monkeypatch.setattr(transformer, "decode_step", stale)


@pytest.mark.parametrize("mix", ["bulk"])
def test_sound_run_is_correct(mix):
    out = _run(mix)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s"}


def test_traced_run_reports_per_layer_metrics():
    out = run.run_cell(_tiny.cell("bulk"), SEED, 1.0, True,
                       t_start=time.monotonic())
    assert out["correct"], out["checks"]
    assert "batch_ms.bulk" in out["metrics"]
    assert "retrievals_per_s" not in out["metrics"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["token", "stale_state", "half_batch"])
@pytest.mark.parametrize("mix", ["bulk"])
def test_broken_path_is_not_correct(monkeypatch, mix, fault):
    _plant(monkeypatch, fault, mix)
    out = _run(mix)
    assert not out["correct"], out["checks"]


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing(tmp_path):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(run.ROOT / "bench" / "run.py"), "--workload",
         "gr3b-prod.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_copy_row0_only_touches_leading_outputs():
    out = (jnp.arange(4).reshape(2, 2), jnp.ones(3))
    got = _copy_row0(out, 1)
    assert got[0].tolist() == [[0, 1], [0, 1]] and got[1].tolist() == [1] * 3
