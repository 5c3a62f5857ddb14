"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler installed beside JAX compiles for a v5e that is described,
not attached, and refuses what the chip would refuse: unaligned blocks, too
much VMEM, a program larger than HBM.  Interpret-mode kernel tests cannot
see any of that.  These tests compile the VNTK kernels at the paper's widths
(V=2048, 2 requests x M=70 = 140 rows, the paper's branch factor) and the
full-width ``static-gr`` retrieval step at the smoke's batch.

The topology is described only inside the module-scoped ``topo`` fixture,
never at import, so every pytest worker collects the same tests and only the
worker that runs them loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import vntk as K

V = 2048
ROWS = 140  # 2 requests x M=70 beams
# The paper's first sparse level (20M SIDs, V=2048, dense_d=2) gives each of
# the 2048^2 prefixes Poisson(4.77) children; their maximum is about 20.
BMAX = 24
WIDTH = 128  # top-C width of M=70 on 128 Pallas lanes
STATES = EDGES = 6_000_000  # about 6 sparse levels of 1M SIDs
K_SETS = 3
GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _kernel_case(name, sds):
    lp = sds((ROWS, V), jnp.float32)
    nodes = sds((ROWS,), jnp.int32)
    rp = sds((STATES + 1,), jnp.int32)
    edges = sds((EDGES + 256, 2), jnp.int32)
    tok = sds((EDGES + 256,), jnp.int16)
    base = sds((), jnp.int32)
    cids = sds((ROWS,), jnp.int32)
    rp_k = sds((K_SETS, STATES + 1), jnp.int32)
    edges_k = sds((K_SETS, EDGES + 256, 2), jnp.int32)
    kw = dict(interpret=False)
    return {
        "plain": (lambda a, n, r, e: K.vntk_pallas(
            a, n, r, e, BMAX, V, **kw), (lp, nodes, rp, edges)),
        "fused": (lambda a, n, r, e: K.vntk_fused_logsoftmax_pallas(
            a, n, r, e, BMAX, V, **kw), (lp, nodes, rp, edges)),
        "topk": (lambda a, n, r, e: K.vntk_topk_pallas(
            a, n, r, e, BMAX, V, WIDTH, **kw), (lp, nodes, rp, edges)),
        "compressed": (lambda a, n, r, t, b: K.vntk_compressed_pallas(
            a, n, r, t, b, BMAX, V, **kw), (lp, nodes, rp, tok, base)),
        "compressed_topk": (lambda a, n, r, t, b:
                            K.vntk_compressed_topk_pallas(
                                a, n, r, t, b, BMAX, V, WIDTH, **kw),
                            (lp, nodes, rp, tok, base)),
        "stacked_topk": (lambda a, n, c, r, e: K.vntk_stacked_topk_pallas(
            a, n, c, r, e, BMAX, V, WIDTH, **kw),
            (lp, nodes, cids, rp_k, edges_k)),
    }[name]


@pytest.mark.parametrize("name", ["plain", "fused", "topk", "compressed",
                                  "compressed_topk", "stacked_topk"])
def test_vntk_kernel_compiles_for_v5e(one_chip, no_persistent_cache, name):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_case(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_static_gr_step_compiles_and_fits_v5e(one_chip, no_persistent_cache,
                                              monkeypatch):
    """The smoke's served step: static-gr-3b, batch 2, history 256, 1M SIDs
    at ``dense_d=2``, with the Pallas top-C kernel on the sparse levels.
    Each history is held once, shared by its 70 beams: the temporaries stay
    small, and no array has the shape of a per-beam copy of the history
    cache (26 layers, 140 beams, 265 slots)."""
    from repro.launch import serve
    from repro.models import transformer

    # the policy routes to the kernel by the default backend, which is the
    # CPU here: steer it to the chip's choice for this compile only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, geo = serve.decoder("static-gr")

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: transformer.init_params(cfg, k), jax.random.key(0)))
    tm = serve.build_index(serve.constraint_sids(1_000_000, geo), geo)
    policy = serve.build_policy(tm, impl="pallas")
    r = serve.build_retriever(params, cfg, policy, geo)
    hist = jax.ShapeDtypeStruct((geo.batch, geo.history), jnp.int32,
                                sharding=one_chip)
    compiled = jax.jit(r._retrieve_impl).lower(
        params, hist, jax.tree.map(sds, policy), None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert geo.batch == 2 and total < 16 * GIB, total / GIB
    assert ma.temp_size_in_bytes < 3 * GIB, ma.temp_size_in_bytes / GIB
    assert "bf16[26,140,265,8,128]" not in compiled.as_text()
