"""The expert layer's grouped matmul: the Pallas kernel (interpret mode
here) against ``jax.lax.ragged_dot`` and a float64 formula, its tiling
rule and its row-tile count.  The layer on the kernel is tested with the
layer (``test_expert_layer.py``)."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels import ops
from repro.kernels.grouped_matmul import (TM, VMEM_BUDGET, _vmem_bytes,
                                          grouped_matmul_pallas, row_tiles,
                                          tiling)

GROUPS = {
    "skewed": [3, 200, 1, 40],
    "empty_first": [0, 60, 70, 9],
    "empty_middle": [50, 0, 0, 77],
    "empty_last": [128, 5, 90, 0],
    "rows_left_over": [10, 20, 30, 5],  # sum(sizes) < rows
    "one_tile_two_groups": [64, 64, 0, 0],
    "all_empty": [0, 0, 0, 0],
    "groups_straddle_tiles": [127, 2, 127, 0],
    "groups_fill_tiles": [128, 0, 128, 0],
    "one_row_groups": [1, 1, 1, 1],  # four groups share one tile
}


def _operands(rows, k, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    lhs = rng.standard_normal((rows, k)).astype(np.float32)
    rhs = rng.standard_normal((4, k, n)).astype(np.float32)
    return jnp.asarray(lhs, dtype), jnp.asarray(rhs, dtype)


def _float64(lhs, rhs, sizes):
    lhs = np.asarray(lhs, np.float64)
    rhs = np.asarray(rhs, np.float64)
    out, start = [], 0
    for g, s in enumerate(sizes):
        out.append(lhs[start:start + s] @ rhs[g])
        start += s
    return np.concatenate(out) if out else np.zeros((0, rhs.shape[2]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rows", [2 * TM, 3 * TM], ids=["two_tiles",
                                                         "three_tiles"])
@pytest.mark.parametrize("groups", list(GROUPS))
def test_pallas_grouped_matmul_matches_ragged_dot(groups, rows, dtype):
    """Every row of every group equals ``ragged_dot``'s and the float64
    product's, with rows left over past the groups or none."""
    sizes = GROUPS[groups]
    used = sum(sizes)
    lhs, rhs = _operands(rows, 40, 48, dtype)
    s = jnp.asarray(sizes, jnp.int32)
    got = ops.grouped_matmul(lhs, rhs, s, impl="pallas")
    ref = ops.grouped_matmul(lhs, rhs, s, impl="xla")
    assert got.shape == (rows, 48) and got.dtype == dtype
    want = _float64(lhs, rhs, sizes)
    tol = dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32 else \
        dict(rtol=2e-2, atol=2e-1)
    np.testing.assert_allclose(np.asarray(got[:used], np.float64), want,
                               **tol)
    np.testing.assert_allclose(np.asarray(got[:used], np.float64),
                               np.asarray(ref[:used], np.float64), **tol)


@pytest.mark.parametrize("groups", list(GROUPS))
def test_row_tiles_are_the_tiles_the_kernel_visits(groups):
    """``row_tiles`` is ``(end-1)//tm - start//tm + 1`` summed over the
    non-empty groups, and equals the number of row-tile steps the
    kernel's own metadata schedules."""
    sizes = GROUPS[groups]
    tm = TM
    want, start = 0, 0
    for s in sizes:
        if s:
            want += (start + s - 1) // tm - start // tm + 1
        start += s
    s = jnp.asarray(sizes, jnp.int32)
    assert int(row_tiles(s)) == want
    m = -(-max(sum(sizes), 1) // tm) * tm
    _, scheduled = make_group_metadata(
        group_sizes=s, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=len(sizes), visit_empty_groups=False)
    assert int(scheduled) == want


@pytest.mark.parametrize("shape,want", [
    ((2048, 1408), (TM, 2048, 1408)),  # DeepSeek-V2-Lite's w1 and w3
    ((1408, 2048), (TM, 1408, 2048)),  # its w2
    ((4096, 14336), (TM, 4096, 512)),  # Mixtral's w1: the widest tn
    ((14336, 4096), (TM, 14336, 128)),  # its w2: a whole K, narrow tn
    ((18432, 4096), (TM, 9216, 256)),  # no tn fits a whole K
    ((32, 16), (TM, 32, 16)),  # the tests' tiny experts
], ids=["v2lite-w1", "v2lite-w2", "mixtral-w1", "mixtral-w2", "split-k",
        "tiny"])
def test_tiling_keeps_whole_slabs_where_they_fit(shape, want):
    k, n = shape
    got = tiling(k, n, 2)
    assert got == want
    assert _vmem_bytes(*got, 2) <= VMEM_BUDGET
    assert k % got[1] == 0 and n % got[2] == 0


def test_kernel_takes_whole_row_tiles_only():
    """The layer pads the sort's index to whole row tiles, so that no copy
    of the activations is made; the kernel refuses rows that are not."""
    lhs, rhs = _operands(TM + 7, 40, 48, jnp.float32)
    with pytest.raises(ValueError, match="row tile"):
        grouped_matmul_pallas(lhs, rhs, jnp.asarray([3, 4, 0, 0], jnp.int32))
