"""Grouped matmul of the served expert layer: JAX's megablox ``gmm``
(Pallas) with its tiling chosen from the shape.

The rows of each held expert lie together (the layer sorts them by
expert), and ``gmm`` walks them in row tiles of :data:`TM`, group after
group.  With ``tk`` and ``tn`` the whole K and N of the call, the weight
block it loads changes only when the group does: each expert's slab is
read into VMEM once per call and stays there over all its row tiles, and
the lhs is read once.  Rows past the last group are never visited and
their output is left unwritten.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

__all__ = ["TM", "grouped_matmul_pallas", "tiling", "row_tiles"]

# Row tile.  Weight reuse does not depend on it (a whole-K, whole-N slab
# is loaded once per group whatever the tile); it sets the padding of each
# group's last tile.
TM = 128

# The kernel's scoped VMEM on a TPU v5e, as the compiler reports it.
VMEM_BUDGET = 16 * 2**20


def _widths(d: int) -> list[int]:
    """Block widths Pallas takes for a dimension of ``d``: ``d`` itself,
    then the multiples of 128 that divide it, widest first."""
    return [d] + [t for t in range(d - d % 128, 0, -128)
                  if t < d and d % t == 0]


def _vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM that ``gmm`` allocates for a ``(tm, tk, tn)`` tiling: the
    double-buffered lhs, weight and output blocks and the float32
    accumulator.  The compiler's own figure stays at or below it
    (``tests/test_tpu_compile.py`` compiles the tilings that show it)."""
    blocks = tm * tk + tk * tn + tm * tn
    return 2 * blocks * itemsize + 4 * tm * tn


def tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """``(TM, tk, tn)`` for a (rows, k) @ (groups, k, n) call: the whole K
    and N where the blocks fit :data:`VMEM_BUDGET`, else the whole K and
    the widest ``tn`` that fits, else the widest ``tk`` at which some
    ``tn`` fits, with the widest such ``tn``.  A whole K keeps a group's
    weight block fixed over its row tiles; a split K reloads it every
    tile."""
    ks, ns = _widths(k), _widths(n)
    for tk in ks:
        for tn in ns:
            if _vmem_bytes(TM, tk, tn, itemsize) <= VMEM_BUDGET:
                return TM, tk, tn
    return TM, ks[-1], ns[-1]


def row_tiles(sizes: jax.Array) -> jax.Array:
    """Row tiles of :data:`TM` that ``gmm`` visits for groups of ``sizes``
    laid end to end: ``(end-1)//TM - start//TM + 1`` for each non-empty
    group (a tile two groups share is visited by each)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    per = (ends - 1) // TM - starts // TM + 1
    return jnp.sum(jnp.where(sizes > 0, per, 0)).astype(jnp.int32)


def grouped_matmul_pallas(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array):
    """``lhs`` (m, k) rows grouped by ``sizes`` (g,) @ ``rhs`` (g, k, n) ->
    (m, n) in ``lhs.dtype``, accumulated in float32.  ``m`` is a multiple
    of :data:`TM`; rows past ``sum(sizes)`` are left unwritten (they may
    hold anything)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if m % TM:
        raise ValueError(f"{m} rows are not a multiple of the row tile {TM}")
    return gmm(lhs, rhs, sizes.astype(jnp.int32),
               preferred_element_type=lhs.dtype,
               tiling=tiling(k, n, lhs.dtype.itemsize),
               interpret=jax.default_backend() != "tpu")
