"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel ships three layers: ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted dispatcher), ``ref.py`` (pure-jnp oracle used by the
shape/dtype sweep tests in tests/test_kernels_pallas.py).

  vntk           — Alg. 2: compare-reduce masking and top-C selection over
                   each beam's CSR burst, plus the fused masked-logsoftmax
                   variant
  embedding_bag  — recsys fixed-arity gather+reduce over HBM tables
"""
