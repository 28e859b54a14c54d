"""Pallas TPU kernels for the framework's compute hot spots.

  spmv            -- ELL segment-sum SpMV (PageRank contribution pull)
  frontier        -- BFS pull step over packed frontier bitmaps
  flash_attention -- blocked online-softmax attention (LM train/prefill)

Each subpackage has kernel.py (pl.pallas_call + BlockSpec VMEM tiling)
and ref.py (pure-jnp oracle).  The graph kernels are validated against
ref.py in interpret mode (tests/test_kernels_{spmv,frontier}.py) and
run only when ``REPRO_LOCALOPS=kernel`` asks for them: Mosaic does not
lower either for a TPU yet ("Only 2D gather is supported"), so the
default local-ops path is the blocked-ELL gather (core/localops.py).
"""
