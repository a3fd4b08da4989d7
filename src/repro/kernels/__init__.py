"""Pallas TPU kernels for the framework's compute hot spots.

The paper's hot path is the sparse embedding layer (pull -> bag-reduce ->
push); on TPU that is a row gather by DMA + segment-reduce, fused in
``embedding_bag``, a DMA row scatter in ``sparse_adagrad`` and the cache
tier's id→slot probe in ``hash_map``.  ``dot_interaction`` fuses DLRM's
pairwise-dot feature cross; ``fused_adam`` and ``sparse_adagrad`` fuse the
optimizer element-wise chains.

Every kernel ships with a jit'd wrapper (ops.py) and a pure-jnp oracle
(ref.py); tests sweep shapes/dtypes in interpret mode on the CPU, and
``tests/test_tpu_compile.py`` compiles the main-path kernels for TPU v5e.
"""
