"""Pallas TPU kernels for the framework's compute hot-spots.

- ``blast_matmul``          fused 3-stage BLAST product (paper Alg. 1, §2)
- ``blast_matmul_grouped``  G congruent factor sets, one shared x, one launch
- ``blast_matmul_q``        int8 / nibble-packed-int4 factor variants
- ``flash_attention``       causal / sliding-window / GQA online-softmax attn
- ``autotune``              measured (block_t, block_r) cache per call shape
- ``ref``                   pure-jnp oracles (the correctness contract)
- ``ops``                   jit'd wrappers: padding, block sizing, where kernels run

Decode note: at T <= block_t the fused BLAST kernel runs a single T-tile, so
every factor (U, S, V) streams from HBM exactly once -- bandwidth-optimal
for the paper's Table-4 matvec regime (the roofline term is the
(m+n+b^2)*r parameter bytes).  What decode *launches* pay for is the
per-projection dispatch + x-tile overhead; the grouped kernels amortize
both across every shape-congruent projection bundle of a layer (see
``README.md`` in this package for the tiling/grouping contract).
"""

from repro.kernels.ops import (blast_matmul, blast_matmul_grouped,  # noqa: F401
                               blast_matmul_grouped_q, blast_matmul_q,
                               flash_attention)
