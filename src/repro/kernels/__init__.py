"""Pallas TPU kernels for the paper's compute hot-spots, with XLA references.

- ``w4a16_matmul``:     int4 weights dequantized in VMEM inside the GEMM (§2.3)
- ``w4a16_grouped``:    the same GEMM over stacked [E, Ci, Co] weights with the
                        expert/head dim on the grid (MoE experts, MLA absorbed)
- ``flash_attention``:  causal block-skipping online-softmax prefill attention
- ``paged_attention``:  decode attention with the KV page-table gather fused
                        into the kernel (scalar-prefetch block tables), fp16
                        and int8 pools

``ops.py`` is the dispatching entry point (pallas / interpret / xla);
``ref.py`` holds the pure-jnp oracles the interpret-mode tests compare
against.
"""
from repro.kernels.ops import (  # noqa: F401
    default_backend,
    gqa_paged_attention,
    mla_paged_attention,
    quantized_linear,
    w4a16_grouped_matmul,
    w4a16_matmul,
)
