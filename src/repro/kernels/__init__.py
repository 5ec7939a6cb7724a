"""Pallas TPU kernels for the framework's compute hot spots.

  flash_attention — GQA/causal/sliding-window attention (VMEM-tiled,
                    online softmax); not on the model path, whose prefill
                    attention is models/attention.py.
  kd_loss         — fused distillation loss over large vocabs (the MDD
                    integration objective; no full softmax in HBM).
  ssd_scan        — Mamba2/SSD chunked scan (MXU matmul form, carried
                    VMEM state).

``ops.py`` dispatches to the kernels on TPU and to the pure-jnp reference
(``ref.py`` oracles) elsewhere; every kernel is validated against its
oracle in interpret mode (tests/test_kernels.py).
"""
from repro.kernels import ops

__all__ = ["ops"]
