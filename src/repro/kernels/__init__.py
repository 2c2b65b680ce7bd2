"""Pallas TPU kernels (pl.pallas_call + BlockSpec) with jnp oracles.

  soft_threshold  — RPCA shrinkage (ADMM inner loop elementwise op)
  rpca_admm       — fused RPCA ADMM elementwise tail (S/Y update + residual)
  svt_subspace    — fused subspace-SVT sweep tail (reconstruction + tail +
                    next-iteration Gram accumulation, DESIGN.md §6)
  lora_matmul     — fused base + LoRA projection y = xW + s(xA)B, plus the
                    gathered multi-adapter pool variant (scalar-prefetch
                    block gather; Punica/S-LoRA-style SGMV)
  local_attention — flash-style causal sliding-window attention
  ssd_scan        — Mamba-2 SSD chunk core, forward and backward, with the
                    decay mask and scores in VMEM (``ssd_chunk_scan``)

Execution mode (compiled vs interpret) is resolved per-call by
``repro.kernels.backend``.  Validated against ``repro.kernels.ref`` in
interpret mode on CPU (TPU is the compile target; see tests/test_kernels.py
shape/dtype sweeps).
"""
from repro.kernels import backend, ops, ref, rpca_admm, ssd_scan, svt_subspace
from repro.kernels.ops import (
    gathered_lora_matmul,
    local_attention,
    lora_matmul,
    soft_threshold,
)
from repro.kernels.rpca_admm import admm_tail
from repro.kernels.svt_subspace import subspace_apply

__all__ = [
    "backend",
    "ops",
    "ref",
    "rpca_admm",
    "svt_subspace",
    "admm_tail",
    "subspace_apply",
    "gathered_lora_matmul",
    "local_attention",
    "lora_matmul",
    "soft_threshold",
    "ssd_scan",
]
