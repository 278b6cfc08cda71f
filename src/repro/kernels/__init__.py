"""Pallas TPU kernels for ViTA's compute hot-spots (+ jnp oracles).

Kernels (each with a pure-jnp oracle in ref.py, validated in interpret mode):
  * fused_mlp      — ViTA inter-layer MLP optimization (hidden never
                     materialized; input-stationary, weight-streaming)
  * head_attention — head-streamed flash attention (GQA/causal/SWA) and the
                     single-query decode kernel
  * vita_msa       — paper-faithful fused per-head QKV+attention (ViT-scale);
                     batched (batch, head) grid + int8 PTQ variant
  * int8_matmul    — int8xint8->int32 MXU matmul with fused requantization

`ops` is the backend-dispatching public surface used by model code.
"""

from . import ops, ref
from .fused_mlp import fused_mlp
from .head_attention import decode_attention, flash_attention
from .int8_matmul import int8_matmul
from .vita_msa import vita_msa, vita_msa_batched, vita_msa_int8

__all__ = ["ops", "ref", "fused_mlp", "flash_attention",
           "decode_attention", "int8_matmul", "vita_msa",
           "vita_msa_batched", "vita_msa_int8"]
