"""Pallas TPU kernels (+ jnp oracles) for the perf-critical compute:

  flash_attention — block-tiled online-softmax attention
                    (causal / sliding-window / softcap / GQA)
  ssd_scan        — Mamba2 SSD chunked scan with VMEM-carried state
  dense_adam      — one client's Adam step on a dense weight, its
                    gradient hᵀ·dy formed per tile (fl/executor.py)
  fed_agg         — staleness-weighted federated aggregation (Eq. 3)
  fed_agg_apply   — fused weighted-sum → pseudo-gradient → server-
                    optimizer moment update → apply (core/merge.py)
  *_sharded       — the same two under shard_map on a device mesh
                    (P dim split over every mesh axis)
  int8_*/topk_*   — client-update compression encode/decode pair
                    (per-chunk int8 quantization, top-k sparsification)
"""
from .compress import COMPRESS_SCHEMES
from .fed_agg import APPLY_OPTS
from .ops import (dense_adam, fed_agg, fed_agg_apply, fed_agg_apply_sharded,
                  fed_agg_sharded, flash_attention, int8_decode,
                  int8_encode, ssd_scan, topk_decode, topk_encode,
                  topk_mask)
from . import ref

__all__ = ["APPLY_OPTS", "COMPRESS_SCHEMES", "dense_adam", "fed_agg",
           "fed_agg_apply", "fed_agg_apply_sharded", "fed_agg_sharded",
           "flash_attention", "int8_decode", "int8_encode", "ssd_scan",
           "topk_decode", "topk_encode", "topk_mask", "ref"]
