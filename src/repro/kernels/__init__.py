"""Pallas TPU kernels for the perf-critical compute layers.

Three kernels, each with a jit'd wrapper (ops.py) and a pure-jnp oracle
(ref.py) that tests sweep shapes/dtypes against in interpret mode:

  cached_gather/    DCI's two-source feature-row gather (scalar-memory
                    position map; hit -> hot table, miss -> full table)
  seg_agg/          padded-neighborhood aggregation (GNN sum/mean)
  flash_attention/  blocked online-softmax attention with sliding-window
                    and logit-softcap variants (Gemma-2, long_500k)
"""

from repro.kernels.cached_gather.ops import cached_feature_gather
from repro.kernels.flash_attention.ops import multi_head_attention
from repro.kernels.seg_agg.ops import aggregate_neighbors

__all__ = ["cached_feature_gather", "multi_head_attention", "aggregate_neighbors"]
