from repro.models.gnn.models import (
    LAYER_KINDS,
    MODELS,
    attn_edges,
    forward,
    forward_layer,
    init_params,
    layer_kind,
)

__all__ = [
    "LAYER_KINDS",
    "MODELS",
    "attn_edges",
    "forward",
    "forward_layer",
    "init_params",
    "layer_kind",
]
