"""The paper's own model/dataset configurations (Table II/III), plus GAT."""

GNN_MODELS = {
    "graphsage": {"layers": 3, "agg": "sum", "hidden": 128},
    "gcn": {"layers": 3, "agg": "avg", "hidden": 128},
    # PyG's ogbn-products GAT: 4 heads of 128 (concatenated), skips, ELU;
    # the last layer averages its 4 heads of classes.
    "gat": {"layers": 3, "agg": "attention", "hidden": 128, "heads": 4},
}

FANOUTS = {"small": (2, 2, 2), "medium": (8, 4, 2), "large": (15, 10, 5)}
BATCH_SIZES = (256, 1024, 4096)
