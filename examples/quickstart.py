"""Quickstart: DCI dual-cache GNN inference vs baselines in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.graph import load_dataset
from repro.graph.csc import BYTES_PER_ADJ_ELEMENT
from repro.runtime.gnn_engine import GNNInferenceEngine

# A scaled synthetic stand-in for Ogbn-products (Table II statistics).
dataset = load_dataset("ogbn-products", scale=0.004, seed=0)
print(f"graph: {dataset.num_nodes} nodes, {dataset.graph.num_edges} edges, "
      f"feat dim {dataset.spec.feat_dim}")

for policy in ("dgl", "sci", "dci"):
    engine = GNNInferenceEngine(
        dataset, model="graphsage", fanouts=(8, 4, 2), batch_size=512
    )
    # DCI: pre-sample 8 batches -> Eq.1 capacity split -> lightweight fill.
    engine.prepare(policy, total_cache_bytes=2_000_000)
    report = engine.run(max_batches=8)
    s = report.summary()
    # Bytes the cache misses moved, from the report's exact counts.
    miss_bytes = (report.feat_lookups - report.feat_hits) * report.feat_row_bytes + (
        report.adj_lookups - report.adj_hits
    ) * BYTES_PER_ADJ_ELEMENT
    print(
        f"{policy:4s} | total {s['total_s']:6.3f}s | prep {s['prep_s']:6.3f}s | "
        f"adj hit {s['adj_hit_rate']:.2f} | feat hit {s['feat_hit_rate']:.2f} | "
        f"miss bytes {miss_bytes:,d}"
    )
