"""The work a batch needs, from shapes, and the chip's peaks.

``forward_flops`` counts the model's own arithmetic per sampled batch: for
each layer, the neighbour aggregation (one add per neighbour element, plus
GCN's self add and mean divide) and the dense products (2·m·k·n each; two
for GraphSAGE, one for GCN).  With fan-outs 15,10,5 and batch 1024 the
layers map 1,081,344 -> 67,584 -> 6,144 -> 1,024 rows.  The count is of
what the model needs, not of how a backend computes it, so a faster
implementation moves time and never the count.

``gather_bytes`` is the least HBM traffic of a feature gather: every row
gathered is read once and written once, and every prefetched miss row is
read from its staged pack and written into place once.

``peaks`` reads ``peaks.json``; a device kind that is not in the table is
an error, never a default.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in {path.name}")
    return table[device_kind]


def forward_flops(model: str, dims: list[int], fanouts, batch: int) -> float:
    """FLOPs of one sampled forward; ``dims`` = [in, hidden..., classes]."""
    rev = tuple(int(f) for f in reversed(tuple(fanouts)))
    sizes = [int(batch)]
    for f in rev:
        sizes.append(sizes[-1] * (1 + f))
    depth = len(rev)
    flops = 0.0
    for li in range(depth):
        level = depth - 1 - li  # destination frontier of model layer li
        n, f = sizes[level], rev[level]
        k, m = dims[li], dims[li + 1]
        if model == "graphsage":
            flops += n * (f - 1) * k  # neighbour sum
            flops += 2 * (2.0 * n * k * m) + 2 * n * m  # two products, their add, the bias
        elif model == "gcn":
            flops += n * f * k + n * k  # self + neighbour sum, the mean's divide
            flops += 2.0 * n * k * m + n * m  # one product, the bias
        else:
            raise ValueError(f"unknown model {model!r}")
    return flops


def gather_bytes(gathered_rows: int, prefetched_rows: int, row_bytes: int) -> float:
    return 2.0 * (gathered_rows + prefetched_rows) * row_bytes
