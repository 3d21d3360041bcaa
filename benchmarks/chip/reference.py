"""Plain float32 numpy references for the sampled GNN forward.

Independent of the program: they read only the sampled input frontier (the
node ids of the deepest layer, in the ``[self | neighbours]`` layout the
sampler documents), the feature table and the weights.  What a layer
computes, its activation included, is the model plug-in's ``layer``
(``models/<name>.py``); this module holds what every model shares: the
walk of the frontier tree and the products at a stated precision.

Frontier layout.  With fan-outs ``(f_1, ..., f_L)`` listed outermost first,
the expansion uses them in reverse, ``rev = (f_L, ..., f_1)``.  Frontier 0
is the batch's seeds and frontier ``l+1`` is ``[frontier l | neighbours of
frontier l]``, the neighbours of position ``p`` sitting at
``S_l + p * rev[l] + j``.  A layer that maps frontier ``l+1`` to frontier
``l`` therefore reads, for destination ``p``, row ``p`` as itself and rows
``S_l + p * rev[l] + [0, rev[l])`` as its neighbours.

The reference walks that tree from a block of seeds downwards, so a batch
is computed a block of seeds at a time and never holds the whole frontier
(1,081,344 rows x 602 floats on Reddit).

``matmul="high"`` emulates the three-pass bfloat16 product that a TPU runs
for float32 operands at ``precision=HIGH``: each operand is split into a
bfloat16 head and a bfloat16 tail and ``a_hi b_hi + a_hi b_lo + a_lo b_hi``
is accumulated in float32.  It is the precision step below the float32
that the configurations state, and serves as the control of the logit
comparison.
"""

from __future__ import annotations

import functools

import numpy as np


def frontier_sizes(batch: int, fanouts) -> list[int]:
    sizes = [int(batch)]
    for f in reversed(tuple(fanouts)):
        sizes.append(sizes[-1] * (1 + int(f)))
    return sizes


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(
        0xFFFF0000
    )
    return rounded.view(np.float32)


def matmul(a: np.ndarray, b: np.ndarray, precision: str = "highest") -> np.ndarray:
    if precision == "highest":
        return a @ b
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, b_hi = to_bf16(a), to_bf16(b)
    a_lo, b_lo = to_bf16(a - a_hi), to_bf16(b - b_hi)
    return a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)


def forward(
    model,
    params,
    table: np.ndarray,
    input_nodes: np.ndarray,
    batch: int,
    fanouts,
    *,
    block: int = 128,
    matmul_precision: str = "highest",
) -> np.ndarray:
    """Logits ``[batch, classes]`` of one sampled batch.

    ``params`` is a list of layer dicts of float32 numpy arrays (input layer
    first), ``table`` the float32 feature table, ``input_nodes`` the deepest
    frontier's node ids, ``model`` the model's plug-in module."""
    rev = tuple(int(f) for f in reversed(tuple(fanouts)))
    sizes = frontier_sizes(batch, fanouts)
    depth = len(rev)
    if input_nodes.shape[0] != sizes[-1]:
        raise ValueError(f"frontier has {input_nodes.shape[0]} rows, expected {sizes[-1]}")
    params = [{k: np.asarray(v, np.float32) for k, v in p.items()} for p in params]
    mm = functools.partial(matmul, precision=matmul_precision)

    def rows(level: int, pos: np.ndarray) -> np.ndarray:
        """Hidden rows at frontier ``level`` for positions ``pos``."""
        if level == depth:
            return table[input_nodes[pos]]
        f = rev[level]
        nbr_pos = sizes[level] + pos[:, None] * f + np.arange(f)
        below = rows(level + 1, np.concatenate([pos, nbr_pos.reshape(-1)]))
        n = pos.shape[0]
        li = depth - 1 - level  # model layer index, input layer first
        return model.layer(
            params[li], below[:n], below[n:].reshape(n, f, below.shape[1]), matmul=mm,
            last=level == 0,
        )

    out = [rows(0, np.arange(s, min(s + block, batch))) for s in range(0, batch, block)]
    return np.concatenate(out).astype(np.float32)
