"""Closed-loop offline traffic: the paper's inference over the test set.

Seeds are the test nodes in a permutation drawn from the run's seed, cut
into back-to-back batches of ``batch_size``; each pass over the test set
draws a fresh permutation, so a window longer than one pass keeps going.
The next batch is offered as soon as the engine takes it (a closed loop:
a slower system is offered less).  Parameters (the traffic file):

* ``chunk_batches``: batches per ``GNNInferenceEngine.run`` call.
"""

from __future__ import annotations

import numpy as np


def batches(test_idx: np.ndarray, *, batch_size: int, seed: int, params: dict):
    """Endless generator of ``int32[batch_size]`` seed batches."""
    del params
    rng = np.random.default_rng([int(seed), 0xC1])
    test_idx = np.asarray(test_idx, np.int32)
    while True:
        order = rng.permutation(test_idx)
        if order.shape[0] < batch_size:  # tiny graphs: cycle to fill a batch
            order = np.tile(order, -(-batch_size // max(order.shape[0], 1)))
        for i in range(order.shape[0] // batch_size):
            yield order[i * batch_size : (i + 1) * batch_size]
