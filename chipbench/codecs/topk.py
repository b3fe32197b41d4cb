"""Reference of the top-k wire codec, written from its definition.

A shard is cut into tiles of ``tile`` elements from its own start, the last
one padded with zeros. In each tile a threshold is found by ``bisect_iters``
halvings of ``[0, max|x| + 1e-12]``: the midpoint becomes the new lower end
while at least ``k_per_tile`` elements of the tile have ``|x| >= midpoint``.
Every element with ``|x| >=`` the final lower end keeps its value; the rest
are zero. All arithmetic is f32.
"""

import numpy as np


def apply(shard: np.ndarray, params: dict) -> np.ndarray:
    """The dense f32 values an aggregator folds for one client's shard."""
    tile, k = int(params["tile"]), int(params["k_per_tile"])
    n = shard.shape[0]
    n_tiles = -(-n // tile)
    x = np.zeros(n_tiles * tile, np.float32)
    x[:n] = shard
    x = x.reshape(n_tiles, tile)
    ax = np.abs(x)
    lo = np.zeros(n_tiles, np.float32)
    hi = ax.max(axis=1) + np.float32(1e-12)
    for _ in range(int(params["bisect_iters"])):
        mid = np.float32(0.5) * (lo + hi)
        enough = np.count_nonzero(ax >= mid[:, None], axis=1) >= k
        lo = np.where(enough, mid, lo)
        hi = np.where(enough, hi, mid)
    kept = np.where(ax >= lo[:, None], x, np.float32(0.0))
    return kept.reshape(-1)[:n]
