"""Reference of the identity wire codec: a shard reaches the fold as sent."""

import numpy as np

#: shards reach the fold as sent, so the whole gradient stands for them
PASSTHROUGH = True


def apply(shard: np.ndarray, params: dict) -> np.ndarray:
    """The f32 values an aggregator folds for one client's shard."""
    return shard
