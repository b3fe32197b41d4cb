"""Fold kernel's share of its roofline, in percent.

The least time a round's fold can take on the chip is the bytes it has to
move over the HBM peak: N contributions read and one sum written, each of
|theta| f32 elements, ``(N + 1) * |theta| * 4`` bytes. The count comes from
the deployment, not from launch shapes, padding or the number of windows,
so it is the same work whatever implements the fold. The fold has one add
per element read and no reuse, so HBM bandwidth bounds it. The share is
that least time over the fold's device time per round (``fold_kernel_ms``).
"""

from chipbench.metrics import fold_kernel_ms


def fold_bytes(config: dict) -> int:
    return (int(config["n_clients"]) + 1) * int(config["grad_elems"]) * 4


def read(run):
    ms = fold_kernel_ms.read(run)
    if not ms:
        return None
    least = fold_bytes(run.config) / float(run.peak["hbm_bytes_per_s"])
    return least / (ms / 1e3) * 100.0
