"""Fold windows (``kernels/ops.fedavg_multi``: window fill, host to device,
launch, copy back, host divide): per round, the summed ``fedavg_multi``
spans, in seconds."""


def read(run):
    folds = run.span_s("fedavg_multi")
    if folds is None:
        return None
    return sum(folds) / len(folds)
