"""Fold kernel (``kernels/fedavg_stream.py``): per round, the device time
of the fold's ops in the trace, in milliseconds."""

#: the fold's ops in a TPU trace: the Pallas call inside ``ops._fold_sum``
#: is the HLO instruction ``%_fold_sum.<n>``
FOLD_OPS = ("_fold_sum", "fedavg_stream")


def read(run):
    if run.trace is None:
        return None
    s = run.trace.kernel_s(*FOLD_OPS)
    if s is None:
        return None
    return s / run.trace.rounds * 1e3
