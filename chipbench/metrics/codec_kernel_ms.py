"""Codec kernels (``kernels/topk_sparsify.py`` through
``core/wire_codec.py``): per round, the device time of the top-k kernel's
ops in the trace, in milliseconds."""

#: the Pallas call inside ``ops._topk_flat`` is ``%_topk_flat.<n>``
TOPK_OPS = ("_topk_flat", "topk_sparsify")


def read(run):
    if run.trace is None:
        return None
    s = run.trace.kernel_s(*TOPK_OPS)
    if s is None:
        return None
    return s / run.trace.rounds * 1e3
