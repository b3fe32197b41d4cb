"""Device: the allocator's peak bytes in use on the chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB of 1e9 bytes."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
