"""The most device memory held at once during the window, fullest device:
``bytes_in_use`` + ``bytes_reserved`` of ``memory_stats()``, sampled every
20 ms (``run.MemoryPeak`` says why not ``peak_bytes_in_use``)."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
