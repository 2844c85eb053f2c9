"""Peak device bytes in use after the window on the fullest of the cell's
devices, in GiB (memory_stats)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2 ** 30
