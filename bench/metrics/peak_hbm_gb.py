"""``peak_hbm_gb``: ``memory_stats()["peak_bytes_in_use"]`` of the chip
after the window, in 1e9 bytes; nothing off the chip."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
