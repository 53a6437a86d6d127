"""Peak device memory in use (``memory_stats()["peak_bytes_in_use"]``) after
the window, on the fullest of the cell's chips, in megabytes (1e6 B)."""


def read(run):
    return run.peak_bytes / 1e6 if run.peak_bytes else None
