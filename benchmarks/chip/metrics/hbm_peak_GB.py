"""Peak device memory in use (``memory_stats()["peak_bytes_in_use"]``),
read when the window closes."""


def read(run):
    if run.hbm_peak_bytes is None:
        return None
    return run.hbm_peak_bytes / 1e9
