"""Peak resident host memory of the process during the window, sampled
every 10 ms (``runner.HostPeak``)."""


def read(run):
    return run.host_peak_bytes / 1e9
