"""Share of the HBM roofline the hash kernels reached: the least time the
chip needs to read the bytes they hashed, over the kernels' device time in
the trace.  Nothing is returned where the trace holds no hash kernel."""
from roofline import hash_least_seconds
from tracefold import kernel_seconds

# the hash kernels in a TPU trace: each Pallas call is a custom call named
# after the jitted function that holds it (kernels/hash_delta/ops.py)
KERNELS = ("custom-call _batched_lanes", "custom-call _packed_lanes",
           "custom-call _block_lanes", "custom-call _digest_lanes")


def read(run):
    if run.trace is None or run.hashed_bytes == 0:
        return None
    kernel_s = kernel_seconds(run.trace_events, KERNELS)
    if kernel_s <= 0:
        return None
    return 100.0 * hash_least_seconds(run.hashed_bytes,
                                      run.device_kind) / kernel_s
