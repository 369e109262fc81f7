"""Process start to the first timed cell: runtime start, state made from
the seed, compilation and warm-up cycles."""


def read(run):
    return run.setup_s
