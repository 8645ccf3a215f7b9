"""Process start to the start of the window: loading, weights, compiling
or loading programs, and filling what the traffic needs (s)."""


def read(rec):
    return rec.setup_s
