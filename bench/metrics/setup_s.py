"""Seconds from the start of the process to the start of the measured
window: loading, weights from the seed, quantize-on-load, engine build,
warm-up compiles (or loads from the persistent cache) and the ramp of the
cell's own traffic."""


def read(r):
    return r.setup_s
