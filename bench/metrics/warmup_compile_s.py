"""Seconds the harness spends making the step programs ready before the
ramp: tracing, lowering and compiling (or loading from the persistent
cache) the decode step, each prefill (rows, bucket) pair the traffic file
lists, and the sampler at each row count.  Layer: launch.  Moves
``setup_s``."""


def read(r):
    return r.warm_s
