"""Programs lowered inside the measured window (``jax.monitoring``
jaxpr-to-MLIR events): each is a stall that lands in every slot's gap
between tokens.  Layer: launch (jit).  Moves ``itl_p95_ms``."""


def read(r):
    return r.window.compiles["lowered"]
