"""Device time of one layer of the decode step: the median, over the
``jit_decode_step`` launches in the traced window, of the device time of
the ops the program traced under its ``lm.layers`` scope (the layer scan),
over the number of layers, in ms.  It carries a cell's cut in depth over
to the published depth.  ``None`` where the program names no such scope;
``Absent`` where it does and no decode step ran.  Layer: model step.
Moves ``itl_p50_ms``."""
from harness import op_scopes


def read(r):
    v = op_scopes.per_launch_ms(r, "lm.layers")
    return None if v is None else v / r.dims.layers
