"""Device time of the decode step's head: the median, over the
``jit_decode_step`` launches in the traced window, of the device time of
the ops the program traced under its ``lm.head`` scope (final norm and
lm_head), in ms.  ``None`` where the program names no such scope;
``Absent`` where it does and no decode step ran.  Layer: model step.
Moves ``itl_p50_ms``."""
from harness import op_scopes


def read(r):
    return op_scopes.per_launch_ms(r, "lm.head")
