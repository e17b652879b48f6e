"""Seconds of SmoothQuant+ quantize-on-load (calibration, alpha search,
smoothing, int4 packing) through ``serve.quantize_on_load``, host clock
ending in ``block_until_ready``.  Layer: PTQ.  Moves ``setup_s``."""


def read(r):
    return r.ptq_s
