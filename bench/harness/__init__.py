"""The benchmark's own machinery: spec loading, traffic, weights, the
served path (``serving``), trace reduction and the correctness comparison.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``; this package only reads them.
"""
