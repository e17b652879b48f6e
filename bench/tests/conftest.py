"""Tests of the benchmark's own machinery, on the CPU.

    python3 -m pytest -q bench/tests

They import the harness from ``bench/`` and the program from ``src/``.
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def off_chip(monkeypatch):
    """Let ``run.run_cell`` go on where JAX sees only the CPU."""
    import run

    monkeypatch.setattr(run, "require_tpu", lambda devs, chips: None)


@pytest.fixture
def break_engine(monkeypatch):
    """``break_engine(fault)``: every engine the harness builds from now on
    goes through ``fault(engine)`` before its warm-up."""
    from harness import serving

    build = serving.build

    def apply(fault):
        def broken(*a, **kw):
            built = build(*a, **kw)
            fault(built.eng)
            return built

        monkeypatch.setattr(serving, "build", broken)

    return apply
