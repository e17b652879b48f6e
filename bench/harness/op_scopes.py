"""The program's named scopes of the ops in a traced run, read from the
compiled modules the profile holds.

On a TPU the profiler names each event of the ``XLA Ops`` line after its
HLO instruction (``%fusion.37 = f32[64,49152] fusion(...)``) and gives it
none of the instruction's metadata, so a ``jax.named_scope`` of the
program (``lm.layers``, ``lm.head`` in ``models/lm.py``) is not on the
event.  The profile's ``/host:metadata`` plane holds each compiled
module's HLO (an ``HloProto`` in a stat of an event-metadata entry), and
there every instruction's ``metadata.op_name`` is the scope path it was
traced under: ``jit(decode_step)/lm.layers/while/body/...``.  This module
walks the protobuf wire format for the few fields it needs, so it needs no
protobuf definitions.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, Iterator, Optional, Tuple

from harness import program
from harness import trace as TR
from harness.readings import Absent

METADATA_PLANE = b"/host:metadata"
DECODE = r"^jit_decode_step\b"

_cache: Dict[str, Dict[str, Dict[str, str]]] = {}   # by profile path


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a message: an int for varints, the
    bytes (a memoryview) for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield num, v


def _first(b, num: int):
    return next((v for f, v in _fields(b) if f == num), None)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace") if v is not None else ""


def hlo_op_names(hlo_proto) -> Tuple[str, Dict[str, str]]:
    """``HloProto`` -> (module name, {instruction name: op_name})."""
    module = _first(hlo_proto, 1)                     # hlo_module
    names: Dict[str, str] = {}
    for f, comp in _fields(module):
        if f != 3:                                    # computations
            continue
        for g, ins in _fields(comp):
            if g != 2:                                # instructions
                continue
            name = op_name = None
            for h, v in _fields(ins):
                if h == 1:                            # name
                    name = _text(v)
                elif h == 7:                          # metadata
                    op_name = _text(_first(v, 2))     # OpMetadata.op_name
            if name:
                names[name] = op_name or ""
    return _text(_first(module, 1)), names


def module_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Every compiled module's {instruction: op_name} in the profile at
    ``path``, by module name (``jit_decode_step``)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(data):                    # XSpace.planes
        if f != 1 or bytes(_first(plane, 2) or b"") != METADATA_PLANE:
            continue
        for g, entry in _fields(plane):               # event_metadata map
            if g != 4:
                continue
            for h, stat in _fields(_first(entry, 2) or b""):
                if h != 5:                            # XEventMetadata.stats
                    continue
                blob = _first(stat, 6)                # XStat.bytes_value
                if blob is None:
                    continue
                try:
                    name, names = hlo_op_names(blob)
                except (ValueError, IndexError, TypeError):
                    continue                          # not an HloProto
                if names:
                    out.setdefault(name, {}).update(names)
    return out


def of(r) -> Optional[Dict[str, Dict[str, str]]]:
    """Run ``r``'s compiled modules' op scopes, or ``None`` where the run
    was not traced or its profile is not found.  The profile is the one
    ``program.of`` found for the run's window (its parse is cached there);
    this reads only its metadata plane.  A later harness may hand the
    scopes over as ``Trace.op_scopes``."""
    tr = r.trace
    if tr is None:
        return None
    if hasattr(tr, "op_scopes"):
        return tr.op_scopes
    if program.of(r) is None:
        return None
    path = next((p for (p, w), prog in program._cache.items()
                 if prog is not None and w == tuple(tr.window)), None)
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = module_op_names(path)
    return _cache[path]


def per_launch_ms(r, scope: str) -> Optional[float]:
    """Median over the decode step's launches in the traced window of the
    device time of its ops traced under ``scope`` (enclosing loops left
    out), in ms.  ``None`` where the run was not traced or its program
    names no such scope; ``Absent`` where it does and no decode step
    ran."""
    scopes = of(r)
    if not scopes:
        return None
    rx, mod = re.compile(rf"(^|/){re.escape(scope)}(/|$)"), re.compile(DECODE)
    inside = {name for m, ops in scopes.items() if mod.search(m)
              for name, op_name in ops.items() if rx.search(op_name)}
    if not inside:
        return None
    prog = program.of(r)
    per = []
    for dev, mods in zip(r.trace.devices, prog.modules if prog else []):
        ops = [e for e in TR.leaves(dev) if instruction(e.name) in inside]
        per += [sum(e.dur for e in ops if m.start <= e.start < m.end)
                for m in TR.matching(mods, DECODE)]
    if not per:
        raise Absent(f"ops carry {scope!r} but no module event matches "
                     f"{DECODE!r} in the trace")
    return statistics.median(per) * 1e-6


def instruction(event_name: str) -> str:
    """``%fusion.37 = f32[...] fusion(...)`` -> ``fusion.37``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")
