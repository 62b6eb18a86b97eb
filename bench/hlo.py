"""Collective bytes of a compiled program, from its optimized HLO text.

The arithmetic is a copy of the program's ``parse_collective_bytes``
(``repro.launch.analysis``), kept here so that the yardstick does not move
with the program: every array in the result of a collective counts, tuple
elements included, at its dtype's width.
"""
from __future__ import annotations

import re
from typing import Dict

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
               "s4": 0.5, "u4": 0.5}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Result bytes of each kind of collective in ``hlo_text``, per device.

    A synchronous op counts its result; an asynchronous one counts the
    result of its ``-done``, because the tuple that its ``-start`` returns
    holds the operand beside the output. A dtype missing from the table
    raises: a guess would skew the count unseen.
    """
    out = {k: 0.0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        hit = None
        for c in COLLECTIVES:
            for op in (f" {c}(", f" {c}-done("):
                if op in line:
                    hit, result = c, line.split(op, 1)[0]
                    break
            if hit:
                break
        if hit is None or "=" not in result:
            continue
        for dtype, dims in _ARRAY.findall(result.split("=", 1)[1]):
            if dtype not in DTYPE_BYTES:
                raise ValueError(f"unknown HLO dtype {dtype!r} in: {line}")
            size = DTYPE_BYTES[dtype]
            for dim in dims.split(","):
                if dim:
                    size *= int(dim)
            out[hit] += size
    return out
