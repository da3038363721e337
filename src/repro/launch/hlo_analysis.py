"""HLO-text analysis utilities.

Separate from dryrun.py so tests can import it without triggering
dryrun's 512-device XLA_FLAGS override.
"""
from __future__ import annotations

import re
from typing import Dict

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u64": 8, "s64": 8,
                "u32": 4, "s32": 4, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|u64|s64|u32|s32|u16|s16|u8|s8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Estimate per-device bytes moved by every collective in the SPMD-
    partitioned HLO.  The printed HLO omits operand shapes, so we use the
    *result* shape of each collective line, with a 2x factor for ring
    all-reduce (reduce-scatter + all-gather phases move ~2x the buffer)."""
    out: Dict[str, float] = {}
    factor = {"all-reduce": 2.0}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "= " not in line:
            continue
        kind = m.group(1)
        rhs = line.split("= ", 1)[1]
        op_pos = rhs.find(m.group(0))
        result_part = rhs[:op_pos] if op_pos > 0 else rhs
        nbytes = 0.0
        for dt, dims in _SHAPE_RE.findall(result_part):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + nbytes * factor.get(kind, 1.0)
    return out
