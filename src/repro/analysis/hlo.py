"""Post-SPMD HLO analysis: per-device collective bytes with loop scaling.

``compiled.as_text()`` prints each computation once; ``lax.scan`` lowers to a
``while`` whose body executes trip-count times.  A flat grep therefore
under-counts collectives inside the layer stack by ~L x.  This module parses
the HLO into computations, finds ``while`` ops, extracts the trip count from
the loop-condition's comparison constant, and recursively scales nested
collective bytes (layer scan inside grad-accumulation scan, etc.).

Byte convention: the *result shape* of the op is recorded (per-device, since
post-SPMD shapes are per-partition).  The roofline converts these to link
traffic with the standard ring factors:
  all-reduce ~ 2x, all-gather / reduce-scatter ~ 1x (times (n-1)/n ~ 1),
  all-to-all ~ 1x, collective-permute ~ 1x.
"""
from __future__ import annotations

import dataclasses
import gzip
import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"while\(.*?\)\s*,?\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"s(?:32|64)\[\]\s+constant\((\d+)\)")
_OP_RE = re.compile(r"=\s+(\S.*?)\s+([a-z0-9\-]+)\(")


def _comp_header(raw: str) -> tuple[str | None, bool]:
    """(computation name, is_entry) if this line opens a computation."""
    if raw[:1] in (" ", "\t") or "{" not in raw:
        return None, False
    m = _HEADER_RE.match(raw)
    if not m:
        return None, False
    return m.group(2), bool(m.group(1))


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    coll_bytes: dict
    coll_counts: dict
    whiles: list          # (condition_name, body_name)
    coll_bytes_f32: dict = dataclasses.field(
        default_factory=lambda: {c: 0 for c in COLLECTIVES})


def _f32_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt != "f32":
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * 4
    return total


def parse_computations(text: str) -> tuple[dict, str]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry_name = None
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        name, is_entry = _comp_header(raw)
        if name is not None:
            cur = Computation(name, is_entry,
                              {c: 0 for c in COLLECTIVES},
                              {c: 0 for c in COLLECTIVES}, [])
            comps[name] = cur
            if is_entry:
                entry_name = name
            continue
        if cur is None:
            continue
        wm = _WHILE_RE.search(line)
        if wm:
            cur.whiles.append((wm.group(1), wm.group(2)))
        om = _OP_RE.search(line)
        if om:
            type_str, op = om.group(1), om.group(2)
            for c in COLLECTIVES:
                if op == c or op.startswith(c + "-"):
                    if op.endswith("-done"):
                        break  # counted at -start
                    cur.coll_bytes[c] += _shape_bytes(type_str)
                    cur.coll_bytes_f32[c] += _f32_bytes(type_str)
                    cur.coll_counts[c] += 1
                    break
    return comps, entry_name


def _trip_count(cond_text: list[str]) -> int:
    """Max integer constant in the loop condition (induction bound)."""
    best = 1
    for line in cond_text:
        for m in _CONST_RE.finditer(line):
            best = max(best, int(m.group(1)))
    return best


def collective_totals(text: str) -> dict:
    """Trip-count-scaled per-device collective bytes/counts per op kind."""
    # gather raw text per computation for trip-count extraction
    comp_lines: dict[str, list[str]] = {}
    cur_name = None
    for raw in text.splitlines():
        name, _ = _comp_header(raw)
        if name is not None:
            cur_name = name
            comp_lines[cur_name] = []
            continue
        if cur_name is not None:
            comp_lines[cur_name].append(raw)

    comps, entry = parse_computations(text)
    memo: dict[str, tuple[dict, dict, dict]] = {}

    def total(name: str, depth=0) -> tuple[dict, dict, dict]:
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        if comp is None or depth > 16:
            z = {c: 0 for c in COLLECTIVES}
            return z, dict(z), dict(z)
        b = dict(comp.coll_bytes)
        n = dict(comp.coll_counts)
        f = dict(comp.coll_bytes_f32)
        for cond, body in comp.whiles:
            trips = _trip_count(comp_lines.get(cond, []))
            bb, bn, bf = total(body, depth + 1)
            for c in COLLECTIVES:
                b[c] += trips * bb[c]
                n[c] += trips * bn[c]
                f[c] += trips * bf[c]
        memo[name] = (b, n, f)
        return b, n, f

    if entry is None:
        # fall back: flat sum
        b = {c: 0 for c in COLLECTIVES}
        n = {c: 0 for c in COLLECTIVES}
        f = {c: 0 for c in COLLECTIVES}
        for comp in comps.values():
            for c in COLLECTIVES:
                b[c] += comp.coll_bytes[c]
                n[c] += comp.coll_counts[c]
                f[c] += comp.coll_bytes_f32[c]
        return {"bytes": b, "counts": n, "bytes_f32": f, "scaled": False}
    b, n, f = total(entry)
    return {"bytes": b, "counts": n, "bytes_f32": f, "scaled": True}


def load_hlo(path: str) -> str:
    if path.endswith(".gz"):
        with gzip.open(path, "rt") as f:
            return f.read()
    with open(path) as f:
        return f.read()


# effective link-bytes multipliers (ring algorithms)
LINK_FACTOR = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}


def link_bytes(totals: dict) -> float:
    return sum(LINK_FACTOR[c] * totals["bytes"][c] for c in COLLECTIVES)
