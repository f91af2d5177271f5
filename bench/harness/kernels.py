"""Bytes that a kernel call moves, read from the shapes of its HLO op.

The device trace names each operation by its HLO text, for example::

    %interval_stab_classify_packed.1 = s32[1,16384]{1,0:T(1,128)S(1)}
        custom-call(s32[4,16384]{...} %bitcast.5, s32[4,16384]{...} %b.6,
                    s32[12,16384]{...} %bitcast.7), custom_call_target=...

A Pallas kernel is such a ``custom-call``. It reads each operand once and
writes its result once, so the bytes a call needs are the sizes of the
result and of the operands: the padded batch (the bucket rounded up to the
kernel's block) times the row widths the program laid out (4-word meta rows,
the interval slab's 2K words), as the shapes say. Each shape's layout says
where it lives: ``S(1)`` is the core's on-chip VMEM, where XLA often puts a
kernel's operands (the gathers before ``interval_stab`` write them there),
and no memory space is HBM. The least time of a call is that of the
slowest of the three streams at its peak.
"""
from __future__ import annotations

import re

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([\d,]*)\]")
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``interval_stab_classify_packed.1`` of ``%interval_stab_...1 = ...``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(text: str) -> str:
    """The op's name without its ``.N`` suffix: ``fusion``, ``while``."""
    return op_name(text).split(".", 1)[0]


def is_custom_call(text: str) -> bool:
    return " custom-call(" in text


def _bytes(dtype: str, dims: str) -> int:
    size = DTYPE_BYTES[dtype]
    for d in filter(None, dims.split(",")):
        size *= int(d)
    return size


def _spaced(text: str):
    """(bytes, in HBM?) of every shape in ``text``; a layout that names a
    memory space other than 0 (``S(1)``: the core's VMEM) is on chip."""
    for m in _SHAPE.finditer(text):
        layout = text[m.end():m.end() + 64]
        layout = layout[:layout.find("}") + 1] if layout.startswith("{") \
            else ""
        on_chip = "S(" in layout and "S(0)" not in layout
        yield _bytes(*m.groups()), not on_chip


def call_traffic(text: str):
    """(HBM bytes, on-chip bytes read, on-chip bytes written) of one
    custom-call: its operands are read once and its result written once,
    each from or to the memory its layout names. None where the text does
    not hold them."""
    try:
        _, rhs = text.split(" = ", 1)
        start = rhs.index("custom-call(") + len("custom-call(")
    except ValueError:
        return None
    if _SHAPE.match(rhs.strip()) is None:
        return None
    depth, end = 1, start
    while end < len(rhs) and depth:
        depth += {"(": 1, ")": -1}.get(rhs[end], 0)
        end += 1
    if depth:
        return None
    hbm = vr = vw = 0
    for size, in_hbm in _spaced(rhs[:start]):      # the result
        if in_hbm:
            hbm += size
        else:
            vw += size
    for size, in_hbm in _spaced(rhs[start:end - 1]):   # the operands
        if in_hbm:
            hbm += size
        else:
            vr += size
    return hbm, vr, vw


def least_seconds(traffic, peak: dict) -> float:
    """The least time the chip could move ``traffic`` in: the slowest of
    HBM, on-chip reads and on-chip writes at their peaks, which overlap."""
    hbm, vr, vw = traffic
    return max(hbm / peak["hbm_bytes_per_s"],
               vr / peak["vmem_read_bytes_per_s"],
               vw / peak["vmem_write_bytes_per_s"])
