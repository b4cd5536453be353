"""The plain reference: what every record holds, and the row comparison.

It imports nothing of the program under test and takes nothing it made:
a record's value is recomputed here from ``(seed, id)`` with numpy, and a
served row is correct only if it equals that value bit for bit (the
store's guarantee: a read returns exactly the bytes stored).
"""
from __future__ import annotations

import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)


def _mix(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(15))
    x = x * _M2
    return x ^ (x >> np.uint32(16))


def rows(seed: int, ids: np.ndarray, width: int) -> np.ndarray:
    """Reference rows ``[len(ids), width]`` f32 of records ``ids``."""
    with np.errstate(over="ignore"):
        k0 = _mix(np.uint32(int(seed) & 0xFFFFFFFF))
        k1 = _mix(np.uint32(((int(seed) >> 32) & 0xFFFFFFFF) + 0x9E3779B9
                            & 0xFFFFFFFF))
        x = (np.asarray(ids, np.uint32)[:, None] * np.uint32(width)
             + np.arange(width, dtype=np.uint32)[None, :])
        h = _mix(_mix(x ^ k0) ^ k1)
    return (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest even) and widened back: the
    control, the reference one precision below the stored float32."""
    b = x.astype(np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


def mismatches(seed: int, ids: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Per row of ``served``: does it differ from the reference in any bit?"""
    want = rows(seed, ids, served.shape[1]).view(np.uint32)
    return np.any(served.view(np.uint32) != want, axis=1)
