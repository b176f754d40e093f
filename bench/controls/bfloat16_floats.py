"""The control one precision below a float32 configuration: the plain
reference with its float columns rounded to bfloat16."""
from __future__ import annotations

import numpy as np

from benchlib import check, oracle


def bf16(x: np.ndarray) -> np.ndarray:
    """float32 → bfloat16 (round to nearest even) → float32."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def view(data: bytes, schema) -> dict:
    return check.reference_view(oracle.parse(data), schema, float_round=bf16)
