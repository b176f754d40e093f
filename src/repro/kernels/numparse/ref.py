"""Pure-jnp oracle for numparse — delegates to repro.core.typeconv."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import typeconv


def _as_column(field_bytes):
    """Reconstruct a css/offset view: fields are the rows themselves."""
    r, w = field_bytes.shape
    css = field_bytes.reshape(-1)
    offsets = jnp.arange(r, dtype=jnp.int32) * w
    return css, offsets, w


def parse_int_fields(field_bytes, lengths):
    """Same contract as the kernel: gathered (R, W) bytes + lengths."""
    css, offsets, w = _as_column(field_bytes)
    parsed = typeconv.parse_int(css, offsets, lengths, width=w)
    return parsed.value, parsed.valid


def parse_float_fields(field_bytes, lengths):
    css, offsets, w = _as_column(field_bytes)
    parsed = typeconv.parse_float(css, offsets, lengths, width=w)
    return parsed.value, parsed.valid


def parse_date_fields(field_bytes, lengths):
    r, w = field_bytes.shape
    if w < 19:  # parse_date always gathers 19 bytes; keep rows self-contained
        pad = jnp.zeros((r, 19 - w), field_bytes.dtype)
        field_bytes = jnp.concatenate([field_bytes, pad], axis=1)
    css, offsets, _ = _as_column(field_bytes)
    parsed = typeconv.parse_date(css, offsets, lengths)
    return parsed.value, parsed.valid
