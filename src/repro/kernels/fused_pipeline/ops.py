"""Op layer for the whole-pipeline megakernel: plan-shaped in, typed
columns out.

``fused_parse`` adapts a :class:`repro.core.stages.MaterializePlan`-shaped
argument set onto :func:`fused_pipeline.pipeline_call` and finishes the
*products* that need no ``(N,)`` data — ``Parsed`` normalisation (identical
to the staged composition: ``valid = ok & ~empty``, invalid numerics
zeroed) and the ``str`` no-op columns, whose ``Parsed`` is pure field-index
bookkeeping (``typeconv.parse_string_noop``).  Everything upstream of the
kernel is the §3.1 composite scan, which is O(C·S); everything downstream
is O(max_records) or scalar — the backend executor
(``core.backends._pl_execute``) composes both ends.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import typeconv as typeconv_mod
from repro.core.dfa import Dfa
from repro.kernels.fused_pipeline import fused_pipeline
from repro.kernels.numparse.cores import DATE_WIDTH


class FusedParse(NamedTuple):
    """The megakernel's per-partition products (no ``(N,)`` round-trips)."""

    css: jax.Array             # (N,) uint8 partitioned symbols
    col_start: jax.Array       # (n_cols+1,) int32
    col_count: jax.Array       # (n_cols+1,) int32
    offset: jax.Array          # (n_cols, max_records) int32
    length: jax.Array          # (n_cols, max_records) int32
    present: jax.Array         # (n_cols, max_records) bool
    fields_per_rec: jax.Array  # (max_records,) int32 — §4.3 column counts
    end_state: jax.Array       # () int32
    saw_invalid: jax.Array     # () bool — any chunk hit the invalid sink
    last_record_end: jax.Array # () int32 — §4.4 carry boundary (−1 if none)
    n_records: jax.Array       # () int32
    values: Dict[str, typeconv_mod.Parsed]


def _width_for(dtype: str, int_width: int, float_width: int) -> int:
    if dtype == "int32":
        return int_width
    if dtype == "float32":
        return float_width
    return DATE_WIDTH


def fused_parse(
    chunks: jax.Array,
    start_states: jax.Array,
    dfa: Dfa,
    *,
    tagging: str,
    n_cols: int,
    max_records: int,
    selected,
    convert: Tuple[Tuple[str, int, str], ...],
    int_width: int,
    float_width: int,
    interpret: bool,
) -> FusedParse:
    """One partition through the megakernel (see module docstring).

    ``convert`` is the plan's ``(name, col_idx, dtype)`` tuple — ``str``
    entries are served from the field index outside the kernel; the rest
    convert in-kernel through the shared numparse cores.
    """
    kconv = tuple(
        (c, dtype, _width_for(dtype, int_width, float_width))
        for _, c, dtype in convert if dtype != "str"
    )
    css, col_start, col_count, off, ln, pres, fpr, meta, kvals = (
        fused_pipeline.pipeline_call(
            chunks, start_states, dfa, tagging=tagging, n_cols=n_cols,
            max_records=max_records, selected=selected, convert=kconv,
            interpret=interpret,
        )
    )

    values: Dict[str, typeconv_mod.Parsed] = {}
    ki = 0
    for name, c, dtype in convert:
        empty = ln[c] == 0
        if dtype == "str":
            # typeconv.parse_string_noop: value IS the field offset.
            values[name] = typeconv_mod.Parsed(off[c], ~empty, empty)
            continue
        val, ok = kvals[ki]
        ki += 1
        valid = ok & ~empty
        # Same normalisation as stages.materialize: garbage values are
        # meaningless (``valid`` gates them) — zero them so every path
        # agrees bit-for-bit.
        values[name] = typeconv_mod.Parsed(
            jnp.where(valid, val, jnp.zeros_like(val)), valid, empty
        )

    return FusedParse(
        css=css,
        col_start=col_start,
        col_count=col_count,
        offset=off,
        length=ln,
        present=pres,
        fields_per_rec=fpr,
        end_state=meta[0],
        saw_invalid=meta[1].astype(bool),
        last_record_end=meta[2],
        n_records=meta[3],
        values=values,
    )
