"""Whole-pipeline Pallas megakernel: replay → tag → partition → convert in
one kernel launch per partition (paper §3's device-residency discipline).

The staged pallas path bounces every ``(N,)``-sized intermediate through
HBM between kernels: the replay's class stream, the tag arrays, the
partition's destination map (whose perm-inversion scatter was raw XLA —
``kernels/partition/ops.py``), the field index, and the windowed numparse
gathers.  This kernel keeps all of them VMEM-resident:

  1. **Replay** — the ``dfa_scan`` fori-loop (one-hot select chains over the
     statically unrolled |S|·|G| transition/emission tables) re-simulates
     each chunk from its scan-derived start state, accumulating the class
     stream in a carry instead of an HBM output.
  2. **Ids** — record/column ids via the flat §3.2 cumulative-sum form
     (``offsets.symbol_ids``), bit-identical to the two-level chunk-summary
     form the staged path uses (the forms are cross-checked in tests).
  3. **Tagging** — ``tagging.tag_symbols`` replicated per mode; the
     selected-columns projection unrolls statically over the schema (an
     OR-chain of ``column_id == c`` compares — no gather).
  4. **Partition** — the ``scatter2`` blocked radix pass (per-block uint8
     histograms + intra-block ranks + inter-block scan) computes each
     symbol's destination, and the destination map is consumed in
     *apply-form*: ``out.at[dest].set(payload)`` writes the CSS and the
     sorted tag/flag streams directly — the perm-inversion scatter plus the
     downstream ``apply_partition`` gathers fold into one in-kernel
     scatter (a stable partition's destinations are unique, so the two
     forms are exactly equivalent: ``perm[dest[i]] = i``).
  5. **Field index** — ``fields.field_index_{tagged,terminated}`` replicated
     with in-kernel ``.at[seg].min`` / ``.at[seg].add`` segment reductions
     (the int32 identity of ``min`` is ``INT32_MAX``, matching
     ``segment_min``'s empty-segment fill bit-for-bit).
  6. **Convert** — the shared :mod:`repro.kernels.numparse.cores` arithmetic
     runs per converted column on offsets that never left the kernel, with
     ``block_rows = max_records`` (row-independent arithmetic, so the
     blocking difference vs the staged kernels cannot change results).

Outputs are the pipeline's *products* only — CSS, column extents, field
index, per-column values, per-record field counts, and four scalars — so
nothing ``(N,)``- or ``(R,)``-shaped is ever written to HBM and read back
by a later stage (pinned by ``tests/jaxpr_utils.hbm_roundtrips_outside_pallas``).

Interpret mode (this container) executes every step exactly.  On real
hardware the in-kernel scatters/gathers are Mosaic dynamic VMEM addressing
— the same caveat as the fused numparse gather — and the whole working set
(≈ ``N × ~12 B`` for the class/tag/rank intermediates plus the ``(C, K)``
byte block) must fit VMEM, so the executor gates this path behind a static
byte cap (``ParseBackend.fused_max_bytes``) and falls back to the staged
composition above it; see ``docs/ARCHITECTURE.md`` §fused-pipeline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dfa import DATA, FIELD_DELIM, RECORD_DELIM, TERMINATOR_BYTE, Dfa
from repro.kernels.dfa_scan.dfa_scan import _group_select
from repro.kernels.numparse import cores

#: Partition rank-block width: intra-block ranks must fit uint8 (< 256), and
#: 128 matches the TPU lane count (same tiling as ``partition_scatter2``).
RANK_BLOCK = 128
# Plain Python int: pallas kernels may not capture traced module constants.
_I32_MAX = 2**31 - 1


def _make_pipeline_kernel(
    dfa: Dfa,
    n_chunks: int,
    chunk_bytes: int,
    *,
    tagging: str,
    n_cols: int,
    max_records: int,
    selected,
    convert,
):
    """Build the megakernel for one static shape + plan.

    ``convert`` is a tuple of ``(col_idx, dtype, width)`` for the non-str
    columns, in output order; the kernel emits ``(value, ok)`` refs per
    entry after the fixed outputs.
    """
    S, G = dfa.n_states, dfa.n_groups
    group_bytes = dfa.group_bytes
    t_flat = tuple(int(x) for x in dfa.transition.reshape(-1))
    e_flat = tuple(int(x) for x in dfa.emission.reshape(-1))
    inv = dfa.invalid_state
    C, K = n_chunks, chunk_bytes
    N = C * K
    M = max_records
    NB = -(-N // RANK_BLOCK)
    PAD = NB * RANK_BLOCK - N
    n_segs = n_cols * M

    def kernel(chunks_ref, start_ref, css_ref, col_start_ref,
               col_count_ref, off_ref, len_ref, pres_ref, fpr_ref, meta_ref,
               *val_refs):
        raw_u8 = chunks_ref[...].reshape(N)               # (N,) uint8
        data = chunks_ref[...].astype(jnp.int32)          # (C, K)
        state0 = start_ref[...].astype(jnp.int32).reshape(C)

        # -- 1. replay (dfa_scan one-hot select chains, classes in carry) --
        def body(k, carry):
            state, cls_buf = carry
            byte = jax.lax.dynamic_slice(data, (0, k), (C, 1))[:, 0]
            g = _group_select(byte, group_bytes, G)
            idx = state * G + g  # (C,) in [0, S*G)
            new = jnp.zeros_like(state)
            cls = jnp.zeros_like(state)
            for j in range(S * G):
                hit = idx == j
                new = jnp.where(hit, t_flat[j], new)
                cls = jnp.where(hit, e_flat[j], cls)
            cls_buf = jax.lax.dynamic_update_slice(cls_buf, cls[:, None], (0, k))
            return new, cls_buf

        end_states, cls_chunks = jax.lax.fori_loop(
            0, K, body, (state0, jnp.zeros((C, K), jnp.int32))
        )
        end_state = end_states[C - 1]
        if inv is None:
            saw_inv = jnp.int32(0)
        else:  # the invalid sink is absorbing: "ever hit" == "ended there"
            saw_inv = jnp.any(end_states == inv).astype(jnp.int32)

        # -- 2. record/column ids (offsets.symbol_ids, flat form) ----------
        cls = cls_chunks.reshape(N)
        pos = jnp.arange(N, dtype=jnp.int32)
        is_rec = cls == RECORD_DELIM
        is_fld = cls == FIELD_DELIM
        rec_i32 = is_rec.astype(jnp.int32)
        fld_i32 = is_fld.astype(jnp.int32)
        rec_incl = jnp.cumsum(rec_i32)
        record_id = rec_incl - rec_i32
        fld_incl = jnp.cumsum(fld_i32)
        fld_excl = fld_incl - fld_i32
        last_rec_incl = jax.lax.cummax(jnp.where(is_rec, pos, -1))
        last_rec_excl = jnp.concatenate(
            [jnp.full((1,), -1, jnp.int32), last_rec_incl[:-1]]
        )
        base = jnp.where(last_rec_excl >= 0, fld_incl[jnp.clip(last_rec_excl, 0)], 0)
        column_id = fld_excl - base
        n_records = jnp.sum(rec_i32)

        # -- 3. tagging (tagging.tag_symbols per mode) ---------------------
        is_data = cls == DATA
        is_delim = is_rec | is_fld
        if tagging == "tagged":
            keep = is_data
            symbol = raw_u8
            flag = None
        elif tagging == "inline":
            keep = is_data | is_delim
            symbol = jnp.where(is_delim, jnp.uint8(TERMINATOR_BYTE), raw_u8)
            flag = is_delim
        else:  # vector
            keep = is_data | is_delim
            symbol = raw_u8
            flag = is_delim
        in_schema = column_id < n_cols
        if selected is not None:
            # §4.3 projection, unrolled statically over the schema — the
            # OR-chain is equivalent to the staged path's clip-gather.
            sel = jnp.zeros((N,), jnp.bool_)
            for c, s in enumerate(selected):
                if s:
                    sel |= column_id == c
            in_schema &= sel
        col_tag = jnp.where(keep & in_schema, column_id, n_cols).astype(jnp.int32)

        # -- 4. stable partition (scatter2 blocked radix pass) -------------
        if PAD:
            tags = jnp.concatenate(
                [col_tag, jnp.full((PAD,), n_cols, jnp.int32)]
            )
        else:
            tags = col_tag
        tags2 = tags.reshape(NB, RANK_BLOCK)
        colsv = jnp.arange(n_cols + 1, dtype=jnp.int32)
        onehot8 = (tags2[:, :, None] == colsv[None, None, :]).astype(jnp.uint8)
        block_hist = onehot8.sum(axis=1, dtype=jnp.int32)        # (NB, C+1)
        ranks8 = jnp.cumsum(onehot8, axis=1, dtype=jnp.uint8)    # inclusive
        own_rank = jnp.take_along_axis(
            ranks8, tags2[:, :, None], axis=2
        )[:, :, 0].astype(jnp.int32) - 1                         # exclusive
        blk_excl = jnp.cumsum(block_hist, axis=0) - block_hist
        count = block_hist.sum(axis=0)
        col_start = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(count)[:-1]]
        )
        count = count.at[-1].add(-PAD)
        dest = (col_start[tags2] + jnp.take_along_axis(blk_excl, tags2, axis=1)
                + own_rank).reshape(-1)[:N]

        # Apply-form consumption of the destination map: a stable
        # partition's dest is a bijection on [0, N), so scattering payloads
        # to dest IS apply_partition(perm, payload) with perm[dest[i]] = i —
        # the XLA perm-inversion scatter and the downstream gathers fold
        # into these writes.
        css = jnp.zeros((N,), jnp.uint8).at[dest].set(symbol)
        rec_sorted = jnp.zeros((N,), jnp.int32).at[dest].set(record_id)
        col_sorted = jnp.zeros((N,), jnp.int32).at[dest].set(col_tag)

        # -- 5. field index (fields.field_index_{tagged,terminated}) -------
        in_range = (col_sorted < n_cols) & (rec_sorted < M)
        if tagging == "tagged":
            seg = jnp.where(in_range, col_sorted * M + rec_sorted, n_segs)
            offset = jnp.full((n_segs + 1,), _I32_MAX, jnp.int32
                              ).at[seg].min(pos)[:-1]
            length = jnp.zeros((n_segs + 1,), jnp.int32).at[seg].add(1)[:-1]
            present = (length > 0).reshape(n_cols, M)
            offset = jnp.where(length > 0, offset, 0).reshape(n_cols, M)
            length = length.reshape(n_cols, M)
        else:
            flag_sorted = jnp.zeros((N,), jnp.bool_).at[dest].set(flag)
            valid_t = flag_sorted & in_range
            seg = jnp.where(valid_t, col_sorted * M + rec_sorted, n_segs)
            end = jnp.full((n_segs + 1,), _I32_MAX, jnp.int32).at[seg].min(
                jnp.where(valid_t, pos, _I32_MAX)
            )[:-1].reshape(n_cols, M)
            present = end < _I32_MAX
            # Same absent-tolerant predecessor recurrence as
            # fields.field_index_terminated: start after the last *present*
            # terminator (exclusive running max), the column start when
            # none precedes.
            prev_end = jax.lax.cummax(jnp.where(present, end, -1), axis=1)
            prev_end = jnp.concatenate(
                [jnp.full((n_cols, 1), -1, jnp.int32), prev_end[:, :-1]],
                axis=1,
            )
            start_f = jnp.where(
                prev_end >= 0, prev_end + 1, col_start[:n_cols, None]
            )
            length = jnp.where(present, end - start_f, 0).astype(jnp.int32)
            offset = jnp.where(present, start_f, 0).astype(jnp.int32)

        # -- 6. typed conversion (shared numparse cores, in-kernel offsets) -
        for i, (c, dtype, width) in enumerate(convert):
            # Width-pad so offset + lane never leaves the buffer (offsets of
            # empty/padding rows clamp to [0, N]).
            css_pad = jnp.concatenate([css, jnp.zeros((width,), jnp.uint8)])
            off_c = jnp.clip(offset[c], 0, N)
            ln_c = length[c]
            lane = jax.lax.broadcasted_iota(jnp.int32, (M, width), 1)
            b = css_pad[off_c[:, None] + lane].astype(jnp.int32)
            cols = [b[:, w] for w in range(width)]
            if dtype == "int32":
                val, ok = cores._int_arith(cols, ln_c)
            elif dtype == "float32":
                val, ok = cores._float_arith(cols, ln_c)
            else:  # date
                val, ok = cores._date_arith(cols, ln_c)
            val_refs[2 * i][...] = val[None, :]
            val_refs[2 * i + 1][...] = ok.astype(jnp.int32)[None, :]

        # -- §4.3 validation inputs + §4.4 carry scalars -------------------
        rid = jnp.where(record_id < M, record_id, M)
        fpr = jnp.zeros((M + 1,), jnp.int32).at[rid].add(fld_i32)[:-1] + 1
        last_record_end = jnp.max(jnp.where(is_rec, pos, -1))

        css_ref[...] = css[None, :]
        col_start_ref[...] = col_start[None, :]
        col_count_ref[...] = count[None, :]
        off_ref[...] = offset
        len_ref[...] = length
        pres_ref[...] = present.astype(jnp.int32)
        fpr_ref[...] = fpr[None, :]
        meta_ref[...] = jnp.stack(
            [end_state, saw_inv, last_record_end, n_records]
        ).astype(jnp.int32)[None, :]

    return kernel


def pipeline_call(
    chunks: jax.Array,
    start_states: jax.Array,
    dfa: Dfa,
    *,
    tagging: str,
    n_cols: int,
    max_records: int,
    selected,
    convert,
    interpret: bool,
):
    """Run the megakernel over one partition.

    Args:
      chunks: ``(C, K) uint8`` raw bytes.
      start_states: ``(C,) int32`` per-chunk start states (from the §3.1
        composite scan — the only upstream stage; it is O(C·S), never O(N)).
      convert: tuple of ``(col_idx, dtype, width)`` for non-str columns.

    Returns ``(css (N,) u8, col_start (n_cols+1,) i32, col_count, offset
    (n_cols, M) i32, length, present (n_cols, M) bool, fields_per_rec (M,)
    i32, meta (4,) i32 [end_state, saw_invalid, last_record_end,
    n_records], values)`` with ``values`` a tuple of ``(value (M,), ok (M,)
    bool)`` per convert entry.
    """
    c, k = chunks.shape
    n = c * k
    m = max_records
    kernel = _make_pipeline_kernel(
        dfa, c, k, tagging=tagging, n_cols=n_cols, max_records=m,
        selected=selected, convert=convert,
    )
    fixed_shapes = [
        jax.ShapeDtypeStruct((1, n), jnp.uint8),           # css
        jax.ShapeDtypeStruct((1, n_cols + 1), jnp.int32),  # col_start
        jax.ShapeDtypeStruct((1, n_cols + 1), jnp.int32),  # col_count
        jax.ShapeDtypeStruct((n_cols, m), jnp.int32),      # field offset
        jax.ShapeDtypeStruct((n_cols, m), jnp.int32),      # field length
        jax.ShapeDtypeStruct((n_cols, m), jnp.int32),      # field present
        jax.ShapeDtypeStruct((1, m), jnp.int32),           # fields_per_rec
        jax.ShapeDtypeStruct((1, 4), jnp.int32),           # meta scalars
    ]
    conv_shapes = []
    for _, dtype, _ in convert:
        vdt = jnp.float32 if dtype == "float32" else jnp.int32
        conv_shapes += [
            jax.ShapeDtypeStruct((1, m), vdt),             # value
            jax.ShapeDtypeStruct((1, m), jnp.int32),       # ok
        ]
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[full((c, k)), full((c, 1))],
        out_specs=[full(s.shape) for s in fixed_shapes + conv_shapes],
        out_shape=fixed_shapes + conv_shapes,
        interpret=interpret,
    )(chunks, start_states.astype(jnp.int32)[:, None])
    css, col_start, col_count, off, ln, pres, fpr, meta = out[:8]
    values = tuple(
        (out[8 + 2 * i][0], out[8 + 2 * i + 1][0].astype(bool))
        for i in range(len(convert))
    )
    return (css[0], col_start[0], col_count[0], off, ln, pres.astype(bool),
            fpr[0], meta[0], values)
