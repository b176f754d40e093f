"""Multi-device ParPaRaw: the paper's scans stretched across a TPU mesh.

The single-device pipeline needs three pieces of global information that
cross shard boundaries, each a tiny associative summary per device:

    1. the DFA state-transition composite      — (|S|,) int32
    2. the record count                        — ()   int32
    3. the (abs/rel, column-offset) pair       — 2 ×  int32

Inside ``shard_map`` every device folds its local chunks, ``all_gather``s
the per-device summaries (O(devices · |S|) bytes — independent of input
size), computes its exclusive prefix locally, and proceeds exactly like the
single-device parser: the *complete* ``stages.execute_plan`` composition —
context → ids → materialize → typeconv → §4.3 validation — runs per shard,
including the pallas kernels and the ``fuse_pipeline`` megakernel path.
The cross-device hooks are packaged as a :class:`stages.ParseStitch`
(:func:`mesh_stitch` below); no collective ever moves input-sized data.
This is the collective-level instance of the paper's decoupled-lookback
scan (DESIGN.md §3), and the reason throughput scales linearly with device
count: per-device work is N/D bytes, the stitching collective is constant.

Validation decomposes along record ownership — a record belongs to the
shard holding its terminating record delimiter.  Each shard's
``fields_per_record`` is exact for the records it owns once the head
record is corrected by the column seed (the field delimiters accumulated
since the last record delimiter *before* the shard — the same (tag, off)
semigroup that seeds the column ids), so the global min/max/conformance
reduce with O(1) ``pmin``/``pmax``/``psum`` collectives; ``end_state_ok``
is contributed by the last shard alone.

Each device emits its own columnar shard (per-host Arrow batches — what a
real ingest pipeline wants); record ids are global so shards concatenate
trivially, and :meth:`DistributedParser.assemble` stitches the boundary
records (whose bytes straddle shards) into a single-parser-identical
Arrow-layout table on the host.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import backends as backends_mod
from repro.core import offsets as offsets_mod
from repro.core import stages as stages_mod
from repro.core import transition as tr
from repro.core import typeconv as typeconv_mod
from repro.core import validation as validation_mod
from repro.core.parser import ParserConfig


class ShardedParse(NamedTuple):
    """Per-device columnar shard with globally consistent record ids.

    Leading axes are sharded over the mesh, so the assembled pytree holds
    per-shard arrays back to back: ``css`` is ``(D·N_local,)``,
    ``col_start`` is ``(D·(n_cols+1),)``, the field index is
    ``(D·n_cols, max_records)``, each ``values`` leaf is
    ``(D·max_records,)`` — reshape with a leading ``D`` to address shard
    ``d``.  ``validation`` carries the *global* §4.3 scalars (replicated)
    with per-shard ``record_ok`` on shard-local record ids.
    """

    classes: jax.Array       # (C_local·K,) uint8 per device (global: (C·K,))
    css: jax.Array           # (N_local,) uint8 partitioned symbols
    col_start: jax.Array     # (n_cols+1,) int32 per shard
    col_count: jax.Array     # (n_cols+1,) int32
    field_offset: jax.Array  # (n_cols, max_records) int32, local CSS positions
    field_length: jax.Array  # (n_cols, max_records) int32
    field_present: jax.Array # (n_cols, max_records) bool
    values: Dict[str, typeconv_mod.Parsed]  # per-shard typed columns
    validation: validation_mod.Validation   # global scalars + local record_ok
    rec_base: jax.Array      # () int32 — first global record id in this shard
    n_records: jax.Array     # () int32 — global record count (replicated)


def _device_prefix_vec(local_comp: jax.Array, axis) -> jax.Array:
    """Exclusive composite of all preceding devices' transition summaries."""
    all_comps = jax.lax.all_gather(local_comp, axis)  # (D, S)
    inc = jax.lax.associative_scan(tr.compose, all_comps, axis=0)
    me = jax.lax.axis_index(axis)
    ident = tr.identity_vector(local_comp.shape[-1])
    prev = inc[jnp.maximum(me - 1, 0)]
    return jnp.where(me == 0, ident, prev)


def _device_prefix_offsets(rec: jax.Array, col_t: jax.Array, col_o: jax.Array, axis):
    """Exclusive record-count and column-offset prefixes across devices."""
    all_rec = jax.lax.all_gather(rec, axis)          # (D,)
    me = jax.lax.axis_index(axis)
    rec_prefix = (jnp.cumsum(all_rec) - all_rec)[me]
    n_total = jnp.sum(all_rec)

    all_t = jax.lax.all_gather(col_t, axis)
    all_o = jax.lax.all_gather(col_o, axis)
    t_inc, o_inc = jax.lax.associative_scan(offsets_mod.combine_col, (all_t, all_o), axis=0)
    prev_t = t_inc[jnp.maximum(me - 1, 0)]
    prev_o = o_inc[jnp.maximum(me - 1, 0)]
    t = jnp.where(me == 0, offsets_mod.REL, prev_t)
    o = jnp.where(me == 0, 0, prev_o)
    return rec_prefix, t, o, n_total


def _and_reduce(x: jax.Array, axis) -> jax.Array:
    """AND across the mesh axis — a () int32 psum, never input-sized."""
    return jax.lax.psum(jnp.logical_not(x).astype(jnp.int32), axis) == 0


def mesh_stitch(cfg, plan: stages_mod.ParsePlan, axis,
                n_devices: int) -> stages_mod.ParseStitch:
    """Cross-device hooks for :func:`stages.execute_plan` under shard_map.

    Every hook exchanges only O(D · |S|) summary data (see
    ``stages.ParseStitch``); ``n_devices`` is the static mesh extent along
    ``axis`` (a name or tuple of names, linearized).
    """
    expected = plan.expected_columns
    accept = np.asarray(cfg.dfa.accept)

    def prefix_fn(vecs):
        return _device_prefix_vec(tr.fold_vectors(vecs), axis)

    def offsets_fn(summ):
        rec_l, t_l, o_l = offsets_mod.fold_summary(summ)
        rec_base, t_p, o_p, n_total = _device_prefix_offsets(rec_l, t_l, o_l, axis)
        local_offs = offsets_mod.scan_chunk_offsets(summ)
        g_t, g_o = offsets_mod.combine_col(
            (jnp.broadcast_to(t_p, local_offs.col_tag.shape),
             jnp.broadcast_to(o_p, local_offs.col_offset.shape)),
            (local_offs.col_tag, local_offs.col_offset),
        )
        offs = offsets_mod.ChunkOffsets(local_offs.rec_offset + rec_base, g_t, g_o)
        return offs, rec_base, o_p, n_total

    def validation_fn(fields_per_rec, n_local, end_state, saw_invalid, n_total):
        # §4.3 across the mesh: per-shard counts are exact for owned
        # records (head seeded by the caller), so every global quantity is
        # an O(1) reduction — the same arithmetic validation.validate runs
        # on the flat class stream, decomposed along record ownership.
        m = fields_per_rec.shape[0]
        is_last = jax.lax.axis_index(axis) == n_devices - 1
        end_ok = _and_reduce(
            jnp.where(is_last, jnp.asarray(accept)[end_state.astype(jnp.int32)], True),
            axis)
        no_inv = _and_reduce(~saw_invalid, axis)
        rec_live = jnp.arange(m) < n_local
        big = jnp.int32(2**31 - 1)
        minc = jax.lax.pmin(jnp.min(jnp.where(rec_live, fields_per_rec, big)), axis)
        maxc = jax.lax.pmax(jnp.max(jnp.where(rec_live, fields_per_rec, 0)), axis)
        if expected is None:
            record_ok = rec_live
        else:
            record_ok = rec_live & (fields_per_rec == expected)
        ok = end_ok & no_inv
        if expected is not None:
            ok &= _and_reduce(jnp.all(record_ok | ~rec_live), axis)
        return validation_mod.Validation(
            ok, end_ok, no_inv, n_total.astype(jnp.int32), minc, maxc, record_ok
        )

    return stages_mod.ParseStitch(prefix_fn, offsets_fn, validation_fn)


def _shard_parse(chunks: jax.Array, cfg: ParserConfig,
                 plan: stages_mod.ParsePlan,
                 stitch: stages_mod.ParseStitch, axis) -> ShardedParse:
    """Runs on every device under shard_map; ``chunks (C_local, K)``."""
    backend = backends_mod.get_backend(cfg.backend)

    # The complete per-partition composition — staged or megakernel-fused,
    # exactly as the single-device Parser runs it — with the cross-device
    # stitch plugged in.
    res = stages_mod.execute_plan(chunks, plan, cfg, backend, stitch=stitch)

    # rec_base for shard concatenation / host assembly: re-fold the chunk
    # summaries.  Identical ops to the fold inside execute_plan (or, on the
    # fused path, inside the backend's stitched summary pass), so XLA CSE
    # dedupes it — and it is O(C·|S|) regardless.
    ctx = stages_mod.determine_contexts(chunks, cfg, backend,
                                        prefix_fn=stitch.prefix_fn)
    _, rec_base, _, _ = stitch.offsets_fn(ctx.summaries)

    return ShardedParse(
        classes=ctx.classes.reshape(-1),
        css=res.css,
        col_start=res.col_start,
        col_count=res.col_count,
        field_offset=res.field_offset,
        field_length=res.field_length,
        field_present=res.field_present,
        values=res.values,
        validation=res.validation,
        rec_base=rec_base.reshape(1),  # rank-1 so shards concatenate
        n_records=res.validation.n_records,
    )


class DistributedParser:
    """shard_map-wrapped ParPaRaw over a device mesh.

    ``max_records`` in the config is *per shard* here.  The input byte
    buffer is sharded along its chunk axis over ``axis_names`` (all data
    axes flattened); outputs keep the same sharding, one columnar shard per
    device.

    ``convert=True`` (the default) runs the full plan per shard — CSS +
    field index + typed columns + global validation all materialize
    device-locally, through whichever backend/tagging/fusion path the
    config picks.  ``convert=False`` keeps the historical index-only
    export (shards ship the CSS + field index; hosts convert), which the
    dry-run roofline harness still uses.
    """

    def __init__(self, cfg: ParserConfig, mesh: Mesh,
                 axis_names: Sequence[str] = ("data",), convert: bool = True):
        self.cfg = cfg
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.n_devices = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        #: Static ParsePlan resolved once — the same planning layer every
        #: driver adopts (staged or fused per cfg.fuse_pipeline).
        self.plan = stages_mod.plan_parse(
            cfg, backends_mod.get_backend(cfg.backend), convert=convert
        )
        axis = self.axis_names
        stitch = mesh_stitch(cfg, self.plan, axis, self.n_devices)
        spec_in = P(axis, None)
        out_specs = ShardedParse(
            classes=P(axis),
            css=P(axis),
            col_start=P(axis),
            col_count=P(axis),
            field_offset=P(axis, None),
            field_length=P(axis, None),
            field_present=P(axis, None),
            values={name: typeconv_mod.Parsed(P(axis), P(axis), P(axis))
                    for name, _, _ in self.plan.materialize.convert},
            validation=validation_mod.Validation(
                ok=P(), end_state_ok=P(), no_invalid=P(), n_records=P(),
                min_columns=P(), max_columns=P(), record_ok=P(axis),
            ),
            rec_base=P(axis),
            n_records=P(),
        )

        plan = self.plan

        def wrapped(chunks):
            return _shard_parse(chunks, cfg, plan, stitch, axis)

        self._fn = jax.jit(
            shard_map(
                wrapped, mesh=mesh, in_specs=(spec_in,), out_specs=out_specs,
                check_vma=False,
            )
        )

    def prepare(self, data: bytes) -> np.ndarray:
        """``Parser.prepare`` plus padding the chunk *count* to a mesh
        multiple — appended all-PAD chunks are inert (identity transitions,
        no symbols), exactly like the in-chunk PAD tail."""
        from repro.core.parser import Parser

        chunks = Parser(self.cfg).prepare(data)
        k = self.cfg.chunk_size
        n = chunks.shape[0]
        target = -(-n // self.n_devices) * self.n_devices
        if target != n:
            from repro.core.dfa import PAD_BYTE
            pad = np.full((target - n, k), PAD_BYTE, np.uint8)
            chunks = np.concatenate([chunks, pad], axis=0)
        return chunks

    def parse_chunks(self, chunks) -> ShardedParse:
        return self._fn(chunks)

    def lower(self, n_chunks: int, chunk_bytes: int):
        """ShapeDtypeStruct lowering hook — the dry-run harness and the
        collective-accounting tests/bench compile this without data."""
        spec = jax.ShapeDtypeStruct((n_chunks, chunk_bytes), jnp.uint8)
        return self._fn.lower(spec)

    # -- host assembly -----------------------------------------------------

    def assemble(self, shards: ShardedParse) -> Dict[str, dict]:
        """Stitch the per-device shards into one Arrow-layout table,
        bit-identical to ``Parser.to_arrow`` on the unsharded input.

        Only *boundary* records need host work: record ``rec_base[d]`` (the
        first record owned by shard ``d ≥ 1``) may have bytes on earlier
        shards, so its fields are re-gathered by concatenating each
        holding shard's CSS piece — shard ``e`` holds a piece of record
        ``r`` iff ``0 ≤ r − rec_base[e] ≤ n_local[e]`` (its own records
        plus its unterminated tail) — and numeric fields re-parse through
        the reference converters (pinned bit-identical to the kernel
        paths by the parity suites).  Everything else is a pure gather
        from the owning shard.  O(n_records) host work, like the
        non-tagged ``to_arrow`` export.
        """
        cfg = self.cfg
        d_cnt = self.n_devices
        n_cols = len(cfg.schema.columns)
        m = cfg.max_records
        n_total = int(shards.n_records)
        rec_base = np.asarray(shards.rec_base).reshape(d_cnt).astype(np.int64)
        n_local = np.diff(np.append(rec_base, n_total))
        css = np.asarray(shards.css).reshape(d_cnt, -1)
        f_off = np.asarray(shards.field_offset).reshape(d_cnt, n_cols, m)
        f_len = np.asarray(shards.field_length).reshape(d_cnt, n_cols, m)
        f_pres = np.asarray(shards.field_present).reshape(d_cnt, n_cols, m)
        cs = np.asarray(shards.col_start).reshape(d_cnt, n_cols + 1)
        cc = np.asarray(shards.col_count).reshape(d_cnt, n_cols + 1)
        terminated = cfg.tagging != "tagged"

        rid = np.arange(n_total)
        owner = np.searchsorted(rec_base, rid, side="right") - 1
        local = rid - rec_base[owner]
        boundary = {int(rec_base[d]) for d in range(1, d_cnt)
                    if rec_base[d] < n_total}

        def tail_piece(e: int, c: int) -> np.ndarray:
            # Terminated modes index a field only on the shard holding its
            # terminator, so an unterminated tail piece has *no* entry on
            # the shard that holds its bytes.  Those bytes are exactly the
            # suffix of column c's CSS segment after the last terminated
            # field (off+len points at the terminator; skip it).
            ends = f_off[e, c] + f_len[e, c]
            ends = ends[f_pres[e, c].astype(bool)]
            lo = int(ends.max()) + 1 if ends.size else int(cs[e, c])
            return css[e, lo:int(cs[e, c]) + int(cc[e, c])]

        def field_bytes(r: int, c: int) -> np.ndarray:
            pieces = []
            for e in range(d_cnt):
                lr = r - rec_base[e]
                if lr < 0 or lr > n_local[e] or lr >= m:
                    continue
                if terminated and lr == n_local[e] and not f_pres[e, c, lr]:
                    b = tail_piece(e, c)
                    if b.size:
                        pieces.append(b)
                    continue
                length = int(f_len[e, c, lr])
                if length <= 0:
                    continue
                off = int(f_off[e, c, lr])
                pieces.append(css[e, off:off + length])
            return (np.concatenate(pieces) if pieces
                    else np.zeros(0, np.uint8))

        pad = max(cfg.int_width, cfg.float_width, 20)

        def reparse_field(r: int, c: int, dtype: str):
            b = field_bytes(r, c)
            buf = jnp.asarray(np.concatenate([b, np.zeros(pad, np.uint8)]))
            off = jnp.zeros((1,), jnp.int32)
            ln = jnp.full((1,), len(b), jnp.int32)
            p = (typeconv_mod.parse_int(buf, off, ln, width=cfg.int_width)
                 if dtype == "int32" else
                 typeconv_mod.parse_float(buf, off, ln, width=cfg.float_width)
                 if dtype == "float32" else
                 typeconv_mod.parse_date(buf, off, ln))
            valid = bool(p.valid[0])
            value = p.value[0] if valid else np.zeros((), np.asarray(p.value).dtype)
            return value, valid

        out: Dict[str, dict] = {}
        for c, col in enumerate(cfg.schema.columns):
            if not col.selected:
                continue
            if col.dtype == "str":
                datas, lens = [], np.zeros(n_total, np.int32)
                for r in range(n_total):
                    if r in boundary:
                        b = field_bytes(r, c)
                    else:
                        e, lr = owner[r], local[r]
                        o, ln = int(f_off[e, c, lr]), int(f_len[e, c, lr])
                        b = css[e, o:o + ln]
                    datas.append(b)
                    lens[r] = len(b)
                offsets = np.zeros(n_total + 1, np.int32)
                np.cumsum(lens, out=offsets[1:])
                data = (np.concatenate(datas) if datas
                        else np.zeros(0, np.uint8))
                out[col.name] = dict(
                    offsets=offsets, data=data,
                    validity=np.packbits(lens > 0, bitorder="little"))
            else:
                parsed = shards.values[col.name]
                vals = np.asarray(parsed.value).reshape(d_cnt, m)
                valid = np.asarray(parsed.valid).reshape(d_cnt, m)
                v = vals[owner, local].copy()
                ok = valid[owner, local].copy()
                for r in boundary:
                    v[r], ok[r] = reparse_field(r, c, col.dtype)
                out[col.name] = dict(
                    values=v, validity=np.packbits(ok, bitorder="little"))
        return out
