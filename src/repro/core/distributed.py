"""Multi-device ParPaRaw: the paper's scans stretched across a TPU mesh.

The input is cut into equal byte ranges, one per device, with no
sequential pass to find where records start (the paper's central claim,
lifted from threads to devices).  Inside ``shard_map`` every device:

    1. folds its chunks' DFA transition summaries, ``all_gather``s one
       ``(|S|,)`` vector per device and composes the exclusive prefix: its
       bytes now parse in their true context (§3.1), so it knows where its
       last record ends;
    2. passes its unterminated tail (the bytes after that record end) to
       the next device: one ``ppermute`` of a fixed ``max_carry_bytes``
       buffer and its length, repeated ``D − 1`` times so that a tail
       crosses a device that holds no record end;
    3. splices the tail it received in front of its own bytes with the
       §4.4 carry splice (``backend.prepend_carry``), drops its own tail,
       and runs the *complete* ``stages.execute_plan`` composition on the
       result from the record start state — context → ids → materialize →
       typeconv → §4.3 validation, pallas kernels and the
       ``fuse_pipeline`` megakernel path included.

So every record is whole on exactly one device: the one holding its
terminating record delimiter.  Record ids are shard-local in the field
index; ``rec_base`` (an ``all_gather`` of one record count per device)
makes them global.  §4.3 validation reduces with O(1) ``pmin``/``pmax``/
``psum`` collectives (:func:`mesh_stitch`); ``end_state_ok`` is
contributed by the last shard alone.  No collective ever moves
input-sized data: per-device work is N/D bytes plus the tail, the
collectives are constant.

A tail longer than ``max_carry_bytes`` cannot be passed on: the call's
``overflow`` flag is set (its columns are then not whole), as
``StreamOverflow`` is for a stream; :meth:`DistributedParser.assemble`
refuses such a parse.  The host spans ``mesh.prepare``, ``mesh.put`` and
``mesh.dispatch`` (``repro.core.spans``) time the driver's host work.

Each device emits its own columnar shard (per-host Arrow batches — what a
real ingest pipeline wants); :meth:`DistributedParser.assemble`
concatenates them, in global record order, into a
single-parser-identical Arrow-layout table on the host.
"""
from __future__ import annotations

import itertools
from typing import Dict, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core import backends as backends_mod
from repro.core import spans
from repro.core import stages as stages_mod
from repro.core import transition as tr
from repro.core import typeconv as typeconv_mod
from repro.core import validation as validation_mod
from repro.core.dfa import PAD_BYTE, RECORD_DELIM
from repro.core.parser import Parser, ParserConfig

#: The longest tail a shard passes on when ``max_carry_bytes`` is not
#: given.
DEFAULT_MAX_CARRY_BYTES = 64 << 10

#: Ids of driver calls, process-wide: every host span of a call carries
#: ``call=<id>``.
_CALL_IDS = itertools.count(1)


class ShardedParse(NamedTuple):
    """Per-device columnar shard with globally consistent record ids.

    Leading axes are sharded over the mesh, so the assembled pytree holds
    per-shard arrays back to back: ``css`` is ``(D·N_shard,)``,
    ``col_start`` is ``(D·(n_cols+1),)``, the field index is
    ``(D·n_cols, max_records)``, each ``values`` leaf is
    ``(D·max_records,)`` — reshape with a leading ``D`` to address shard
    ``d``.  Shard ``d`` holds records ``rec_base[d]`` up to
    ``rec_base[d+1]`` (or ``n_records``), each whole, at shard-local ids
    from 0.  ``validation`` carries the *global* §4.3 scalars (replicated)
    with per-shard ``record_ok`` on shard-local record ids.
    """

    classes: jax.Array       # (C_local·K,) uint8 per device: the shard's
                             # own bytes' classes in their stitched context
    css: jax.Array           # (N_shard,) uint8 partitioned symbols
    col_start: jax.Array     # (n_cols+1,) int32 per shard
    col_count: jax.Array     # (n_cols+1,) int32
    field_offset: jax.Array  # (n_cols, max_records) int32, local CSS positions
    field_length: jax.Array  # (n_cols, max_records) int32
    values: Dict[str, typeconv_mod.Parsed]  # per-shard typed columns
    validation: validation_mod.Validation   # global scalars + local record_ok
    rec_base: jax.Array      # (1,) int32 per shard — its first global record id
    n_records: jax.Array     # () int32 — global record count (replicated)
    halo_bytes: jax.Array    # (1,) int32 per shard — tail bytes it passed on
    overflow: jax.Array      # () bool — a tail did not fit max_carry_bytes
                             # (replicated); the shards' columns are not whole


def _device_prefix_vec(local_comp: jax.Array, axis) -> jax.Array:
    """Exclusive composite of all preceding devices' transition summaries."""
    all_comps = jax.lax.all_gather(local_comp, axis)  # (D, S)
    inc = jax.lax.associative_scan(tr.compose, all_comps, axis=0)
    me = jax.lax.axis_index(axis)
    ident = tr.identity_vector(local_comp.shape[-1])
    prev = inc[jnp.maximum(me - 1, 0)]
    return jnp.where(me == 0, ident, prev)


def _and_reduce(x: jax.Array, axis) -> jax.Array:
    """AND across the mesh axis — a () int32 psum, never input-sized."""
    return jax.lax.psum(jnp.logical_not(x).astype(jnp.int32), axis) == 0


def mesh_stitch(cfg, plan: stages_mod.ParsePlan, axis, n_devices: int,
                n_total: jax.Array) -> stages_mod.ParseStitch:
    """The cross-device §4.3 reduction for :func:`stages.execute_plan`
    under shard_map.

    Every shard holds whole records, so its per-record column counts are
    exact and every global quantity is an O(1) reduction — the same
    arithmetic ``validation.validate`` runs on the flat class stream,
    decomposed along record ownership.  ``n_devices`` is the static mesh
    extent along ``axis`` (a name or tuple of names, linearized);
    ``n_total`` is the global record count.
    """
    expected = plan.expected_columns
    accept = np.asarray(cfg.dfa.accept)

    def validation_fn(fields_per_rec, n_local, end_state, saw_invalid):
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

    return stages_mod.ParseStitch(validation_fn)


def _halo_exchange(flat: jax.Array, flat_classes: jax.Array, cap: int, cfg,
                   backend, axis, n_devices: int):
    """Pass every shard's unterminated tail to the shard that holds its end
    and splice it there (the §4.4 carry, across devices in one call).

    ``flat`` is the shard's own bytes, ``flat_classes`` their classes in
    the stitched context.  A shard's tail is its bytes after its last
    record delimiter (trailing PAD carries nothing); a shard with no record
    delimiter passes on what it received followed by all its bytes.  The
    exchange runs ``D − 1`` rounds of one ``ppermute`` of ``cap`` bytes
    plus the length, so a tail may cross devices that end no record.

    Returns ``(buf, sent, overflow)``: the shard's bytes to parse (the tail
    it received, then its own bytes up to its last record end; the last
    shard keeps all of its bytes), ``cap + len(flat)`` long; the tail
    length it passed on; and whether that tail was longer than ``cap``.
    """
    n = flat.shape[0]
    me = jax.lax.axis_index(axis)
    is_last = me == n_devices - 1
    pos = jnp.arange(n, dtype=jnp.int32)
    last_end = stages_mod.locate_carry(flat_classes)
    payload = jnp.max(jnp.where(flat != PAD_BYTE, pos + 1, 0)).astype(jnp.int32)
    has_end = last_end >= 0
    own_len = jnp.maximum(payload - (last_end + 1), 0)

    # The shard's first and last ``cap`` bytes: every tail that fits lies
    # in them.
    if n >= cap:
        front, back, base = flat[:cap], flat[n - cap:], n - cap
    else:
        front = back = jnp.concatenate(
            [flat, jnp.full((cap - n,), PAD_BYTE, jnp.uint8)])
        base = 0
    hpos = jnp.arange(cap, dtype=jnp.int32)
    tail = jnp.where(hpos < own_len, jnp.roll(back, base - (last_end + 1)),
                     jnp.uint8(PAD_BYTE))

    def passed_on(recv_buf, recv_len):
        fwd, _, _ = backend.prepend_carry(recv_buf, recv_len, front, payload,
                                          False, cfg)
        return (jnp.where(has_end, tail, fwd),
                jnp.where(has_end, own_len, recv_len + payload))

    recv_buf = jnp.full((cap,), PAD_BYTE, jnp.uint8)
    recv_len = jnp.int32(0)
    perm = [(d, d + 1) for d in range(n_devices - 1)]

    def one_round(_, recv):
        out_buf, out_len = passed_on(*recv)
        msg = jnp.concatenate(
            [out_buf, jax.lax.bitcast_convert_type(out_len, jnp.uint8)])
        got = jax.lax.ppermute(msg, axis, perm)   # shard 0 receives zeros
        return got[:cap], jax.lax.bitcast_convert_type(got[cap:], jnp.int32)

    recv_buf, recv_len = jax.lax.fori_loop(0, n_devices - 1, one_round,
                                           (recv_buf, recv_len))
    _, out_len = passed_on(recv_buf, recv_len)
    sent = jnp.where(is_last, 0, out_len)
    overflow = sent > cap

    keep = jnp.where(is_last, n, jnp.where(has_end, last_end + 1, 0))
    take = jnp.where(is_last | has_end, recv_len, 0)
    buf, _, _ = backend.prepend_carry(
        jnp.concatenate([recv_buf, jnp.full((n,), PAD_BYTE, jnp.uint8)]),
        take, jnp.concatenate([flat, jnp.full((cap,), PAD_BYTE, jnp.uint8)]),
        keep, False, cfg)
    return buf, sent.astype(jnp.int32), overflow


def _shard_parse(chunks: jax.Array, cfg: ParserConfig,
                 plan: stages_mod.ParsePlan, axis, n_devices: int,
                 max_carry_bytes: Optional[int]) -> ShardedParse:
    """Runs on every device under shard_map; ``chunks (C_local, K)``."""
    backend = backends_mod.get_backend(cfg.backend)
    k = cfg.chunk_size

    # §3.1 across the mesh: the shard's own bytes in their true context.
    with jax.named_scope("stage.contexts"):
        vecs = backend.chunk_vectors(chunks, cfg)
    with jax.named_scope("stage.stitch"):
        prefix = _device_prefix_vec(tr.fold_vectors(vecs), axis)
    with jax.named_scope("stage.contexts"):
        ctx = stages_mod.determine_contexts(chunks, cfg, backend, vecs=vecs,
                                            prefix=prefix)
        flat_classes = ctx.classes.reshape(-1)
        n_own = jnp.sum(flat_classes == RECORD_DELIM).astype(jnp.int32)
    # The shard owns the records whose delimiter it holds: global ids.
    with jax.named_scope("stage.stitch"):
        all_rec = jax.lax.all_gather(n_own, axis)
        me = jax.lax.axis_index(axis)
        rec_base = (jnp.cumsum(all_rec) - all_rec)[me]
        n_total = jnp.sum(all_rec)

    if n_devices > 1:
        cap = -(-(max_carry_bytes or DEFAULT_MAX_CARRY_BYTES) // k) * k
        with jax.named_scope("stage.halo"):
            buf, sent, over = _halo_exchange(
                chunks.reshape(-1), flat_classes, cap, cfg, backend, axis,
                n_devices)
            shard_chunks = buf.reshape(-1, k)
        with jax.named_scope("stage.stitch"):
            overflow = jax.lax.psum(over.astype(jnp.int32), axis) > 0
    else:
        shard_chunks, sent, overflow = chunks, jnp.int32(0), jnp.bool_(False)

    # The complete per-partition composition — staged or megakernel-fused,
    # exactly as the single-device Parser runs it — on whole records, with
    # the cross-device validation reduction plugged in.
    stitch = mesh_stitch(cfg, plan, axis, n_devices, n_total)
    res = stages_mod.execute_plan(shard_chunks, plan, cfg, backend,
                                  stitch=stitch)
    return ShardedParse(
        classes=flat_classes,
        css=res.css,
        col_start=res.col_start,
        col_count=res.col_count,
        field_offset=res.field_offset,
        field_length=res.field_length,
        values=res.values,
        validation=res.validation,
        rec_base=rec_base.reshape(1),  # rank-1 so shards concatenate
        n_records=res.validation.n_records,
        halo_bytes=sent.reshape(1),
        overflow=overflow,
    )


class DistributedParser:
    """shard_map-wrapped ParPaRaw over a device mesh.

    ``max_records`` in the config is *per shard* here.  The input byte
    buffer is sharded along its chunk axis over ``axis_names`` (all data
    axes flattened); outputs keep the same sharding, one columnar shard per
    device, each holding whole records (module docstring).

    ``max_carry_bytes`` is the longest tail a shard may pass on, that is,
    the longest stretch of a record before a cut (default
    ``DEFAULT_MAX_CARRY_BYTES``); a longer one sets
    ``ShardedParse.overflow``.

    ``convert=True`` (the default) runs the full plan per shard — CSS +
    field index + typed columns + global validation all materialize
    device-locally, through whichever backend/tagging/fusion path the
    config picks.  ``convert=False`` keeps the historical index-only
    export (shards ship the CSS + field index; hosts convert), which the
    dry-run roofline harness still uses.
    """

    def __init__(self, cfg: ParserConfig, mesh: Mesh,
                 axis_names: Sequence[str] = ("data",), convert: bool = True,
                 max_carry_bytes: Optional[int] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.n_devices = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        if max_carry_bytes is not None and max_carry_bytes < 1:
            raise ValueError(
                f"max_carry_bytes must be >= 1, got {max_carry_bytes}")
        self.max_carry_bytes = max_carry_bytes
        self._host = Parser(cfg)   # its ``prepare`` cuts the bytes into chunks
        #: Static ParsePlan resolved once — the same planning layer every
        #: driver adopts (staged or fused per cfg.fuse_pipeline).
        self.plan = stages_mod.plan_parse(
            cfg, backends_mod.get_backend(cfg.backend), convert=convert
        )
        axis = self.axis_names
        self.sharding = NamedSharding(mesh, P(axis, None))
        out_specs = ShardedParse(
            classes=P(axis),
            css=P(axis),
            col_start=P(axis),
            col_count=P(axis),
            field_offset=P(axis, None),
            field_length=P(axis, None),
            values={name: typeconv_mod.Parsed(P(axis), P(axis), P(axis))
                    for name, _, _ in self.plan.materialize.convert},
            validation=validation_mod.Validation(
                ok=P(), end_state_ok=P(), no_invalid=P(), n_records=P(),
                min_columns=P(), max_columns=P(), record_ok=P(axis),
            ),
            rec_base=P(axis),
            n_records=P(),
            halo_bytes=P(axis),
            overflow=P(),
        )

        plan, n_devices = self.plan, self.n_devices

        def wrapped(chunks):
            return _shard_parse(chunks, cfg, plan, axis, n_devices,
                                max_carry_bytes)

        self._fn = jax.jit(
            shard_map(
                wrapped, mesh=mesh, in_specs=(P(axis, None),),
                out_specs=out_specs, check_vma=False,
            )
        )

    def prepare(self, data: bytes, pad_to: Optional[int] = None) -> np.ndarray:
        """``Parser.prepare`` plus padding the chunk *count* to a mesh
        multiple — appended all-PAD chunks are inert (identity transitions,
        no symbols), exactly like the in-chunk PAD tail.  ``pad_to`` fixes
        the total bytes, so that inputs of different sizes share one
        compiled shape (it must be a multiple of ``chunk_size`` × devices).
        """
        k = self.cfg.chunk_size
        if pad_to is not None and pad_to % (k * self.n_devices):
            raise ValueError(
                f"pad_to={pad_to} is not a multiple of chunk_size × "
                f"devices ({k} × {self.n_devices})")
        chunks = self._host.prepare(data, pad_to=pad_to)
        n = chunks.shape[0]
        target = -(-n // self.n_devices) * self.n_devices
        if target != n:
            pad = np.full((target - n, k), PAD_BYTE, np.uint8)
            chunks = np.concatenate([chunks, pad], axis=0)
        return chunks

    def parse_chunks(self, chunks) -> ShardedParse:
        return self._fn(chunks)

    def load(self, data: bytes, pad_to: Optional[int] = None) -> ShardedParse:
        """One table from host bytes: ``prepare``, the sharded transfer (one
        equal byte range per device) and the dispatch of the parse, which
        returns before the devices finish.  Each step is a host span
        (``mesh.prepare``, ``mesh.put``, ``mesh.dispatch``) with its
        ``bytes`` and the call's ``call`` id."""
        call = next(_CALL_IDS)
        with spans.span("mesh.prepare", bytes=len(data), call=call):
            chunks = self.prepare(data, pad_to=pad_to)
        with spans.span("mesh.put", bytes=chunks.nbytes, call=call):
            chunks = jax.device_put(chunks, self.sharding)
        with spans.span("mesh.dispatch", bytes=chunks.nbytes, call=call):
            return self._fn(chunks)

    def lower(self, n_chunks: int, chunk_bytes: int):
        """ShapeDtypeStruct lowering hook — the dry-run harness and the
        collective-accounting tests/bench compile this without data."""
        spec = jax.ShapeDtypeStruct((n_chunks, chunk_bytes), jnp.uint8,
                                    sharding=self.sharding)
        return self._fn.lower(spec)

    # -- host assembly -----------------------------------------------------

    def assemble(self, shards: ShardedParse) -> Dict[str, dict]:
        """Concatenate the per-device shards, in global record order, into
        one Arrow-layout table, bit-identical to ``Parser.to_arrow`` on the
        unsharded input.  Every record is whole on the shard that owns it,
        so this is a gather per shard; a parse whose ``overflow`` is set
        is refused."""
        if bool(shards.overflow):
            raise ValueError(
                "a shard's tail is longer than max_carry_bytes: the shards' "
                "records are not whole; increase max_carry_bytes")
        cfg = self.cfg
        d_cnt = self.n_devices
        n_cols = len(cfg.schema.columns)
        m = cfg.max_records
        n_total = int(shards.n_records)
        rec_base = np.asarray(shards.rec_base).reshape(d_cnt).astype(np.int64)
        n_local = np.minimum(np.diff(np.append(rec_base, n_total)), m)
        css = np.asarray(shards.css).reshape(d_cnt, -1)
        f_off = np.asarray(shards.field_offset).reshape(d_cnt, n_cols, m)
        f_len = np.asarray(shards.field_length).reshape(d_cnt, n_cols, m)

        out: Dict[str, dict] = {}
        for c, col in enumerate(cfg.schema.columns):
            if not col.selected:
                continue
            if col.dtype == "str":
                lens = np.concatenate(
                    [f_len[d, c, :n_local[d]] for d in range(d_cnt)]
                ).astype(np.int32)
                data = np.concatenate(
                    [_gather_fields(css[d], f_off[d, c, :n_local[d]],
                                    f_len[d, c, :n_local[d]])
                     for d in range(d_cnt)])
                offsets = np.zeros(lens.size + 1, np.int32)
                np.cumsum(lens, out=offsets[1:])
                out[col.name] = dict(
                    offsets=offsets, data=data,
                    validity=np.packbits(lens > 0, bitorder="little"))
            else:
                parsed = shards.values[col.name]
                vals = np.asarray(parsed.value).reshape(d_cnt, m)
                valid = np.asarray(parsed.valid).reshape(d_cnt, m)
                v = np.concatenate([vals[d, :n_local[d]] for d in range(d_cnt)])
                ok = np.concatenate([valid[d, :n_local[d]] for d in range(d_cnt)])
                out[col.name] = dict(
                    values=v, validity=np.packbits(ok, bitorder="little"))
        return out


def _gather_fields(css: np.ndarray, off: np.ndarray, length: np.ndarray) -> np.ndarray:
    """The bytes ``css[off[i]:off[i] + length[i]]`` of every field, back to
    back."""
    length = length.astype(np.int64)
    starts = np.repeat(off.astype(np.int64) - (np.cumsum(length) - length), length)
    return css[np.arange(int(length.sum())) + starts]
