"""Pluggable parse-stage backends (DESIGN.md §2; paper §3.1–§3.3).

Every driver — ``Parser``, ``DistributedParser``, ``StreamingParser`` — runs
the *same* stage functions from :mod:`repro.core.stages`; what varies is who
implements the byte-level hot loops.  A :class:`ParseBackend` bundles the
swappable stage implementations:

  * ``chunk_vectors``     — §3.1 first pass: per-chunk state-transition
    vectors (the |S|-simultaneous-DFA sweep over every byte).
  * ``replay_summaries``  — §3.1 second pass fused with the §3.2 per-chunk
    offset summaries: class codes + end states + (rec_count, col_tag,
    col_off) triples in one sweep.
  * ``partition``         — §3.3 stable partition of the tagged symbol
    stream by column tag.  Receives the *resolved* ``partition_impl``
    (``stages.plan_materialize`` maps ``"auto"`` to the backend's
    ``default_partition_impl``; ``partition_impls`` lists what the backend
    accepts).  ``"sort"`` never reaches it: both backends list it and
    ``stages.materialize`` runs it directly (``core.partition``).
  * ``parse_field``       — §3.3 type conversion, one entry per schema dtype
    (``int32`` / ``float32`` / ``date`` / ``str``), each mapping
    ``(css, offset, length)`` to a :class:`typeconv.Parsed`.
    ``stages.materialize`` dispatches *every* selected column through this
    table, so a backend that kernelises a dtype needs no driver changes at
    all.
  * ``prepend_carry`` / ``extract_carry`` — the §4.4 per-stream device-carry
    state machine of the streaming engine (``core/streaming.py``): splice
    the carried tail record in front of the fresh partition bytes, and cut
    the new tail after ``last_record_end``.  Both default to the shared jnp
    implementations below (pure ``where``/``roll`` masks — cheap next to
    the parse); they are backend hooks so a whole-pipeline-fusion backend
    can fold the splice into its first kernel's DMA and the cut into its
    last, without the engine changing.
  * ``execute``            — OPTIONAL whole-pipeline override: run the
    entire §3.1→§4.4 per-partition step (replay → tag → partition → field
    index → convert → validation inputs) as the backend sees fit, bypassing
    the staged composition in ``stages.execute_plan``.  Resolved into
    ``ParsePlan.execute_path`` by ``stages.plan_parse`` when the config
    asks for it (``ParserConfig.fuse_pipeline=True``) and gated at trace
    time behind the static ``fused_max_bytes`` byte cap (the megakernel
    holds the whole partition's working set in VMEM on real hardware);
    above the cap ``execute_plan`` silently runs the staged tier — same
    statically-bounded fallback design as the windowed numparse kernels.
    Backends without a fused executor leave it ``None``.

Backends:

  * ``reference`` — the pure-jnp path (``core.transition`` /
    ``core.offsets`` / ``core.partition`` / ``core.typeconv``); always
    available, the oracle.
  * ``pallas``    — the Pallas TPU kernels (``kernels.dfa_scan`` /
    ``kernels.partition`` / ``kernels.numparse``).  The fused replay kernel
    makes the separate ``chunk_summaries`` jnp pass disappear; the
    partition defaults on a TPU to one stable sort by column tag that
    carries the payloads (``partition_impl="auto"`` → ``"sort"``; off the
    chip the kernels run interpreted and "auto" resolves to the jit-fused
    jnp radix pass; the single-pass radix kernel stays selectable as
    ``"kernel"``); and int32/float32/date columns convert
    inside *fused gather+convert* ``numparse`` kernels that index the CSS
    in-kernel — no XLA ``take``/gather between the field index and
    conversion.  The fused kernels are *windowed* by default: each row
    block DMAs only its contiguous CSS window into VMEM (offsets within a
    column are sorted), so per-parse input is not capped by VMEM capacity;
    ``cfg.window_rows`` / ``cfg.max_window_bytes`` size the windows, and
    ``cfg.fuse_typeconv=False`` restores the unfused gather+kernel path
    for comparison (``str`` stays the shared no-op — strings live in the
    CSS and need no arithmetic).  Whether the kernels run compiled or in
    the Pallas interpreter is not a knob: :func:`interpret_kernels` derives
    it from the platform (interpreted exactly when it is not a TPU), and
    ``stages.plan_parse`` records the answer on ``ParsePlan.interpret``.

Stage functions receive the ``ParserConfig`` duck-typed (``cfg.dfa``,
``cfg.block_chunks``, ``cfg.int_width``) so kernel knobs
travel with the config instead of threading through every call site, and so
this module never imports :mod:`repro.core.parser` (no cycle).

The registry is open: future PRs add a backend (e.g. a Mosaic-GPU or a
partially-fused one) with :func:`register_backend` and every driver picks it
up through ``ParserConfig.backend``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import offsets as offsets_mod
from repro.core import partition as partition_mod
from repro.core import transition as tr
from repro.core import typeconv as typeconv_mod
from repro.core.dfa import PAD_BYTE

#: Default chunk-block size for the Pallas grid (mirrors
#: ``kernels.dfa_scan.dfa_scan.DEFAULT_BLOCK_CHUNKS`` without importing the
#: kernel package at module load).
DEFAULT_BLOCK_CHUNKS = 256


def interpret_kernels() -> bool:
    """The platform probe: Pallas kernels run in the interpreter exactly
    when JAX's default platform is not a TPU (Mosaic compiles them only
    there).  Every pallas stage reads this one function, so the plan and
    the traced kernels always agree."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# shared §4.4 stream-state hooks (device carry splice / cut) — the defaults
# every backend inherits; a fusing backend overrides them to fold the splice
# into its first kernel's DMA and the cut into its last.
# ---------------------------------------------------------------------------

def prepend_carry_jnp(carry_buf: jax.Array, carry_len: jax.Array,
                      fresh: jax.Array, fresh_len: jax.Array,
                      flush: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Splice ``carry_buf[:carry_len]`` in front of ``fresh[:fresh_len]``.

    All buffers are fixed-capacity ``(capacity,) uint8`` with PAD tails, so
    the splice is two masked ``where``s over a ``roll`` — no dynamic shapes,
    no host round-trip.  Under ``flush`` (the stream's final partition) an
    unterminated payload gets the record delimiter appended, judged on the
    last non-PAD byte (a PAD-only tail carries no record; paper §4.4 flush).

    Returns ``(buf, total, overflow)``: the assembled partition, its byte
    length (carry + fresh), and whether it no longer fits the capacity
    (including the flush delimiter's slot) — the condition the host raises
    "record longer than capacity" on, one partition behind.  Under overflow
    the buffer contents are garbage (the roll wraps); callers must raise
    before using them.
    """
    capacity = carry_buf.shape[0]
    delim = cfg.record_delim_byte
    pos = jnp.arange(capacity, dtype=jnp.int32)
    total = carry_len + fresh_len
    rolled = jnp.roll(fresh, carry_len)  # fresh payload now starts at carry_len
    buf = jnp.where(pos < carry_len, carry_buf,
                    jnp.where(pos < total, rolled, jnp.uint8(PAD_BYTE)))
    # Flush: append a record delimiter so the tail record completes.  Whether
    # one is needed is judged on the last *payload* byte (PAD bytes in the
    # source are inert — a PAD-only tail carries no record; carry_buf beyond
    # carry_len is PAD by the extract invariant below), but it is written at
    # ``total`` — after any trailing source PADs — exactly where the host
    # oracle writes it, so the two engines stay bit-identical.
    payload = jnp.max(jnp.where(buf != PAD_BYTE, pos + 1, 0))
    last_byte = buf[jnp.maximum(payload - 1, 0)]
    need_delim = flush & (payload > 0) & (last_byte != delim)
    overflow = (total > capacity) | (need_delim & (total >= capacity))
    buf = jnp.where(need_delim & (pos == total), jnp.uint8(delim), buf)
    return buf, total, overflow


def extract_carry_jnp(buf: jax.Array, total: jax.Array,
                      last_record_end: jax.Array, flush: jax.Array,
                      cfg) -> Tuple[jax.Array, jax.Array]:
    """Cut the carried tail ``buf[last_record_end+1 : total]`` to the front
    of a fresh fixed-capacity buffer.

    ``last_record_end == -1`` (no complete record) carries the whole
    payload; under ``flush`` the leftover is stale — either inert PADs or a
    record the appended delimiter could not close (malformed input;
    ``validation`` flags it) — and is dropped so the stream ends consumed.

    Returns ``(new_carry_buf, new_carry_len)`` with everything beyond
    ``new_carry_len`` PAD (the invariant ``prepend_carry`` relies on).
    """
    capacity = buf.shape[0]
    pos = jnp.arange(capacity, dtype=jnp.int32)
    cut = last_record_end + 1
    new_len = jnp.maximum(total - cut, 0)
    new_len = jnp.where(flush, 0, new_len)
    new_buf = jnp.where(pos < new_len, jnp.roll(buf, -cut), jnp.uint8(PAD_BYTE))
    return new_buf, new_len.astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class ParseBackend:
    """Bundle of swappable stage implementations (see module docstring).

    Signatures (all traced under the driver's jit):
      chunk_vectors(chunks (C,K) u8, cfg) -> (C,S) i32
      replay_summaries(chunks (C,K) u8, start (C,) i32, cfg)
          -> (classes (C,K) u8, end_states (C,) i32, saw_invalid (C,) bool,
              offsets.ChunkSummary)
      partition(col_tag (N,) i32, n_cols, impl: str, cfg)
          -> partition.Partitioned      for impl in ``partition_impls``
                                        other than ``"sort"``
      parse_field[dtype](css (N,) u8, offset (R,) i32, length (R,) i32, cfg)
          -> typeconv.Parsed     for dtype in int32 | float32 | date | str

    ``partition_impls`` / ``default_partition_impl`` are static metadata the
    planning layer uses to resolve ``ParserConfig.partition_impl="auto"``
    and to fail fast on impls the backend does not implement;
    ``typeconv_path`` names the conversion strategy the config resolves to
    (``reference`` / ``unfused`` / ``fused-windowed``)
    so plans and benchmark reports can label it without re-deriving the
    backend's dispatch logic.
    """

    name: str
    chunk_vectors: Callable
    replay_summaries: Callable
    partition: Callable
    parse_field: Dict[str, Callable]
    partition_impls: Tuple[str, ...]
    default_partition_impl: Callable  # (cfg) -> impl name ("auto" resolution)
    typeconv_path: Callable = lambda cfg: "reference"  # (cfg) -> path label
    # §4.4 per-stream device-carry hooks (streaming engine); see module
    # docstring.  Signatures:
    #   prepend_carry(carry_buf (B,) u8, carry_len () i32, fresh (B,) u8,
    #                 fresh_len () i32, flush () bool, cfg)
    #       -> (buf (B,) u8, total () i32, overflow () bool)
    #   extract_carry(buf (B,) u8, total () i32, last_record_end () i32,
    #                 flush () bool, cfg) -> (carry_buf (B,) u8, carry_len () i32)
    prepend_carry: Callable = prepend_carry_jnp
    extract_carry: Callable = extract_carry_jnp
    # Whole-pipeline fused executor (see module docstring).  Signature:
    #   execute(raw_chunks (C,K) u8, plan: stages.ParsePlan, cfg,
    #           initial_state () i32,
    #           stitch: Optional[stages.ParseStitch] = None)
    #       -> stages.ParseResult
    # ``stitch`` carries the distributed driver's cross-shard hooks (prefix
    # composition, offset/record-base seeding, global validation reductions)
    # so the fused path runs per-shard under shard_map — see
    # ``stages.ParseStitch``.  None = backend has no fused path; plans
    # resolve to "staged".
    execute: Optional[Callable] = None
    # Static byte cap for the fused path: partitions larger than this run
    # the staged tier instead (checked at trace time in execute_plan — the
    # megakernel's whole working set must fit VMEM on real hardware).
    fused_max_bytes: int = 4 << 20
    # Backend-specific contribution to ``stages.plan_key`` (the serving
    # registry's executable fingerprint): a hashable tuple of every config
    # knob this backend's traced code *reads* beyond what the ParsePlan
    # already captures.  Two configs whose plan keys are equal must trace
    # to bit-identical executables — list knobs conservatively.
    config_key: Callable = lambda cfg: ()
    # Whether this backend's kernels run in the Pallas interpreter; recorded
    # on ``ParsePlan.interpret`` at plan time.
    interpret: Callable[[], bool] = lambda: False
    # Plan-time refusal of knob values this backend cannot build on the
    # current platform: ``check_config(cfg)`` raises ValueError.
    check_config: Callable = lambda cfg: None


BACKENDS: Dict[str, ParseBackend] = {}


def register_backend(backend: ParseBackend) -> ParseBackend:
    BACKENDS[backend.name] = backend
    return backend


def get_backend(name: str) -> ParseBackend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown parser backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(BACKENDS))


def pad_to_block(arr: jax.Array, block: int, fill) -> Tuple[jax.Array, int]:
    """Pad ``arr``'s leading axis up to a multiple of ``block``.

    Returns ``(padded, original_length)``; padding rows are ``fill`` and are
    inert by construction (PAD bytes / dummy states / zero-length fields), so
    callers slice results back to ``original_length``.
    """
    n = arr.shape[0]
    pad = (-n) % block
    if pad == 0:
        return arr, n
    padding = jnp.full((pad,) + arr.shape[1:], fill, arr.dtype)
    return jnp.concatenate([arr, padding], axis=0), n


def _saw_invalid(end_states: jax.Array, dfa) -> jax.Array:
    """The invalid sink is absorbing, so "ever hit" == "ended there"."""
    if dfa.invalid_state is None:
        return jnp.zeros(end_states.shape, bool)
    return end_states == dfa.invalid_state


# ---------------------------------------------------------------------------
# reference backend — pure jnp (core.transition / core.offsets / core.typeconv)
# ---------------------------------------------------------------------------

def _ref_chunk_vectors(chunks: jax.Array, cfg) -> jax.Array:
    groups = tr.byte_groups(chunks, cfg.dfa)
    return tr.chunk_transition_vectors(groups, cfg.dfa)


def _ref_replay_summaries(chunks: jax.Array, start: jax.Array, cfg):
    groups = tr.byte_groups(chunks, cfg.dfa)
    classes, end_states, saw_invalid = tr.replay(groups, start, cfg.dfa)
    summaries = offsets_mod.chunk_summaries(classes)
    return classes, end_states, saw_invalid, summaries


def _jnp_partition(col_tag, n_cols, impl, cfg) -> partition_mod.Partitioned:
    return partition_mod.PARTITION_IMPLS[impl](col_tag, n_cols)


def _ref_parse_int(css, offset, length, cfg) -> typeconv_mod.Parsed:
    return typeconv_mod.parse_int(css, offset, length, width=cfg.int_width)


def _ref_parse_float(css, offset, length, cfg) -> typeconv_mod.Parsed:
    return typeconv_mod.parse_float(css, offset, length, width=cfg.float_width)


def _ref_parse_date(css, offset, length, cfg) -> typeconv_mod.Parsed:
    return typeconv_mod.parse_date(css, offset, length)


def _shared_parse_str(css, offset, length, cfg) -> typeconv_mod.Parsed:
    # Strings stay in the CSS; both backends share the bookkeeping no-op.
    return typeconv_mod.parse_string_noop(css, offset, length)


REFERENCE = register_backend(ParseBackend(
    name="reference",
    chunk_vectors=_ref_chunk_vectors,
    replay_summaries=_ref_replay_summaries,
    partition=_jnp_partition,
    parse_field={
        "int32": _ref_parse_int,
        "float32": _ref_parse_float,
        "date": _ref_parse_date,
        "str": _shared_parse_str,
    },
    partition_impls=tuple(sorted(partition_mod.PARTITION_IMPLS)) + ("sort",),
    default_partition_impl=lambda cfg: "scatter",
))


# ---------------------------------------------------------------------------
# pallas backend — kernels.dfa_scan + kernels.partition + kernels.numparse
# ---------------------------------------------------------------------------

def _block_chunks(cfg, c: int) -> int:
    return min(getattr(cfg, "block_chunks", DEFAULT_BLOCK_CHUNKS) or
               DEFAULT_BLOCK_CHUNKS, c)


def _pl_chunk_vectors(chunks: jax.Array, cfg) -> jax.Array:
    from repro.kernels.dfa_scan import dfa_scan

    bc = _block_chunks(cfg, chunks.shape[0])
    padded, n = pad_to_block(chunks, bc, PAD_BYTE)
    vecs = dfa_scan.chunk_vectors(
        padded, cfg.dfa, block_chunks=bc, interpret=interpret_kernels()
    )
    return vecs[:n]


def _pl_replay_summaries(chunks: jax.Array, start: jax.Array, cfg):
    from repro.kernels.dfa_scan import dfa_scan

    bc = _block_chunks(cfg, chunks.shape[0])
    padded, n = pad_to_block(chunks, bc, PAD_BYTE)
    start_p, _ = pad_to_block(
        start.astype(jnp.int32), bc, cfg.dfa.start_state
    )
    classes, end_states, summ = dfa_scan.replay_fused(
        padded, start_p, cfg.dfa, block_chunks=bc,
        interpret=interpret_kernels(),
    )
    classes, end_states, summ = classes[:n], end_states[:n], summ[:n]
    summaries = offsets_mod.ChunkSummary(
        rec_count=summ[:, 0], col_tag=summ[:, 1], col_off=summ[:, 2]
    )
    return classes, end_states, _saw_invalid(end_states, cfg.dfa), summaries


def _pl_partition(col_tag, n_cols, impl, cfg) -> partition_mod.Partitioned:
    if impl != "kernel":  # explicit jnp impls stay available for comparison
        return partition_mod.PARTITION_IMPLS[impl](col_tag, n_cols)
    from repro.kernels.partition import ops as partition_ops

    kw = {}
    bt = getattr(cfg, "partition_block_tags", 0)
    if bt:
        kw["block_tags"] = bt
    return partition_ops.partition_tags(
        col_tag, n_cols, interpret=interpret_kernels(), **kw
    )


def _fuse(cfg) -> bool:
    return getattr(cfg, "fuse_typeconv", True)


def _window_kw(cfg) -> Dict[str, int]:
    """Windowed-DMA knobs for the fused numparse path (see ParserConfig)."""
    return dict(window_rows=getattr(cfg, "window_rows", 0),
                window_bytes=getattr(cfg, "max_window_bytes", 0))


def _pl_parse_int(css, offset, length, cfg) -> typeconv_mod.Parsed:
    from repro.kernels.numparse import ops as numparse_ops

    if not _fuse(cfg):
        return numparse_ops.parse_int_column(
            css, offset, length, width=cfg.int_width,
            interpret=interpret_kernels())
    return numparse_ops.parse_int_column_fused(
        css, offset, length, width=cfg.int_width,
        interpret=interpret_kernels(), **_window_kw(cfg))


def _pl_parse_float(css, offset, length, cfg) -> typeconv_mod.Parsed:
    from repro.kernels.numparse import ops as numparse_ops

    if not _fuse(cfg):
        return numparse_ops.parse_float_column(
            css, offset, length, width=cfg.float_width,
            interpret=interpret_kernels())
    return numparse_ops.parse_float_column_fused(
        css, offset, length, width=cfg.float_width,
        interpret=interpret_kernels(), **_window_kw(cfg))


def _pl_parse_date(css, offset, length, cfg) -> typeconv_mod.Parsed:
    from repro.kernels.numparse import ops as numparse_ops

    if not _fuse(cfg):
        return numparse_ops.parse_date_column(
            css, offset, length, interpret=interpret_kernels())
    return numparse_ops.parse_date_column_fused(
        css, offset, length, interpret=interpret_kernels(), **_window_kw(cfg))


def _pl_execute(raw_chunks, plan, cfg, initial_state, stitch=None):
    """Whole-pipeline fused executor: §3.1 scan + ONE megakernel per
    partition (``kernels/fused_pipeline``), then O(max_records)/scalar
    assembly — no ``(N,)``/``(R,)`` intermediate ever leaves a kernel.

    Bit-identical to the staged composition in ``stages.execute_plan`` by
    construction: the megakernel replicates each staged stage op-for-op
    (same replay select chains, same id scans, same scatter2 radix pass,
    same segment reductions, same shared numparse cores) and this wrapper
    replicates the §4.3 validation arithmetic on the kernel's
    ``fields_per_rec``/scalar outputs exactly as ``validation.validate``
    computes it from the flat class stream.

    Under a ``stitch`` (distributed execution; ``stages.ParseStitch``) the
    composite scan is seeded with the cross-device prefix, the megakernel's
    in-kernel tagging is seeded with the shard's column offset, and
    validation goes through the stitch's global reductions.  The column
    seed comes from the §3.2 summaries, which the megakernel only produces
    *internally* — so the stitched fused path runs the staged summary
    kernel (``replay_summaries``) first for the stitch and the megakernel
    re-replays in VMEM.  That duplicate replay is the price of keeping the
    megakernel single-launch; the shard driver's own summary pass CSEs
    against it, so it is one extra replay total, still O(N/D) per device
    with O(D·|S|) collectives.
    """
    from repro.core import stages as stages_mod
    from repro.core import validation as validation_mod
    from repro.kernels.fused_pipeline import ops as fused_ops

    mat = plan.materialize
    # §3.1 upstream: chunk transition vectors (pallas kernel) + the O(C·S)
    # composite scan — the only stages outside the megakernel.
    vecs = _pl_chunk_vectors(raw_chunks, cfg)
    scanned = tr.exclusive_scan_vectors(vecs, use_matmul=cfg.use_matmul_scan)
    if stitch is not None:
        prefix = stitch.prefix_fn(vecs)
        scanned = tr.compose(jnp.broadcast_to(prefix, scanned.shape), scanned)
    start = tr.start_states(scanned, cfg.dfa, initial_state=initial_state)

    col_seed = None
    if stitch is not None:
        _, _, _, summaries = _pl_replay_summaries(raw_chunks, start, cfg)
        _, _, col_seed, n_total = stitch.offsets_fn(summaries)

    out = fused_ops.fused_parse(
        raw_chunks, start, cfg.dfa,
        tagging=mat.tagging, n_cols=mat.n_cols, max_records=mat.max_records,
        selected=mat.selected, convert=mat.convert,
        int_width=cfg.int_width, float_width=cfg.float_width,
        col_seed=col_seed, interpret=interpret_kernels(),
    )

    if stitch is not None:
        # §4.3 goes through the stitch's global reductions; the kernel's
        # fields_per_rec is already seed-corrected at the head record.
        val = stitch.validation_fn(
            out.fields_per_rec, out.n_records, out.end_state,
            out.saw_invalid, n_total,
        )
        return stages_mod.ParseResult(
            css=out.css,
            col_start=out.col_start,
            col_count=out.col_count,
            field_offset=out.offset,
            field_length=out.length,
            field_present=out.present,
            values=out.values,
            validation=val,
            end_state=out.end_state.astype(jnp.int32),
            last_record_end=out.last_record_end.astype(jnp.int32),
        )

    # §4.3 validation from the kernel's per-record field counts + scalars —
    # the same arithmetic validation.validate runs on the flat class stream.
    m = mat.max_records
    accept = jnp.asarray(cfg.dfa.accept)
    end_ok = accept[out.end_state.astype(jnp.int32)]
    no_inv = ~out.saw_invalid
    rec_live = jnp.arange(m) < out.n_records
    big = jnp.int32(2**31 - 1)
    minc = jnp.min(jnp.where(rec_live, out.fields_per_rec, big))
    maxc = jnp.max(jnp.where(rec_live, out.fields_per_rec, 0))
    if plan.expected_columns is None:
        record_ok = rec_live
    else:
        record_ok = rec_live & (out.fields_per_rec == plan.expected_columns)
    ok = end_ok & no_inv
    if plan.expected_columns is not None:
        ok &= jnp.all(record_ok | ~rec_live)
    val = validation_mod.Validation(
        ok, end_ok, no_inv, out.n_records, minc, maxc, record_ok
    )

    return stages_mod.ParseResult(
        css=out.css,
        col_start=out.col_start,
        col_count=out.col_count,
        field_offset=out.offset,
        field_length=out.length,
        field_present=out.present,
        values=out.values,
        validation=val,
        end_state=out.end_state.astype(jnp.int32),
        last_record_end=out.last_record_end.astype(jnp.int32),
    )


def _pl_config_key(cfg) -> Tuple:
    """Pallas kernel knobs that shape traced code beyond the ParsePlan."""
    return (
        "interpret", interpret_kernels(),
        "block_chunks", getattr(cfg, "block_chunks", None),
        "fuse_typeconv", _fuse(cfg),
        "window_rows", getattr(cfg, "window_rows", 0),
        "max_window_bytes", getattr(cfg, "max_window_bytes", 0),
        # None (unset) and False trace identically (staged)
        "fuse_pipeline", bool(getattr(cfg, "fuse_pipeline", False) or False),
        "partition_block_tags", getattr(cfg, "partition_block_tags", 0),
        "fused_max_bytes", getattr(cfg, "fused_max_bytes", 0),
    )


def _pl_check_config(cfg) -> None:
    """On a TPU, refuse the knob values whose kernels Mosaic cannot build."""
    if interpret_kernels():
        return
    for knob in ("block_chunks", "window_rows", "partition_block_tags"):
        value = getattr(cfg, knob, 0)
        if value > 0 and value % 128:
            raise ValueError(
                f"{knob}={value}: on a TPU kernel blocks tile the lanes in "
                f"multiples of 128")


def _pl_typeconv_path(cfg) -> str:
    return "fused-windowed" if _fuse(cfg) else "unfused"


PALLAS = register_backend(ParseBackend(
    name="pallas",
    chunk_vectors=_pl_chunk_vectors,
    replay_summaries=_pl_replay_summaries,
    partition=_pl_partition,
    parse_field={
        "int32": _pl_parse_int,
        "float32": _pl_parse_float,
        "date": _pl_parse_date,
        "str": _shared_parse_str,
    },
    partition_impls=(tuple(sorted(partition_mod.PARTITION_IMPLS))
                     + ("kernel", "sort")),
    # "auto" resolution: on a TPU, "sort" — one stable sort by column tag
    # that carries the symbols and tags along, in place of a permutation
    # (the radix kernel's, inverted by a scatter) and a gather per payload
    # through it, which cost many times the sort there.  Off the chip the
    # kernels run op-by-op in the Pallas interpreter and gathers are cheap:
    # XLA's jit-fused radix pass (scatter2) is the fastest there.  "kernel"
    # and "sort" stay selectable everywhere and are pinned bit-identical
    # by the parity/fuzz/golden suites.
    default_partition_impl=lambda cfg: (
        "scatter2" if interpret_kernels() else "sort"),
    typeconv_path=_pl_typeconv_path,
    config_key=_pl_config_key,
    interpret=lambda: interpret_kernels(),
    check_config=_pl_check_config,
    # whole-pipeline fusion (ParserConfig.fuse_pipeline=True): one
    # megakernel per partition, gated behind fused_max_bytes (the dataclass
    # default) with the staged composition above as the fallback tier
    execute=_pl_execute,
))
