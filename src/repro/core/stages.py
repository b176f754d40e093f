"""Shared parse-pipeline stages (paper §3.1–§3.3) — the single composition
point every driver runs through.

``Parser`` (single device), ``DistributedParser`` (shard_map over a mesh)
and ``StreamSession``/``StreamingParser`` (partition-pipelined, device-
resident carry) all compose exactly these functions; the byte-level hot
loops inside them come from the
:class:`repro.core.backends.ParseBackend` selected by
``ParserConfig.backend``:

    determine_contexts  — §3.1 context determination + replay, fused with
                          the §3.2 per-chunk offset summaries
    identify_symbols    — §3.2 record/column ids from the chunk summaries
    materialize         — the §3.2/§3.3 back half as ONE backend-owned
                          stage: tagging → stable partition → field index
                          → per-dtype type conversion.  What to build is
                          described by a static :class:`MaterializePlan`
                          (``plan_materialize``); *how* each step runs is
                          the backend's call (``backend.partition``,
                          ``backend.parse_field``) — so fusing partition
                          and conversion into kernels is a backend change,
                          never a driver change.
    locate_carry        — §4.4 carry-over boundary for streaming

The whole per-partition pipeline is itself planned and executed the same
way: :func:`plan_parse` resolves a config into a static :class:`ParsePlan`
(the :class:`MaterializePlan` plus the §4.3 validation contract), and
:func:`execute_plan` runs context-determination → symbol-ids → materialize
→ validation → carry location as one traced function returning a
:class:`ParseResult`.  ``Parser.parse_chunks`` is one ``jax.jit`` of
``execute_plan``; the streaming engine (``core/streaming.py``) wraps the
same executor in a donated carry-prepend/carry-extract step and ``vmap``s
it over a stream axis — every driver executes the *same* plan, so a plan
change (new stage, new fusion) propagates to all of them at once.

Materialization is a backend responsibility, not driver glue: drivers pass
the plan through and receive a :class:`ColumnBatch` plus converted values.
On ``backend="pallas"`` on a TPU the partition is one stable sort by
column tag that carries the symbols and record tags along
(``partition_impl="sort"``, ``core.partition``) and every typed column
converts in a fused
gather+convert kernel (``kernels.numparse``) that indexes the CSS in-kernel
— no XLA ``take``/gather between the field index and conversion.  The
fused kernels DMA one contiguous CSS *window* per row block (sorted
offsets make windows contiguous; ``cfg.window_rows`` /
``cfg.max_window_bytes``), so VMEM never holds the whole CSS and per-parse
input size is unbounded by VMEM capacity; see ``docs/ARCHITECTURE.md``.

Driver-specific glue stays in the drivers: the cross-device scans of
``DistributedParser`` plug in via a :class:`ParseStitch` — three hooks
(transition-composite prefix, stitched chunk offsets + shard seeds, and a
cross-shard validation reduction) that let every shard of a mesh run this
*same* ``execute_plan`` composition end to end (conversion included) while
this module stays mesh-agnostic.  ``plan_parse(convert=False)`` remains
available for index-only shard export (each host converts its own batch —
the pre-mesh-native contract, still used by the dry-run roofline cells).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fields as fields_mod
from repro.core import offsets as offsets_mod
from repro.core import partition as partition_mod
from repro.core import tagging as tagging_mod
from repro.core import typeconv as typeconv_mod
from repro.core import validation as validation_mod
from repro.core.backends import ParseBackend
from repro.core.dfa import RECORD_DELIM


class ParseContext(NamedTuple):
    """§3.1/§3.2 output: every chunk knows its context and its summaries."""

    classes: jax.Array                    # (C, K) uint8 symbol classes
    end_states: jax.Array                 # (C,) int32 — per-chunk end state
    saw_invalid: jax.Array                # (C,) bool — invalid sink hit
    summaries: offsets_mod.ChunkSummary   # per-chunk §3.2 scan elements


class ColumnBatch(NamedTuple):
    """§3.3 output: partitioned CSS plus its field index."""

    css: jax.Array        # (N,) uint8 partitioned symbols
    col_start: jax.Array  # (n_cols+1,) int32
    col_count: jax.Array  # (n_cols+1,) int32
    findex: fields_mod.FieldIndex


class MaterializePlan(NamedTuple):
    """Static description of the §3.3 back half (what ``materialize`` builds).

    Everything here is hashable config, baked into the jitted closure:
    the tagging output layout, the partition choice (already resolved —
    never ``"auto"``), and which columns convert to which dtype.  Building
    the plan up front keeps driver call sites one line and makes the
    fused/unfused choice a plan+backend property instead of driver glue.
    """

    tagging: str                                # tagged | inline | vector
    partition_impl: str                         # argsort|scatter|scatter2|
                                                # kernel|sort
    n_cols: int
    max_records: int
    selected: Optional[Tuple[bool, ...]]        # None = every column selected
    convert: Tuple[Tuple[str, int, str], ...]   # (name, schema index, dtype)
    typeconv_path: str = "reference"            # reference | unfused |
                                                # fused-windowed


def plan_materialize(cfg, backend: ParseBackend, *, convert: bool = True
                     ) -> MaterializePlan:
    """Resolve ``cfg`` into a :class:`MaterializePlan` for ``backend``.

    ``partition_impl="auto"`` becomes the backend's platform-aware default
    (on ``pallas``: the payload-carrying sort on a TPU, the jit-fused jnp
    radix pass where the kernels run interpreted); explicit impls are
    validated against ``backend.partition_impls`` so typos and
    backend-foreign impls fail at config time, not under jit.  The
    windowed-DMA knobs (``cfg.window_rows`` / ``cfg.max_window_bytes``,
    pallas fused path) are range-checked here for the same reason, and the
    resolved conversion strategy is recorded as ``plan.typeconv_path``
    (``reference`` / ``unfused`` / ``fused-windowed``)
    so benchmarks and debug output can name the path a config actually
    runs.  With ``convert=False`` the plan builds the CSS + field index
    only (the distributed driver's per-shard contract).
    """
    impl = cfg.partition_impl
    if impl == "auto":
        impl = backend.default_partition_impl(cfg)
    if impl not in backend.partition_impls:
        raise ValueError(
            f"partition_impl {impl!r} not supported by backend "
            f"{backend.name!r}; available: {backend.partition_impls}"
        )
    window_rows = getattr(cfg, "window_rows", 0)
    if window_rows < 0:
        raise ValueError(
            f"window_rows must be ≥ 0 (0 = kernel default), got {window_rows}"
        )
    max_window_bytes = getattr(cfg, "max_window_bytes", 0)
    if max_window_bytes < 0:
        raise ValueError(
            f"max_window_bytes must be ≥ 0 (0 = auto-size), got {max_window_bytes}"
        )
    partition_block_tags = getattr(cfg, "partition_block_tags", 0)
    if partition_block_tags < 0:
        raise ValueError(
            f"partition_block_tags must be ≥ 0 (0 = kernel default), "
            f"got {partition_block_tags}"
        )
    if getattr(cfg, "fused_max_bytes", 0) < 0:
        raise ValueError(
            f"fused_max_bytes must be ≥ 0 (0 = backend default), "
            f"got {cfg.fused_max_bytes}"
        )
    backend.check_config(cfg)
    selected = None
    if not all(c.selected for c in cfg.schema.columns):
        selected = tuple(bool(c.selected) for c in cfg.schema.columns)
    conv: Tuple[Tuple[str, int, str], ...] = ()
    if convert:
        conv = tuple(
            (col.name, c, col.dtype)
            for c, col in enumerate(cfg.schema.columns) if col.selected
        )
    return MaterializePlan(
        tagging=cfg.tagging,
        partition_impl=impl,
        n_cols=cfg.schema.n_cols,
        max_records=cfg.max_records,
        selected=selected,
        convert=conv,
        typeconv_path=backend.typeconv_path(cfg),
    )


class ParseResult(NamedTuple):
    """Everything one parsed partition produces (all device arrays).

    Returned by :func:`execute_plan`; re-exported as
    ``repro.core.parser.ParseResult`` (the public name).
    """

    css: jax.Array                       # (N,) uint8 partitioned symbols
    col_start: jax.Array                 # (n_cols+1,) int32
    col_count: jax.Array                 # (n_cols+1,) int32
    field_offset: jax.Array              # (n_cols, max_records) int32
    field_length: jax.Array              # (n_cols, max_records) int32
    field_present: jax.Array             # (n_cols, max_records) bool — field
                                         # materialised in input (disambiguates
                                         # empty-but-terminated from absent;
                                         # the distributed host assembly keys
                                         # boundary-piece recovery on it)
    values: Dict[str, typeconv_mod.Parsed]
    validation: validation_mod.Validation
    end_state: jax.Array                 # () int32 — carried into next partition
    last_record_end: jax.Array           # () int32 — byte pos of last record
                                         # delimiter (−1 if none); the
                                         # streaming carry-over boundary


class ParsePlan(NamedTuple):
    """Static description of the WHOLE per-partition parse step.

    ``plan_parse`` resolves a config once — the materialize sub-plan plus
    the §4.3 validation contract plus the staged-vs-fused execution choice —
    and ``execute_plan`` runs it.  Like :class:`MaterializePlan`, everything
    here is hashable config baked into the jitted closure; drivers build the
    plan at construction time so typos fail fast and every partition of a
    stream reuses one executable.

    ``interpret`` records whether the backend's Pallas kernels run in the
    interpreter (derived from the platform by
    ``backends.interpret_kernels``, never configured).

    ``execute_path`` records the *resolved* execution tier (``"staged"`` =
    the stage composition below; ``"fused"`` = the backend's whole-pipeline
    ``execute`` override, still subject to the trace-time
    ``backend.fused_max_bytes`` cap — :func:`resolved_execute_path` names
    the tier a concrete input size actually takes) and ``path_reason`` says
    why, replacing silent resolution with an inspectable decision.
    """

    materialize: MaterializePlan
    expected_columns: Optional[int]   # None = skip the §4.3 column-count check
    execute_path: str = "staged"      # staged | fused
    path_reason: str = "fuse_pipeline not requested"
    interpret: bool = False           # Pallas kernels interpreted (off-TPU)


class ParseStitch(NamedTuple):
    """Cross-shard stitching hooks for running :func:`execute_plan` under
    ``shard_map`` (the distributed driver's glue, paper Fig. 4 at mesh
    granularity).

    Each hook exchanges only O(devices · |S|) summary data — never anything
    input-sized — which is the whole scale-out argument: per-shard work is
    N/D bytes, the stitching collectives are constant.

    ``prefix_fn(vecs (C,S)) -> (S,)``
        Exclusive cross-device composite of the §3.1 transition summaries,
        applied before the local exclusive scan (one all-gather of one
        ``(S,)`` vector per device).
    ``offsets_fn(summaries) -> (ChunkOffsets, rec_base (), col_seed (), n_total ())``
        Globally stitched §3.2 chunk offsets plus the shard seeds: the
        first global record id in the shard, the column offset entering the
        shard (field delimiters since the last record delimiter before it),
        and the global record count (one all-gather of one summary triple
        per device).
    ``validation_fn(fields_per_rec (M,), n_local (), end_state (), saw_invalid (), n_total ()) -> Validation``
        Cross-shard §4.3 reduction: ``fields_per_rec`` is the shard's
        *seed-corrected* per-record column counts on shard-local ids (the
        boundary record's count already includes ``col_seed``), and the hook
        reduces the global flags (accepting end state on the last shard,
        min/max columns, conformance) across the mesh axis — O(devices)
        scalars.  ``record_ok`` in the returned Validation stays per-shard.

    With a stitch in place the executor materializes with *shard-local*
    record ids (``record_id - rec_base``) so the field index stays small;
    ``rec_base`` restores global ids.
    """

    prefix_fn: Callable
    offsets_fn: Callable
    validation_fn: Callable


def plan_parse(cfg, backend: ParseBackend, *, convert: bool = True) -> ParsePlan:
    """Resolve ``cfg`` into the full per-partition :class:`ParsePlan`.

    ``convert=False`` plans an index-only materialization (the distributed
    driver's per-shard contract: shards export the CSS + field index and
    each host converts its own batch).

    ``cfg.fuse_pipeline=True`` requests the backend's whole-pipeline fused
    executor (``backend.execute``); the request resolves here — softly, with
    the decision and its reason recorded on the plan — because the fallback
    tiers are part of the design (mirroring the windowed numparse kernels):
    backends without a fused executor, and index-only plans (the megakernel
    produces typed columns, which ``convert=False`` drivers must not pay
    for), stay staged.  On a TPU the request is refused outright: the
    megakernel has no Mosaic lowering yet, and silently running the staged
    path (or the interpreter) in its place would misreport what ran.
    """
    # Fail fast on malformed DFA tables (a hand-rolled or third-party
    # format whose groups/PAD/record-delimiter contract is broken would
    # otherwise surface as wrong parses deep inside a traced kernel).
    # Registered formats (core/formats.py) were validated at registration;
    # this covers configs built around ad-hoc Dfa instances too.
    cfg.dfa.validate_tables()
    interpret = backend.interpret()
    path, reason = "staged", "fuse_pipeline not requested"
    if getattr(cfg, "fuse_pipeline", False):
        if backend.execute is not None and not interpret:
            raise ValueError(
                "fuse_pipeline=True: the whole-pipeline megakernel "
                "(kernels/fused_pipeline) has no Mosaic lowering for the TPU "
                "yet; use the staged pallas path (fuse_pipeline=False)"
            )
        if backend.execute is None:
            reason = f"backend {backend.name!r} has no fused executor"
        elif not convert:
            reason = "index-only plan (convert=False) stays staged"
        else:
            path, reason = "fused", "fuse_pipeline=True"
    return ParsePlan(
        materialize=plan_materialize(cfg, backend, convert=convert),
        expected_columns=cfg.schema.n_cols if cfg.validate_columns else None,
        execute_path=path,
        path_reason=reason,
        interpret=interpret,
    )


def fused_cap(cfg, backend: ParseBackend) -> int:
    """The fused path's effective byte cap: the config override
    (``cfg.fused_max_bytes``, a tunable — the real ceiling is a VMEM
    property only measurable on hardware) or the backend's static default."""
    return int(getattr(cfg, "fused_max_bytes", 0) or 0) or backend.fused_max_bytes


def resolved_execute_path(plan: ParsePlan, backend: ParseBackend,
                          n_bytes: int, cfg=None) -> str:
    """The execution tier ``execute_plan`` actually takes for an input of
    ``n_bytes`` — the plan's choice plus the static byte cap (benchmarks
    and debug output report this instead of guessing).  ``cfg`` enables the
    per-config cap override; without it the backend default applies."""
    if plan.execute_path != "fused":
        return "staged"
    cap = fused_cap(cfg, backend) if cfg is not None else backend.fused_max_bytes
    return "fused" if n_bytes <= cap else "staged"


def dfa_key(dfa) -> Tuple:
    """Content fingerprint of a :class:`~repro.core.dfa.Dfa`.

    ``Dfa`` hashes by identity (its tables are numpy arrays), which is right
    for jit caching within a process but wrong for a serving registry: two
    tenants constructing ``make_csv_dfa()`` independently get *equal* DFAs
    in different objects.  This keys on the table bytes instead.
    """
    return (
        dfa.transition.tobytes(), dfa.emission.tobytes(),
        dfa.group_of.tobytes(), tuple(dfa.group_bytes),
        int(dfa.start_state), dfa.accept.tobytes(), dfa.invalid_state,
    )


def plan_key(cfg, backend: Optional[ParseBackend] = None, *,
             convert: bool = True) -> Tuple:
    """Stable, hashable fingerprint of the executable ``cfg`` compiles to.

    Two configs with equal plan keys trace to bit-identical jitted parse
    steps — same DFA *content* (not object identity), same schema, same
    static capacities, same resolved :class:`ParsePlan`, same backend knobs
    (``backend.config_key``) — so a serving registry can share ONE compiled
    ``Parser``/``StreamSession`` among the tenants that produce them.
    Unequal keys may still compile identically (the key is conservative);
    that only costs a duplicate executable, never a wrong share.
    """
    if backend is None:
        from repro.core import backends as backends_mod
        backend = backends_mod.get_backend(cfg.backend)
    plan = plan_parse(cfg, backend, convert=convert)
    return (
        backend.name,
        backend.config_key(cfg),
        dfa_key(cfg.dfa),
        tuple((c.name, c.dtype, bool(c.selected)) for c in cfg.schema.columns),
        cfg.chunk_size,
        cfg.use_matmul_scan,
        cfg.int_width,
        cfg.float_width,
        plan,
    )


def execute_plan(
    raw_chunks: jax.Array,
    plan: ParsePlan,
    cfg,
    backend: ParseBackend,
    initial_state: Optional[jax.Array] = None,
    stitch: Optional[ParseStitch] = None,
) -> ParseResult:
    """Run one partition through the full §3.1→§4.4 pipeline per ``plan``.

    The single traced composition point every driver executes:
    ``Parser.parse_chunks`` jits exactly this; the streaming engine wraps it
    in its donated carry step (prepend → ``execute_plan`` → extract) and
    ``vmap``s that over a stream axis; the distributed driver runs it on
    every shard under ``shard_map`` with a :class:`ParseStitch` supplying
    the cross-device prefixes/seeds/reductions.  ``initial_state`` overrides
    the DFA start state (the mid-record partition-boundary hook).
    """
    if initial_state is None:
        initial_state = jnp.int32(cfg.dfa.start_state)

    # Whole-pipeline fusion: when the plan resolved to the backend's fused
    # executor AND the partition fits the backend's static VMEM byte cap,
    # hand the entire replay→tag→partition→convert composition to the
    # megakernel.  Both conditions are trace-time Python (shape + plan), so
    # the staged composition below is the statically bounded fallback tier
    # — same design as the windowed numparse cap, one level up.
    if plan.execute_path == "fused" and raw_chunks.size <= fused_cap(cfg, backend):
        return backend.execute(raw_chunks, plan, cfg, initial_state,
                               stitch=stitch)

    # Each stage runs under one ``stage.<name>`` scope, so the device ops of
    # a trace name the stage they belong to; glue between two stages goes
    # under the scope of the stage that consumes it.
    # §3.1/§3.2 — parsing context + fused per-chunk offset summaries (the
    # stitch plugs the cross-device composite prefix into the scan).
    with jax.named_scope("stage.contexts"):
        ctx = determine_contexts(
            raw_chunks, cfg, backend, initial_state=initial_state,
            prefix_fn=None if stitch is None else stitch.prefix_fn,
        )

    # §3.2 — record/column identification from the summaries.  Under a
    # stitch the chunk offsets are globally seeded and materialization runs
    # on shard-local record ids (rec_base restores global ids).
    with jax.named_scope("stage.ids"):
        if stitch is None:
            ids = identify_symbols(ctx)
            rec_for_index = ids.record_id
        else:
            offs, rec_base, col_seed, n_total = stitch.offsets_fn(ctx.summaries)
            ids = identify_symbols(ctx, chunk_offsets=offs)
            rec_for_index = ids.record_id - rec_base

    # §3.2/§3.3 — backend-owned materialization: tagging, stable partition,
    # field index, type conversion (one shared stage, one static plan).
    cols, values = materialize(
        raw_chunks, ctx.classes, rec_for_index, ids.column_id,
        plan.materialize, cfg, backend,
    )

    # §4.3 — validation (stitched: local per-record column counts, with the
    # boundary record's count completed by the cross-device column seed,
    # reduced globally by the stitch hook).
    with jax.named_scope("stage.validate"):
        end_state = ctx.end_states[-1]
        flat_classes = ctx.classes.reshape(-1)
        if stitch is None:
            val = validation_mod.validate(
                flat_classes, rec_for_index, end_state, ctx.saw_invalid,
                cfg.dfa, plan.materialize.max_records,
                expected_columns=plan.expected_columns,
            )
        else:
            fpr = validation_mod.fields_per_record(
                flat_classes, rec_for_index, plan.materialize.max_records
            ).at[0].add(col_seed)
            n_local = jnp.sum(flat_classes == RECORD_DELIM).astype(jnp.int32)
            val = stitch.validation_fn(
                fpr, n_local, end_state, jnp.any(ctx.saw_invalid), n_total
            )

    with jax.named_scope("stage.carry"):
        end_state = end_state.astype(jnp.int32)
        last_record_end = locate_carry(flat_classes)
    return ParseResult(
        css=cols.css,
        col_start=cols.col_start,
        col_count=cols.col_count,
        field_offset=cols.findex.offset,
        field_length=cols.findex.length,
        field_present=cols.findex.present,
        values=values,
        validation=val,
        end_state=end_state,
        last_record_end=last_record_end,
    )


def determine_contexts(
    chunks: jax.Array,
    cfg,
    backend: ParseBackend,
    initial_state: Optional[jax.Array] = None,
    prefix_fn=None,
) -> ParseContext:
    """§3.1: transition vectors → composite scan → replay (+§3.2 summaries).

    ``prefix_fn(vecs) -> (S,)`` supplies a cross-device exclusive composite
    (the distributed parser's all-gather stitch) applied before the local
    exclusive scan; ``initial_state`` overrides the DFA start state (the
    streaming carry-over hook).
    """
    from repro.core import transition as tr

    vecs = backend.chunk_vectors(chunks, cfg)
    scanned = tr.exclusive_scan_vectors(vecs, use_matmul=cfg.use_matmul_scan)
    if prefix_fn is not None:
        prefix = prefix_fn(vecs)
        scanned = tr.compose(jnp.broadcast_to(prefix, scanned.shape), scanned)
    start = tr.start_states(scanned, cfg.dfa, initial_state=initial_state)
    classes, end_states, saw_invalid, summaries = backend.replay_summaries(
        chunks, start, cfg
    )
    return ParseContext(classes, end_states, saw_invalid, summaries)


def identify_symbols(
    ctx: ParseContext,
    chunk_offsets: Optional[offsets_mod.ChunkOffsets] = None,
) -> offsets_mod.SymbolIds:
    """§3.2: per-symbol record/column ids from the chunk summaries.

    ``chunk_offsets`` overrides the local exclusive scan with externally
    stitched offsets (the distributed parser's cross-device prefixes).
    """
    if chunk_offsets is None:
        chunk_offsets = offsets_mod.scan_chunk_offsets(ctx.summaries)
    return offsets_mod.symbol_ids_from_chunks(ctx.classes, chunk_offsets)


def materialize(
    raw_chunks: jax.Array,
    classes: jax.Array,
    record_id: jax.Array,
    column_id: jax.Array,
    plan: MaterializePlan,
    cfg,
    backend: ParseBackend,
) -> Tuple[ColumnBatch, Dict[str, typeconv_mod.Parsed]]:
    """§3.2/§4.1 tagging → §3.3 stable partition → field index → typeconv.

    ``record_id`` is whatever the caller wants in the field index: global
    ids for the single-device parser, shard-local ids for the distributed
    one.  The partition and every per-dtype conversion dispatch through the
    backend (``backend.partition`` / ``backend.parse_field``), except
    ``partition_impl="sort"``, which both backends share: it sorts the
    payloads into column order itself instead of gathering them through a
    permutation (``core.partition.sort_partition``); invalid
    numeric values are normalised to 0 so backends agree bit-for-bit (their
    Horner loops treat non-digit garbage differently, and garbage values
    are meaningless anyway — ``valid`` gates them).  ``str`` is exempt: its
    ``value`` is the field offset, which the export path may use regardless
    of validity.
    """
    n_cols = plan.n_cols
    selected = np.asarray(plan.selected) if plan.selected is not None else None
    with jax.named_scope("stage.tag"):
        tagged = tagging_mod.tag_symbols(
            raw_chunks, classes.reshape(-1), record_id, column_id, n_cols,
            plan.tagging, selected_mask=selected,
        )

    payloads = (tagged.symbol, tagged.rec_tag)
    if plan.tagging != "tagged":
        # delim_flag is structurally all-False in tagged mode: move it into
        # column order only where the field index reads it
        payloads += (tagged.delim_flag,)
    if plan.partition_impl == "sort":
        # one stable sort by column tag carries the payloads: no permutation
        with jax.named_scope("stage.partition"):
            col_start, col_count = partition_mod.column_layout(
                tagged.col_tag, n_cols)
        with jax.named_scope("stage.gather"):
            col_sorted, css, rec_sorted, *flag = partition_mod.sort_partition(
                tagged.col_tag, n_cols, *payloads)
    else:
        with jax.named_scope("stage.partition"):
            part = backend.partition(tagged.col_tag, n_cols,
                                     plan.partition_impl, cfg)
            col_start, col_count = part.col_start, part.col_count
        with jax.named_scope("stage.gather"):
            col_sorted, css, rec_sorted, *flag = partition_mod.apply_partition(
                part.perm, tagged.col_tag, *payloads)
    flag_sorted = flag[0] if flag else None
    with jax.named_scope("stage.fields"):
        findex = fields_mod.field_index(
            plan.tagging, col_sorted, rec_sorted, col_start, n_cols,
            plan.max_records, term_flag=flag_sorted,
        )
    cols = ColumnBatch(css, col_start, col_count, findex)

    values: Dict[str, typeconv_mod.Parsed] = {}
    with jax.named_scope("stage.convert"):
        for name, c, dtype in plan.convert:
            p = backend.parse_field[dtype](
                css, findex.offset[c], findex.length[c], cfg
            )
            if dtype != "str":
                p = p._replace(value=jnp.where(p.valid, p.value,
                                               jnp.zeros_like(p.value)))
            values[name] = p
    return cols, values


def locate_carry(flat_classes: jax.Array) -> jax.Array:
    """§4.4: byte position of the last record delimiter (−1 if none) — the
    streaming carry-over boundary."""
    pos = jnp.arange(flat_classes.shape[0], dtype=jnp.int32)
    return jnp.max(jnp.where(flat_classes == RECORD_DELIM, pos, -1)).astype(jnp.int32)
