"""End-to-end single-device ParPaRaw parse pipeline (paper §3).

Pipeline (all on-device, one jit):

    bytes ─▶ symbol groups ─▶ chunk transition vectors ─▶ composite scan
          ─▶ replay (class codes) ─▶ record/column ids ─▶ materialize
             (tagging ─▶ stable partition (CSS) ─▶ field index ─▶ type
              conversion, per a static MaterializePlan) ─▶ validation

The stage bodies live in ``core/stages.py`` and are shared with the
distributed and streaming drivers; ``ParserConfig.backend`` selects who runs
the byte-level hot loops (``"reference"`` jnp vs ``"pallas"`` kernels, see
``core/backends.py``).

The pipeline is plan + executor: construction resolves the config into a
static :class:`stages.ParsePlan` once, and ``parse_chunks`` is a single
``jax.jit`` of :func:`stages.execute_plan` over that plan.  Static
configuration (DFA, schema, chunk size, capacities, backend) is baked into
the jitted closure; the only traced input is the padded byte buffer, so
repeated parses of same-shaped partitions reuse one executable — the
property the streaming engine (core/streaming.py) builds its device-carry
step on.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends as backends_mod
from repro.core import stages as stages_mod
from repro.core import typeconv as typeconv_mod
from repro.core.dfa import PAD_BYTE, Dfa


@dataclasses.dataclass(frozen=True)
class Column:
    name: str
    dtype: str = "str"  # int32 | float32 | date | str
    selected: bool = True  # paper §4.3: deselected columns' symbols are
                           # marked irrelevant at tagging and never partake
                           # in partitioning/typeconv

    def __post_init__(self):
        assert self.dtype in typeconv_mod.PARSERS, self.dtype


@dataclasses.dataclass(frozen=True)
class Schema:
    columns: Tuple[Column, ...]

    @classmethod
    def of(cls, *cols: Tuple[str, str]) -> "Schema":
        return cls(tuple(Column(n, d) for n, d in cols))

    @property
    def n_cols(self) -> int:
        return len(self.columns)


@dataclasses.dataclass(frozen=True)
class ParserConfig:
    """Static parse-pipeline configuration, baked into the jitted closure.

    Every knob is hashable config resolved at construction time
    (``__post_init__`` runs ``stages.plan_parse`` so typos fail fast,
    before any tracing).  Knobs:

    ``dfa``
        The format automaton (``make_csv_dfa`` / ``make_log_dfa`` / …):
        byte→group table, transition table, symbol classes (paper §3.1).
    ``schema``
        Column names, dtypes (``int32`` / ``float32`` / ``date`` / ``str``)
        and selection flags.  Deselected columns are dropped at tagging
        (paper §4.3) and never partake in partitioning or conversion.
    ``max_records``
        Field-index capacity per parse: the ``(n_cols, max_records)``
        offset/length matrices are statically this wide.  Records beyond it
        flag ``validation.truncated``.
    ``chunk_size``
        Bytes per chunk in the §3.1 DFA sweep.  Inputs are padded to whole
        chunks; one chunk is the granularity of the transition-vector scan.
    ``tagging``
        §3.2/§4.1 tagging-output layout: ``tagged`` (per-symbol
        record+column tags, the default), ``inline`` (terminator bytes kept
        inline in the CSS) or ``vector`` (separate terminator bit vector).
    ``partition_impl``
        §3.3 stable-partition implementation: ``auto`` (backend-resolved —
        see ``backends.default_partition_impl``), ``argsort``, ``scatter``,
        ``scatter2`` (jnp radix variants) or ``kernel`` (single-pass Pallas
        radix kernel, pallas backend only).
    ``use_matmul_scan``
        §3.1 composite scan as one-hot matmuls instead of gathers (the
        paper's SpMV formulation; useful where gathers are slow).
    ``int_width`` / ``float_width``
        Fixed conversion widths (bytes incl. sign) for int32/float32
        fields.  Fields longer than the width fail conversion (``valid``
        clears) — they also bound the fused kernels' per-field reads.
    ``validate_columns``
        §4.3 validation: require every record to have exactly
        ``schema.n_cols`` columns.
    ``backend``
        Stage-implementation bundle: ``reference`` (pure jnp oracle) or
        ``pallas`` (TPU kernels); see ``core/backends.py``.  The registry
        is open — third-party backends register under new names.
    ``block_chunks``
        Chunks per Pallas grid step in the §3.1 DFA-scan kernels
        (``0`` = kernel default).
    ``fuse_typeconv``
        pallas: convert typed columns in fused gather+convert kernels that
        index the CSS in-kernel (no XLA gather, no ``(R, W)`` byte-matrix
        round-trip).  ``False`` restores the unfused XLA-gather +
        arithmetic-kernel path — the fusion's escape hatch and benchmark
        baseline.
    ``window_rows``
        pallas fused path: rows per CSS-window DMA block.  ``0`` uses the
        numparse kernel default (512).  Any positive value trades VMEM
        footprint (smaller windows) against grid overhead (more steps).
    ``max_window_bytes``
        pallas fused path: static CSS window tile in bytes.  ``0``
        auto-sizes from ``window_rows`` and the dtype's width (enough for
        every field ≤ width plus a terminator per row); explicit values are
        rounded up to the 128-byte lane alignment.  Columns whose fields
        overflow the tile (a mega-field) fall back at run time to per-row
        windows, so the fallback never compiles an unbounded-VMEM kernel
        either.
    ``fuse_pipeline``
        pallas backend: run the whole replay→tag→partition→convert
        composition as ONE megakernel per partition
        (``kernels/fused_pipeline``) with no ``(R,)`` tag/offset arrays or
        permutation round-trips through HBM.  Resolved softly at plan time
        (``stages.plan_parse`` records the decision on
        ``ParsePlan.execute_path``): backends without a fused executor and
        index-only (``convert=False``) plans stay staged, and partitions
        larger than the backend's static ``fused_max_bytes`` cap take the
        staged tier at trace time.  Bit-identical to the staged path.
        ``None`` (the default) means *unset*: autotune resolution may fill
        it from measurements; unresolved it behaves as ``False`` (staged).
    ``partition_block_tags``
        pallas radix-partition kernel (``partition_impl="kernel"``): tags
        per kernel block.  ``0`` = kernel default.
    ``fused_max_bytes``
        Override of the backend's static fused-path byte cap (partitions
        larger than the cap run the staged tier).  ``0`` = backend default
        (4 MiB on pallas) — the real ceiling is a VMEM property only
        measurable on hardware, which is why it is a tunable.
    ``autotune``
        Consult the measured-config cache (``repro.tune``) at construction:
        every knob field still at its declared default is filled from the
        cache entry for this (backend, device, workload-shape) key, if one
        exists.  Explicitly set knobs always win; a cold cache leaves the
        heuristic defaults — resolution precedence ``explicit knob > cache
        > heuristic default`` (see ``docs/ARCHITECTURE.md`` §Autotuner).
        Cached values were bit-identity-checked against the reference
        backend when measured, so autotuning can never change outputs.
    """

    dfa: Dfa
    schema: Schema
    max_records: int
    chunk_size: int = 64
    tagging: str = "tagged"          # tagged | inline | vector
    partition_impl: str = "auto"     # auto | argsort | scatter | scatter2 |
                                     # kernel (backend-resolved; stages.py)
    use_matmul_scan: bool = False
    int_width: int = 11
    float_width: int = 24
    validate_columns: bool = False
    backend: str = "reference"       # reference | pallas (core/backends.py)
    block_chunks: int = 0            # pallas DFA-scan grid: chunks per step
                                     # (0 = kernel default)
    fuse_typeconv: bool = True       # pallas: fused gather+convert kernels
                                     # (False = XLA gather + arithmetic kernel)
    window_rows: int = 0             # pallas fused: rows per CSS-window DMA
                                     # (0 = kernel default, -1 = whole CSS)
    max_window_bytes: int = 0        # pallas fused: static window tile bytes
                                     # (0 = auto-size from window_rows+width)
    fuse_pipeline: Optional[bool] = None  # pallas: whole-pipeline megakernel
                                     # (replay→tag→partition→convert, one
                                     # kernel per partition; soft-resolves
                                     # to staged on unsupported plans).
                                     # None = unset (autotune-resolvable),
                                     # behaves as False.
    partition_block_tags: int = 0    # pallas radix-partition kernel: tags
                                     # per block (0 = kernel default)
    fused_max_bytes: int = 0         # fused-path byte cap override
                                     # (0 = backend default)
    autotune: bool = False           # fill default-valued knobs from the
                                     # measured-config cache (repro.tune)

    def __post_init__(self):
        if self.autotune:
            # Measured-config resolution (repro.tune): fill every knob
            # field still at its declared default from the cache entry for
            # this (backend, device, workload-shape) key.  Runs before plan
            # validation so resolved values flow through plan_key /
            # config_key exactly like explicit ones.  Lazy import: the tune
            # package imports this module.
            from repro.tune import resolve as tune_resolve

            for name, value in tune_resolve.resolved_knobs(self).items():
                object.__setattr__(self, name, value)
        # fail fast on typos: backend name + partition impl resolution +
        # window-knob ranges (plan_parse exercises the full planning layer)
        stages_mod.plan_parse(self, backends_mod.get_backend(self.backend))

    @property
    def record_delim_byte(self) -> int:
        return self.dfa.group_bytes[0]


#: The per-partition parse output — defined next to the executor in
#: ``core/stages.py``; re-exported here as the public name.
ParseResult = stages_mod.ParseResult


class Parser:
    """User-facing parser: host-side input prep + one jitted plan executor."""

    def __init__(self, cfg: ParserConfig):
        self.cfg = cfg
        self.backend = backends_mod.get_backend(cfg.backend)
        #: Static ParsePlan resolved once; `parse_chunks` and the streaming
        #: engine's carry step both execute exactly this plan.
        self.plan = stages_mod.plan_parse(cfg, self.backend)
        self._jit = jax.jit(
            lambda chunks, st: stages_mod.execute_plan(
                chunks, self.plan, cfg, self.backend, initial_state=st
            )
        )

    # -- host-side -----------------------------------------------------------
    def prepare(self, data: bytes, pad_to: Optional[int] = None) -> np.ndarray:
        """bytes → ``(n_chunks, chunk_size) uint8`` with trailing record
        delimiter + PAD padding.  ``pad_to`` fixes the total byte capacity so
        different partitions share one compiled shape."""
        k = self.cfg.chunk_size
        raw = np.frombuffer(data, np.uint8)
        need_delim = raw.size == 0 or raw[-1] != self.cfg.record_delim_byte
        n = raw.size + (1 if need_delim else 0)
        total = pad_to if pad_to is not None else ((n + k - 1) // k) * k
        if total < n:
            raise ValueError(f"pad_to={pad_to} smaller than input ({n} bytes)")
        buf = np.full(total, PAD_BYTE, np.uint8)
        buf[: raw.size] = raw
        if need_delim:
            buf[raw.size] = self.cfg.record_delim_byte
        return buf.reshape(-1, k)

    # -- device-side ---------------------------------------------------------
    def parse_chunks(self, chunks, initial_state: Optional[jax.Array] = None) -> ParseResult:
        if initial_state is None:
            initial_state = jnp.int32(self.cfg.dfa.start_state)
        return self._jit(jnp.asarray(chunks), jnp.asarray(initial_state, jnp.int32))

    def parse(self, data: bytes) -> ParseResult:
        return self.parse_chunks(self.prepare(data))

    def infer_types(self, result: ParseResult):
        """Paper §4.3 type inference: min numeric type per column via a
        parallel reduction over the already-columnar CSS."""
        out = {}
        for c, col in enumerate(self.cfg.schema.columns):
            if not col.selected:
                continue
            n = self.cfg.max_records
            present = jnp.arange(n) < result.validation.n_records
            code = typeconv_mod.infer_column_type(
                result.css, result.field_offset[c], result.field_length[c],
                present, width=self.cfg.float_width,
            )
            out[col.name] = typeconv_mod.TYPE_CODES[int(code)]
        return out

    # -- export --------------------------------------------------------------
    def to_arrow(self, result: ParseResult) -> Dict[str, dict]:
        """Arrow-layout host export: per column a dict with ``validity``
        (packed bits), plus either ``values`` (numeric) or
        ``offsets``+``data`` (strings).  No pyarrow dependency; layouts match
        the Arrow columnar spec so buffers can be zero-copy wrapped."""
        n = int(result.validation.n_records)
        n = min(n, self.cfg.max_records)
        out = {}
        css = np.asarray(result.css)
        for c, col in enumerate(self.cfg.schema.columns):
            if not col.selected:
                continue
            parsed = result.values[col.name]
            if col.dtype == "str":
                ln = np.asarray(result.field_length[c][:n], np.int32)
                start = int(result.col_start[c])
                count = int(result.col_count[c])
                offsets = np.zeros(n + 1, np.int32)
                np.cumsum(ln, out=offsets[1:])
                data = css[start : start + count]
                if self.cfg.tagging != "tagged":
                    # Terminators/delimiters live inside the CSS in these
                    # modes; re-gather the value bytes densely for export.
                    off_abs = np.asarray(result.field_offset[c][:n])
                    pieces = [css[o : o + l] for o, l in zip(off_abs, ln)]
                    data = np.concatenate(pieces) if pieces else np.zeros(0, np.uint8)
                validity = ~np.asarray(parsed.empty[:n])
                out[col.name] = dict(offsets=offsets, data=data, validity=np.packbits(validity, bitorder="little"))
            else:
                validity = np.asarray(parsed.valid[:n])
                out[col.name] = dict(
                    values=np.asarray(parsed.value[:n]),
                    validity=np.packbits(validity, bitorder="little"),
                )
        return out
