"""The autotuner's search space: every perf knob declared once.

A :class:`Knob` names a ``ParserConfig`` field, the pipeline stage it
gates, the field's *default* value (the "unset" sentinel — only fields
still at their default are cache-resolvable, which is what makes the
precedence ``explicit knob > cache > heuristic default`` decidable on a
frozen dataclass), and its candidate values per backend.  The tuner
sweeps these; :mod:`repro.tune.resolve` validates cached values against
the same declarations, so a stale or hand-edited cache entry can
misconfigure nothing — invalid values fall back to the heuristic default.

Stages (what a knob gates):

  ``scan``       — the §3.1 DFA sweep (grid geometry, scan formulation)
  ``partition``  — the §3.3 stable partition (impl choice, kernel blocks)
  ``typeconv``   — the §3.3 conversion kernels (fusion, window DMA tiles)
  ``pipeline``   — the staged-vs-fused whole-pipeline execution choice
  ``stream``     — the §4.4 streaming/serving geometry (partition bytes,
                   recompile tier ladder).  Stream knobs are not
                   ``ParserConfig`` fields; they live in the cache entry's
                   ``stream`` section (see ``STREAM_PARTITION_BYTES`` /
                   ``STREAM_TIERS`` and ``tuner.tune_stream``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple


@dataclasses.dataclass(frozen=True)
class Knob:
    """One tunable ``ParserConfig`` field (see module docstring).

    ``candidates(backend) -> tuple`` returns the values worth measuring on
    that backend — empty means the knob does not apply (the backend's
    traced code never reads it).  ``valid(backend, value)`` is the
    constraint the resolver re-checks on cached values.
    """

    name: str
    stage: str
    default: Any
    candidates: Callable[[Any], Tuple]
    doc: str

    def valid(self, backend, value) -> bool:
        return value in self.candidates(backend)


def _on_tpu() -> bool:
    from repro.core.backends import interpret_kernels

    return not interpret_kernels()


def _pallas_only(vals, tpu_vals=None):
    """Candidates for the pallas backend; ``tpu_vals`` narrows them where
    the kernels compile for a TPU (block shapes Mosaic tiles, VMEM that
    holds the step)."""
    def cands(be):
        if be.name != "pallas":
            return ()
        return tpu_vals if tpu_vals is not None and _on_tpu() else vals
    return cands


def _has_fused_executor(vals):
    # The megakernel has no Mosaic lowering yet: never offered on a TPU.
    return lambda be: vals if be.execute is not None and not _on_tpu() else ()


#: The search space, in sweep order: cheap/high-leverage knobs first so a
#: tight budget still covers them (the tuner walks coordinates in this
#: order and stops when the candidate budget runs out).
SPACE: Tuple[Knob, ...] = (
    Knob(
        "partition_impl", "partition", "auto",
        lambda be: be.partition_impls,
        "§3.3 stable-partition implementation (jnp radix variants vs the "
        "Pallas radix kernel).  The hand heuristic — scatter on reference, "
        "scatter2 off the chip/kernel on a TPU on pallas — becomes "
        "the cold-cache default.",
    ),
    Knob(
        "fuse_pipeline", "pipeline", None,
        _has_fused_executor((False, True)),
        "Staged composition vs the whole-pipeline megakernel "
        "(ParsePlan.execute_path).  Per-format measurements decide: on "
        "interpret-CPU the megakernel loses on clf/jsonl/zone and wins on "
        "csv (see BENCH_parser.json); None = unset, resolved at config "
        "time.",
    ),
    Knob(
        "use_matmul_scan", "scan", False,
        lambda be: (False, True),
        "§3.1 composite scan as one-hot matmuls (the paper's SpMV "
        "formulation) vs gathers — which wins is purely a device property.",
    ),
    Knob(
        "block_chunks", "scan", 0,
        _pallas_only((64, 128, 256, 512), tpu_vals=(128, 256, 512)),
        "Chunks per Pallas grid step in the §3.1 DFA-scan kernels "
        "(launch geometry; 0 = kernel default).",
    ),
    Knob(
        "window_rows", "typeconv", 0,
        _pallas_only((0, 128, 256, 1024), tpu_vals=(0, 128, 256)),
        "Rows per CSS-window DMA block in the fused numparse kernels "
        "(0 = kernel default).",
    ),
    Knob(
        "max_window_bytes", "typeconv", 0,
        _pallas_only((0, 4096, 16384), tpu_vals=(0, 4096)),
        "Static CSS window tile bytes (0 = auto-size from window_rows and "
        "the dtype width).",
    ),
    Knob(
        "fuse_typeconv", "typeconv", True,
        _pallas_only((True, False)),
        "Fused gather+convert kernels vs the unfused XLA-gather + "
        "arithmetic-kernel path.",
    ),
    Knob(
        "partition_block_tags", "partition", 0,
        _pallas_only((0, 1024, 4096), tpu_vals=(0, 512)),
        "Tags per block in the Pallas radix-partition kernel "
        "(partition_impl='kernel' only; 0 = kernel default).",
    ),
    Knob(
        "fused_max_bytes", "pipeline", 0,
        _has_fused_executor((0, 1 << 20, 16 << 20)),
        "Static byte cap above which a fused plan falls back to the "
        "staged tier (0 = backend default, 4 MiB on pallas).",
    ),
)

#: Stream-stage candidates (cache entry ``stream`` section, not
#: ``ParserConfig`` fields): partition sizes for the §4.4 streaming engine
#: and the batch-width ladder the serve layer's recompile tiers are chosen
#: from (``tuner.tune_stream`` measures aggregate GB/s per width and keeps
#: the widths that pay for their compile).
STREAM_PARTITION_BYTES: Tuple[int, ...] = (1 << 13, 1 << 14, 1 << 16, 1 << 17)
STREAM_TIERS: Tuple[int, ...] = (1, 4, 16, 64)


def knobs_for(backend, stage: str = None) -> Tuple[Knob, ...]:
    """The knobs that apply to ``backend`` (non-empty candidate sets),
    optionally filtered to one stage, in sweep order."""
    return tuple(
        k for k in SPACE
        if k.candidates(backend) and (stage is None or k.stage == stage)
    )


def knob(name: str) -> Knob:
    for k in SPACE:
        if k.name == name:
            return k
    raise KeyError(f"unknown knob {name!r}; space: {[k.name for k in SPACE]}")


def apply_assignment(cfg, assignment: Dict[str, Any]):
    """``cfg`` with ``assignment``'s knob values applied.

    ``autotune`` is forced off so the tuner's candidate configs resolve
    exactly the assignment under measurement — never a cache entry.
    """
    return dataclasses.replace(cfg, autotune=False, **assignment)


def defaults_for(backend) -> Dict[str, Any]:
    """The all-defaults assignment for ``backend`` — the sweep's starting
    point and the baseline every tuned config is compared against."""
    return {k.name: k.default for k in knobs_for(backend)}
