"""The sweep driver: coordinate descent over the knob space, measured by
the shared core, bit-identity-gated, budgeted, partial-result safe.

:func:`tune_parse` walks the knobs of ``repro.tune.space.SPACE`` in sweep
order.  Per knob it measures every candidate (plus the incumbent) with
one round-robin :func:`repro.tune.measure.measure_best` group — candidates
of one knob always time against each other in the same interleaved rounds,
so a noise burst cannot crown a winner — and keeps the fastest.  Before a
candidate is ever *timed* its full parse output is compared bit-for-bit
against the reference backend (:func:`repro.tune.measure.parse_signature`);
a mismatching candidate is rejected, recorded, and can never enter the
cache — **tuning can never change outputs**.

``budget`` caps the number of candidate configs evaluated (each costs one
compile + identity parse + its timing rounds); when it runs out the sweep
stops where it stands and the best-so-far assignment is still returned and
cached — a partial tune is a valid tune.  The cache entry is (re)written
after every completed coordinate for the same reason: an interrupted sweep
leaves its last completed coordinate's winners behind.

:func:`tune_stream` measures the §4.4 stream-stage knobs — the streaming
partition size, then the serve tier ladder (batch widths whose measured
aggregate throughput pays for their compile) — into the same cache entry's
``stream`` section; ``serve.ParseService`` reads the ladder through
``PlanRegistry.tuned_tiers``.

CLI: ``python -m repro.tune`` (see ``repro/tune/__main__.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.parser import Parser
from repro.tune import cache as cache_mod
from repro.tune import measure as measure_mod
from repro.tune import space as space_mod


@dataclasses.dataclass
class Trial:
    """One evaluated candidate: its full assignment and how it fared."""

    assignment: Dict[str, Any]
    seconds: Optional[float] = None     # best-of wall clock (None if rejected)
    rejected: Optional[str] = None      # why it never entered timing


@dataclasses.dataclass
class TuneReport:
    """What a tune run found (and how far the budget let it look)."""

    digest: str                         # cache key the result stored under
    assignment: Dict[str, Any]          # winning knob values (defaults incl.)
    seconds: float                      # winner's best-of wall clock
    baseline_seconds: float             # the all-defaults config, same rounds
    n_bytes: int                        # input size (gbps = n_bytes/seconds)
    trials: List[Trial]
    evaluated: int                      # candidates spent (≤ budget)
    budget_exhausted: bool
    stream: Optional[dict] = None       # tune_stream's section, when run


def _reference_cfg(cfg):
    """The oracle config for identity checks: same format/schema semantics,
    reference backend, every tuned knob at its heuristic default."""
    return dataclasses.replace(
        cfg, backend="reference", autotune=False, use_matmul_scan=False,
        **{k.name: k.default for k in space_mod.SPACE
           if k.name != "use_matmul_scan"},
    )


def _entry(digest: str, echo: dict, assignment: Dict[str, Any],
           seconds: float, n_bytes: int, evaluated: int,
           budget_exhausted: bool) -> dict:
    return {
        "key": echo,
        "knobs": dict(assignment),
        "score": {
            "us_per_call": seconds * 1e6,
            "gbps": n_bytes / seconds / 1e9 if seconds > 0 else 0.0,
            "n_bytes": int(n_bytes),
        },
        "meta": {
            "jax": jax.__version__,
            "evaluated": int(evaluated),
            "budget_exhausted": bool(budget_exhausted),
        },
    }


def tune_parse(
    cfg,
    data: bytes,
    *,
    budget: int = 32,
    rounds: int = 4,
    warmup: int = 1,
    cache: Optional[cache_mod.TuneCache] = None,
    save: bool = True,
    measure_fn: Callable = None,
    stages: Optional[Sequence[str]] = None,
    verbose: bool = False,
) -> TuneReport:
    """Coordinate-descent tune of ``cfg``'s backend knobs on ``data``.

    ``measure_fn`` defaults to :func:`measure.measure_best` and is
    injectable (tests pin descent determinism with a stub clock); the
    bit-identity gate always runs on real outputs regardless.  ``stages``
    optionally restricts the sweep to a subset of knob stages.
    """
    measure_fn = measure_fn or (
        lambda thunks: measure_mod.measure_best(
            thunks, rounds=rounds, warmup=warmup))
    from repro.core import backends as backends_mod

    backend = backends_mod.get_backend(cfg.backend)
    digest, echo = cache_mod.tune_key(cfg)
    knobs = space_mod.knobs_for(backend)
    if stages is not None:
        knobs = tuple(k for k in knobs if k.stage in stages)
    assignment = {k.name: getattr(cfg, k.name, k.default) for k in knobs}

    # Oracle outputs: the reference backend parses the same prepared chunks
    # once; every candidate must reproduce this bit-for-bit to be timed.
    ref = Parser(_reference_cfg(cfg))
    chunks = jnp.asarray(ref.prepare(data))
    ref_sig = measure_mod.parse_signature(
        jax.block_until_ready(ref.parse_chunks(chunks)))
    n_bytes = len(data)

    trials: List[Trial] = []
    evaluated = 0
    exhausted = False
    baseline_seconds: Optional[float] = None
    best_seconds = float("inf")

    def build(cand: Dict[str, Any], count: bool = True):
        """Compile + identity-gate one candidate; None if rejected.

        Build, lowering and compile errors propagate: the space only
        offers candidates that compile on this platform, so a failure is
        a fault to report, never a candidate that quietly drops out."""
        nonlocal evaluated
        if count:
            evaluated += 1
        p = Parser(space_mod.apply_assignment(cfg, cand))
        out = jax.block_until_ready(p.parse_chunks(chunks))
        if not measure_mod.signatures_equal(
                measure_mod.parse_signature(out), ref_sig):
            trials.append(Trial(
                dict(cand), rejected="output mismatch vs reference backend"))
            return None
        return p

    for k in knobs:
        cands = [assignment[k.name]] + [
            v for v in k.candidates(backend) if v != assignment[k.name]]
        group: Dict[str, Tuple[Dict[str, Any], Parser]] = {}
        for v in cands:
            is_incumbent = v == assignment[k.name]
            if not is_incumbent and evaluated >= budget:
                exhausted = True
                break
            cand = dict(assignment, **{k.name: v})
            p = build(cand)
            if p is not None:
                group[f"{k.name}={v!r}"] = (cand, p)
        if group:
            measured = measure_fn(
                {lbl: (lambda p=p: p.parse_chunks(chunks))
                 for lbl, (cand, p) in group.items()})
            for lbl, m in measured.items():
                trials.append(Trial(dict(group[lbl][0]), seconds=m.seconds))
                if verbose:
                    print(f"# tune {lbl}: {m.seconds * 1e6:.0f}us")
            win = min(measured, key=lambda lbl: measured[lbl].seconds)
            if baseline_seconds is None:
                inc_lbl = f"{k.name}={assignment[k.name]!r}"
                baseline_seconds = measured.get(
                    inc_lbl, measured[win]).seconds
            assignment = dict(group[win][0])
            best_seconds = measured[win].seconds
            # partial-result safety: every completed coordinate lands in
            # the cache before the next one starts
            if cache is not None and save:
                cache.store(digest, _entry(
                    digest, echo, assignment, best_seconds, n_bytes,
                    evaluated, exhausted))
                cache.save()
        if exhausted:
            break

    # Final head-to-head: the descent's winner vs the all-defaults config,
    # timed in the SAME round-robin group.  Per-coordinate groups each time
    # in their own rounds, so cross-coordinate numbers are not comparable
    # (a noise burst between coordinates would skew the ratio); this last
    # group is the fair comparison — and the demotion gate: a "winner" that
    # cannot beat the defaults when interleaved with them is noise, and the
    # defaults are kept (the tuned-no-slower-than-default bench invariant
    # starts here).
    defaults = {k.name: k.default for k in knobs}
    if assignment != defaults and best_seconds < float("inf"):
        # identity-gated like any candidate; neither costs budget (both
        # configs were already evaluated during the descent)
        d = build(defaults, count=False)
        w = build(assignment, count=False)
        if d is not None and w is not None:
            final = measure_fn({
                "defaults": lambda: d.parse_chunks(chunks),
                "tuned": lambda: w.parse_chunks(chunks),
            })
            baseline_seconds = final["defaults"].seconds
            best_seconds = final["tuned"].seconds
            if verbose:
                print(f"# tune final: defaults={baseline_seconds * 1e6:.0f}us "
                      f"tuned={best_seconds * 1e6:.0f}us")
            if baseline_seconds < best_seconds:
                assignment, best_seconds = dict(defaults), baseline_seconds
    if baseline_seconds is None:
        baseline_seconds = best_seconds
    report = TuneReport(
        digest=digest, assignment=assignment, seconds=best_seconds,
        baseline_seconds=baseline_seconds, n_bytes=n_bytes, trials=trials,
        evaluated=evaluated, budget_exhausted=exhausted,
    )
    if cache is not None and save and best_seconds < float("inf"):
        cache.store(digest, _entry(
            digest, echo, assignment, best_seconds, n_bytes, evaluated,
            exhausted))
        cache.save()
    return report


def tune_stream(
    cfg,
    datas: Sequence[bytes],
    *,
    partition_candidates: Sequence[int] = space_mod.STREAM_PARTITION_BYTES,
    tiers: Sequence[int] = space_mod.STREAM_TIERS,
    cache: Optional[cache_mod.TuneCache] = None,
    save: bool = True,
    repeats: int = 2,
    timer: Callable[[], float] = time.perf_counter,
    verbose: bool = False,
) -> dict:
    """Measure the stream-stage knobs for ``cfg``'s workload.

    Two passes over real :class:`~repro.core.streaming.StreamSession`\\ s:

    1. single-stream partition-size sweep over ``partition_candidates`` —
       best end-to-end drain time of ``datas[0]`` wins;
    2. tier ladder at the winning partition size: aggregate GB/s for each
       batch width in ``tiers`` (capped by ``len(datas)``); a width stays
       in the ladder only if it beats the previous kept width's aggregate
       throughput by >2% — widths that don't pay for their compile are
       dropped, and ``serve.ParseService`` then never compiles them.

    Returns (and caches, under the entry's ``stream`` section) e.g.
    ``{"partition_bytes": 65536, "serve_tiers": [1, 4], "gbps": {...}}``.
    """
    from repro.core.streaming import StreamSession

    base = dataclasses.replace(cfg) if getattr(cfg, "autotune", False) else cfg
    parser = Parser(base)

    def drain(session, sources) -> float:
        best = float("inf")
        for _ in range(max(1, repeats) + 1):  # +1 warmup/compile run
            t0 = timer()
            for _ in session.parse_streams([[d] for d in sources]):
                pass
            best = min(best, timer() - t0)
        return best

    per_pb = {}
    for pb in partition_candidates:
        sess = StreamSession(parser, pb, max_carry_bytes=pb, n_streams=1)
        per_pb[pb] = drain(sess, [datas[0]])
        if verbose:
            print(f"# tune stream partition_bytes={pb}: "
                  f"{per_pb[pb] * 1e6:.0f}us")
    best_pb = min(per_pb, key=per_pb.get)

    gbps: Dict[str, float] = {}
    ladder: List[int] = []
    for s in tiers:
        if s > len(datas):
            break
        sources = list(datas[:s])
        sess = StreamSession(parser, best_pb, max_carry_bytes=best_pb,
                             n_streams=s)
        dt = drain(sess, sources)
        g = sum(len(d) for d in sources) / dt / 1e9
        gbps[f"S{s}"] = g
        if not ladder or g > gbps[f"S{ladder[-1]}"] * 1.02:
            ladder.append(s)
        if verbose:
            print(f"# tune stream S={s}: {g:.3f}GB/s")

    section = {
        "partition_bytes": int(best_pb),
        "serve_tiers": [int(s) for s in ladder],
        "gbps": gbps,
        "partition_us": {str(pb): dt * 1e6 for pb, dt in per_pb.items()},
    }
    if cache is not None and save:
        digest, echo = cache_mod.tune_key(cfg)
        cache.store(digest, {"key": echo, "stream": section})
        cache.save()
    return section
