"""``python -m repro.tune`` — run the benchmark-driven autotuner.

Sweeps the knob space (``repro.tune.space``) per (workload, backend) on
this machine's device and writes the winning, bit-identity-checked configs
into the persistent cache (``repro.tune.cache``), where
``ParserConfig(autotune=True)`` / ``tuned_parser_config`` resolve them::

    PYTHONPATH=src python -m repro.tune \\
        [--workloads yelp,taxi,csv,jsonl,zone,clf] \\
        [--backends reference,pallas] [--records 250] [--budget 32] \\
        [--rounds 4] [--stream] [--seed | --cache PATH] [-v]

``--seed`` writes the committed seed cache
(``src/repro/tune/default_cache.json``) instead of the user cache — the
nightly interpret-CPU refresh; a fresh checkout then resolves to measured
configs before anyone tunes locally.  ``--stream`` additionally measures
the §4.4 stream knobs (streaming partition size + the serve recompile-tier
ladder) into each entry's ``stream`` section.

Workload fingerprints here deliberately match the benchmark suite's
configs (same schemas, same chunk sizes, same per-format tunings), so a
tune run and a ``bench_parser --tuned`` run resolve the same cache entries.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from repro import compile_cache
from repro.configs.parse_formats import tuned_parser_config
from repro.core import ParserConfig, Schema, make_csv_dfa
from repro.data import synth
from repro.tune import cache as cache_mod
from repro.tune import tuner

CSV_WORKLOADS = ("yelp", "taxi")
FORMAT_WORKLOADS = ("csv", "jsonl", "zone", "clf")
ALL_WORKLOADS = CSV_WORKLOADS + FORMAT_WORKLOADS


def workload(name: str, records: int, backend: str):
    """``(cfg, data)`` for one named workload — the same configs the
    benchmark suite runs, with ``autotune=False`` (the tuner must start
    from the heuristic defaults, never from its own cache)."""
    if name in CSV_WORKLOADS:
        rng = np.random.default_rng(0)
        if name == "yelp":
            data = synth.yelp_like(rng, records)
            schema = synth.YELP_SCHEMA
        else:
            data = synth.taxi_like(rng, 4 * records)
            schema = synth.TAXI_SCHEMA
        cfg = ParserConfig(
            dfa=make_csv_dfa(), schema=Schema.of(*schema),
            max_records=1 << 12, chunk_size=64, backend=backend)
    elif name in FORMAT_WORKLOADS:
        data = synth.format_payload(name, records)
        cfg = tuned_parser_config(
            name, max_records=1 << 12, backend=backend, autotune=False)
    else:
        raise ValueError(
            f"unknown workload {name!r}; available: {ALL_WORKLOADS}")
    return cfg, data


def stream_sources(name: str, records: int, n: int):
    """Per-stream sources for ``tune_stream`` (distinct seeds where the
    generator takes one; deterministic formats replicate)."""
    if name == "yelp":
        return [synth.yelp_like(np.random.default_rng(s), records)
                for s in range(n)]
    if name == "taxi":
        return [synth.taxi_like(np.random.default_rng(s), records)
                for s in range(n)]
    return [synth.format_payload(name, records)] * n


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(
        prog="python -m repro.tune", description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", default=",".join(ALL_WORKLOADS),
                    help=f"comma list from {ALL_WORKLOADS}")
    ap.add_argument("--backends", default="reference,pallas")
    ap.add_argument("--records", type=int, default=250,
                    help="records per workload (taxi runs 4x)")
    ap.add_argument("--budget", type=int, default=32,
                    help="max candidate configs evaluated per (workload, backend)")
    ap.add_argument("--rounds", type=int, default=4,
                    help="round-robin timing rounds per candidate group")
    ap.add_argument("--stream", action="store_true",
                    help="also tune the §4.4 stream knobs (partition size, "
                         "serve tier ladder)")
    ap.add_argument("--stream-tiers", default="1,4",
                    help="serve batch widths to measure with --stream")
    ap.add_argument("--seed", action="store_true",
                    help="write the committed seed cache "
                         "(src/repro/tune/default_cache.json) instead of "
                         "the user cache")
    ap.add_argument("--cache", default=None, metavar="PATH",
                    help="explicit cache file (overrides --seed/user cache)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.cache:
        path = args.cache
    elif args.seed:
        path = cache_mod.seed_cache_path()
    else:
        path = cache_mod.user_cache_path()
    cache = cache_mod.TuneCache(path)
    workloads = [w.strip() for w in args.workloads.split(",") if w.strip()]
    backends = [b.strip() for b in args.backends.split(",") if b.strip()]

    failures = 0
    for name in workloads:
        for backend in backends:
            cfg, data = workload(name, args.records, backend)
            try:
                rep = tuner.tune_parse(
                    cfg, data, budget=args.budget, rounds=args.rounds,
                    cache=cache, verbose=args.verbose)
            except Exception as e:
                print(f"tune {name}/{backend}: FAILED {e!r}", file=sys.stderr)
                failures += 1
                continue
            rejected = sum(1 for t in rep.trials if t.rejected)
            speedup = (rep.baseline_seconds / rep.seconds
                       if rep.seconds > 0 else float("nan"))
            knobs = {k: v for k, v in sorted(rep.assignment.items())}
            print(f"tune {name}/{backend}: {rep.seconds * 1e6:.0f}us "
                  f"({speedup:.2f}x vs defaults, {rep.evaluated} candidates, "
                  f"{rejected} rejected"
                  f"{', budget exhausted' if rep.budget_exhausted else ''})"
                  f" -> {knobs}")
            if args.stream:
                tiers = tuple(int(t) for t in args.stream_tiers.split(","))
                # full-size sources: partition-size winners measured on
                # truncated streams do not transfer (fixed overhead
                # dominates and small partitions look artificially good)
                srcs = stream_sources(name, args.records, max(tiers))
                sec = tuner.tune_stream(
                    cfg, srcs, tiers=tiers, cache=cache,
                    verbose=args.verbose)
                print(f"tune {name}/{backend}/stream: "
                      f"partition_bytes={sec['partition_bytes']} "
                      f"serve_tiers={sec['serve_tiers']}")
    print(f"# cache: {len(cache)} entries -> {path}")
    cache_mod.reset()  # this process resolves against the fresh file
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
