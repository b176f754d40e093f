"""The chip benchmark: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration,
traffic mix, window, data generator, control and per-layer metric readers
are files under ``bench/``, found by name (see ``benchlib/cells.py``).  The run refuses to measure anywhere but on as
many TPU chips as the cell asks for: it exits non-zero and prints no
result.  Set-up (data from the seed, the persistent compilation cache,
compiling or fetching the cell's own step shapes) counts as ``setup_s``;
then the window runs for ``--seconds``, and the comparison with the plain
reference runs after it.

Standard output: one ``bench-facts`` line (the resolved plan, compile
seconds, peak device memory), then the JSON result as the last
line.  Standard error ends with each number compared beside its limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Readings:
    """What a per-layer metric reader sees (``bench/metrics/<name>.py``)."""

    def __init__(self, cell, trace, peaks, **host):
        self.cell, self.trace, self.peaks = cell, trace, peaks
        self.source_bytes = host.get("source_bytes")
        self.out_bytes = host.get("out_bytes")

    def patterns(self, metric: str):
        """The name patterns another metric's reader claims."""
        return tuple(self.cell.reader(metric).PATTERNS)


def _device(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"JAX found {len(devs)} {devs[0].platform} device(s); "
                     f"this cell needs {chips} TPU chip(s)")
    return devs[0]


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             control: bool = False, require_tpu: bool = True,
             t_process: float = None, **window_args) -> dict:
    """One run of ``cell``: the result dict the last line prints, plus
    ``facts`` and ``checks``."""
    from benchlib import peaks as peaks_mod
    from benchlib import program, trace as trace_mod

    dev = _device(cell.chips, require_tpu)
    clock = program.CompileClock()
    tracer = trace_mod.Tracer(trace)
    out = cell.window().run(cell, seed, seconds, tracer, clock, control=control,
                     **window_args)
    setup_s = out["setup_end"] - (T_PROCESS if t_process is None else t_process)
    facts = dict(out["facts"], compile_s=clock.seconds, compile_events=clock.events,
                 peak_bytes_in_use=out["peak"], setup_s=setup_s)

    metrics, device = {}, dict(platform=dev.platform, kind=dev.device_kind,
                               count=cell.chips, memory_peak_bytes=out["peak"])
    breakdown = None
    if trace:
        path = out["readings"].pop("xplane")
        tr = trace_mod.read(path, tracer.names) if path else None
        tracer.cleanup()
        if tr is None or not tr.ops:
            raise RuntimeError("the traced window holds no device operation")
        peaks = peaks_mod.peaks(dev.device_kind) if require_tpu else None
        r = Readings(cell, tr, peaks, **out["readings"])
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(10)],
                     "idle_gaps": [list(x) for x in tr.idle_gaps(10)]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for name, value in dict(out["metrics"], setup_s=setup_s).items():
            metrics[name] = {"value": value, "unit": units[name]}

    limits = cell.config["limits"]
    tally = out["tally"]
    checks = {"mismatches": {"value": tally.mismatches,
                             "limit": limits["mismatches"]}}
    if tally.floats:
        checks["float_rel_gap"] = {"value": tally.float_rel_gap,
                                   "limit": limits["float_rel_gap"]}
    correct = all(c["value"] <= c["limit"] and math.isfinite(c["value"])
                  for c in checks.values()) and out["failed"] == 0
    facts.update(fields_compared=tally.fields, floats_compared=tally.floats,
                 first_mismatches="; ".join(tally.first))
    result = dict(correct=correct, attempted=out["attempted"],
                  failed=out["failed"], metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return dict(result=result, facts=facts)


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), keeping
    every program, the small ones too, so that a second run of a cell
    compiles nothing."""
    import jax

    from repro import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the configuration's control in the program's "
                         "place for the comparison (it must come out not "
                         "correct); not part of a benchmark run")
    args = ap.parse_args(argv)

    from benchlib import cells

    cell = cells.load(args.workload)
    enable_cache()
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       control=args.control)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print("bench-facts " + json.dumps(out["facts"]), flush=True)
    for name, c in out["result"]["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
