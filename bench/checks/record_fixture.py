"""Record the small chip trace that ``test_bench_reduce.py`` reads.

    python bench/checks/record_fixture.py     # on one TPU chip

It runs ``taxi-bulk`` traced for one partition's worth of window and
writes the profiler's ``.xplane.pb`` gzipped to
``bench/checks/data/taxi_step.xplane.pb.gz``, beside the facts of that
run in ``taxi_step.json``.
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import time

T = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import run  # noqa: E402
from benchlib import cells, trace  # noqa: E402


def main() -> int:
    kept = {}
    stop = trace.Tracer.stop

    def keep(self):
        path = stop(self)
        with open(path, "rb") as f, gzip.open(
                os.path.join(HERE, "data", "taxi_step.xplane.pb.gz"), "wb") as g:
            shutil.copyfileobj(f, g)
        kept["path"] = path
        return path

    trace.Tracer.stop = keep
    run.enable_cache()
    out = run.run_cell(cells.load("taxi-bulk"), 2**31 + 21, 0.01, True, t_process=T)
    with open(os.path.join(HERE, "data", "taxi_step.json"), "w") as f:
        json.dump(dict(result=out["result"], facts=out["facts"]), f, indent=1)
    print(json.dumps(out["result"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
