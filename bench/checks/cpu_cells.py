"""Tiny cells for the CPU tests: files written into a temporary checkout
root beside a copy of ``bench/``, found by name like any other cell.

Importing this module puts ``bench/`` and the program's ``src/`` on the
path, so the test modules import it before ``benchlib``."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for _p in (os.path.join(REPO, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def config(name: str) -> dict:
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def tiny_root(tmp_path, keep_device_bytes: int = 1 << 30) -> str:
    """A checkout root holding a copy of ``bench/`` and ``BENCHMARK.json``
    with one more cell, ``tiny-stream``, whose configuration and traffic
    are new files."""
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("checks", "__pycache__"))
    cfg = config("yelp_reviews_csv")
    cfg.update(name="tiny_csv", distinct_bytes=60_000)
    cfg["parser"]["max_carry_bytes"] = 4096
    write(root, "bench/configs/tiny_csv.json", cfg)
    write(root, "bench/traffic/tiny_stream.json", dict(
        kind="stream", partition_bytes=16384, read_bytes=4096,
        sample_partitions=2, keep_device_bytes=keep_device_bytes))
    add_cell(root, "tiny-stream", "tiny_csv", "bench/configs/tiny_csv.json",
             like="yelp-bulk")
    return root


def add_cell(root: str, name: str, config: str, config_file: str,
             like: str) -> None:
    """A ``workloads`` entry (and its configuration's entry) in
    ``<root>/BENCHMARK.json``, reporting every metric ``like`` reports."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    with open(path) as f:
        bench = json.load(f)
    if config not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append(dict(name=config, source="test",
                                     file=config_file, reduced=[], why="test"))
    bench["workloads"].append(dict(name=name, config=config,
                                   traffic=name.replace("-", "_"), chips=1,
                                   why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    write(root, "BENCHMARK.json", bench)


def write(root: str, rel: str, obj) -> None:
    with open(os.path.join(root, rel), "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def run_tiny(root: str, workload: str, seconds: float = 1.5, seed: int = 2**31 + 5,
             **kw) -> dict:
    """One run on the CPU; the result line's dict with the run's facts
    under ``facts``."""
    import run
    from benchlib import cells

    out = run.run_cell(cells.load(workload, root), seed, seconds, False,
                       require_tpu=False, **kw)
    return dict(out["result"], facts=out["facts"])
