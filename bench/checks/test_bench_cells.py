"""Cells made only of new files are found by name and run end to end on
the CPU, without any file of the benchmark being edited: a configuration
and a traffic mix for the existing window and generator, and a new
generator with a new window."""
import hashlib
import os

from cpu_cells import BENCH, REPO, add_cell, config, run_tiny, tiny_root, write


def _digest() -> str:
    h = hashlib.sha256()
    for base, _dirs, files in sorted(os.walk(BENCH)):
        if "__pycache__" in base:
            continue
        for f in sorted(files):
            with open(os.path.join(base, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    with open(os.path.join(REPO, "BENCHMARK.json"), "rb") as fh:
        h.update(fh.read())
    return h.hexdigest()


def test_new_cell_from_files_found_by_name(tmp_path):
    before = _digest()
    root = tiny_root(tmp_path)
    res = run_tiny(root, "tiny-stream")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"load_GBps", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-2] == "checks"
    assert res["facts"]["partitions_released"] == 0
    assert _digest() == before


def test_delivered_columns_past_the_limit_are_handed_off(tmp_path):
    res = run_tiny(tiny_root(tmp_path, keep_device_bytes=200_000), "tiny-stream")
    assert res["correct"], res["checks"]
    assert res["facts"]["partitions_released"] > 0
    assert res["facts"]["kept_bytes"] <= 200_000 or res["facts"]["partitions"] \
        - res["facts"]["partitions_released"] == 1


GENERATOR = '''
"""Pairs ``k,"v"``: an int and a quoted word with a comma in it."""
import numpy as np

from benchlib import pieces as P


def make(seed, nbytes, spec):
    n = max(1, int(nbytes) // int(spec["record_bytes"]))
    rng = np.random.default_rng(seed)
    word = P.const(n, '"a,b"')
    return P.records([P.integer(rng.integers(0, 1000, n)), word], ",", 3 * n)
'''

WINDOW = '''
"""One-shot parses of the whole block, over and over, for the window."""
import time

import jax

from benchlib import check, oracle, program


def run(cell, seed, seconds, tracer, clock, control=False):
    schema = program.schema_of(cell.config)
    data, _rec_end, _ = cell.block(seed)
    parser = program.Parser(program.parser_config(cell.config, len(data)))
    jax.block_until_ready(parser.parse(data))
    t0 = time.perf_counter()
    runs = 0
    while runs == 0 or time.perf_counter() - t0 < seconds:
        res = parser.parse(data)
        runs += 1
    jax.block_until_ready(res)
    dt = time.perf_counter() - t0
    view = (cell.control().view(data, schema) if control
            else check.host_view(check.columns(res, schema)))
    tally = check.Tally()
    tally.add(check.compare(view, oracle.parse(data), schema, True))
    return dict(metrics={"load_GBps": runs * len(data) / dt / 1e9},
                attempted=runs, failed=0, tally=tally, setup_end=t0,
                peak=program.peak_bytes(), facts={}, readings={})
'''


def test_new_generator_and_window_are_new_files(tmp_path):
    before = _digest()
    root = tiny_root(tmp_path)
    write(root, "bench/gen/tiny_pairs.py", GENERATOR)
    write(root, "bench/windows/oneshot.py", WINDOW)
    cfg = config("yelp_reviews_csv")
    cfg.update(name="tiny_pairs", distinct_bytes=2_000,
               generator=dict(name="tiny_pairs", record_bytes=10),
               schema=[["k", "int32"], ["v", "str"]])
    write(root, "bench/configs/tiny_pairs.json", cfg)
    write(root, "bench/traffic/tiny_oneshot.json", dict(kind="oneshot"))
    add_cell(root, "tiny-oneshot", "tiny_pairs", "bench/configs/tiny_pairs.json",
             like="yelp-bulk")
    res = run_tiny(root, "tiny-oneshot", seconds=0.5)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"load_GBps", "setup_s"}
    assert _digest() == before
