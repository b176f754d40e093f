"""The one-shot mesh window (``bench/windows/oneshot.py``) on four virtual
CPU devices at a small piece size, and the readers of its per-layer
metrics on a hand-made four-chip trace.

The window runs in a child process, since the device count must be set
before JAX starts; its cell is made of new files beside a copy of
``bench/``, found by name like any other.  A run of the program is
correct; the configuration's control and a halo that drops the last byte
of every tail it passes on are not."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from cpu_cells import BENCH, REPO, config, write
from benchlib import cells, trace

PIECE = 4 * 16384


def _root(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("checks", "__pycache__"))
    cfg = config("yelp_reviews_csv_4chip")
    cfg.update(name="tiny_csv_4chip", distinct_bytes=200_000)
    cfg["parser"]["max_carry_bytes"] = 4096
    write(root, "bench/configs/tiny_csv_4chip.json", cfg)
    write(root, "bench/traffic/tiny_oneshot.json", dict(
        json.load(open(os.path.join(BENCH, "traffic", "oneshot_128mib.json"))),
        piece_bytes=PIECE, slice_bytes=4096))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(name="tiny_csv_4chip", source="test",
                                 file="bench/configs/tiny_csv_4chip.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="tiny-oneshot", config="tiny_csv_4chip",
                                   traffic="tiny_oneshot", chips=4, why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "yelp-oneshot-4chip" in m.get("workloads", ()):
            m["workloads"].append("tiny-oneshot")
    write(root, "BENCHMARK.json", bench)
    return root


_CHILD = """
import dataclasses, json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax, jax.numpy as jnp
assert len(jax.devices()) == 4, jax.devices()
import run
from benchlib import cells
from repro.core import backends

if {fault!r} == "dropped_halo_byte":
    be = backends.BACKENDS["pallas"]

    def drop_last(carry_buf, carry_len, *rest):
        return be.prepend_carry(carry_buf, jnp.maximum(carry_len - 1, 0), *rest)

    backends.BACKENDS["pallas"] = dataclasses.replace(be, prepend_carry=drop_last)
out = run.run_cell(cells.load("tiny-oneshot", {root!r}), 2**31 + 11, 2.0, False,
                   require_tpu=False, control={fault!r} == "control")
print("RESULT " + json.dumps(dict(out["result"], facts=out["facts"])))
"""


def _run(root: str, fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(_CHILD.format(
        bench=BENCH, src=os.path.join(REPO, "src"), root=root, fault=fault))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("fault", ["none", "control", "dropped_halo_byte"])
def test_oneshot_window_on_four_devices(tmp_path, fault):
    res = _run(_root(tmp_path), fault)
    facts = res["facts"]
    assert res["attempted"] >= 2 and res["failed"] == 0, res
    assert set(res["metrics"]) == {"load_GBps", "setup_s"}
    assert facts["halo_bytes_max"] > 0 and facts["compiles_in_window"] == 0
    if fault == "none":
        assert res["correct"], (res["checks"], facts["first_mismatches"])
        assert facts["fields_compared"] > 0
    else:
        assert not res["correct"]
        assert res["checks"]["mismatches"]["value"] > 0


class _Readings:
    def __init__(self, cell, t, source_bytes=1e9):
        self.cell, self.trace, self.source_bytes = cell, t, source_bytes
        self.out_bytes, self.peaks = 1e9, {"hbm_bytes_per_s": 1e12}


def _op(dev_start, dur, stack):
    return trace.Op("%f.1 = f32[] fusion()", f"{stack} @ repro/core/x.py",
                    dev_start, dur)


def test_mesh_readers_take_the_any_chip_union():
    """Four chips in lockstep over a 1000 ns window: each chip runs the same
    ops at the same times, so any-chip wall time is one chip's time, where
    ``Trace.busy_s`` reads a quarter of it."""
    cell = cells.load("yelp-oneshot-4chip")
    ops = []
    for _chip in range(4):
        ops += [_op(0, 600, "jit(wrapped)/shard_map/stage.ids/add"),
                _op(600, 100, "jit(wrapped)/shard_map/stage.stitch/all-gather"),
                _op(700, 50, "jit(wrapped)/shard_map/stage.halo/while/body/ppermute"),
                _op(750, 50, "jit(wrapped)/shard_map/xstage.halo/add")]
    spans = [("window", 0, 1000), ("mesh.prepare", 10, 30),
             ("mesh.put", 30, 35), ("mesh.dispatch", 35, 40),
             ("mesh.put", 990, 1010), ("source", 0, 500)]
    t = trace.Trace(ops, spans, (0, 1000), devices=4)
    assert t.busy_s() == pytest.approx(800e-9 / 4)
    r = _Readings(cell, t)
    got = {m: cell.reader(m).read(r) for m in (
        "stage_ms_per_GB.stitch", "stage_ms_per_GB.halo",
        "device_idle_pct.mesh", "step_hbm_roofline.mesh",
        "mesh_host_ms_per_GB")}
    assert got["stage_ms_per_GB.stitch"] == pytest.approx(1e3 * 100e-9)
    assert got["stage_ms_per_GB.halo"] == pytest.approx(1e3 * 50e-9)
    assert got["device_idle_pct.mesh"] == pytest.approx(20.0)
    assert got["step_hbm_roofline.mesh"] == pytest.approx(
        100 * 2e9 / (4 * 1e12) / 800e-9)
    assert got["mesh_host_ms_per_GB"] == pytest.approx(1e3 * 40e-9)
    empty = _Readings(cell, trace.Trace([], [], (0, 10), devices=4))
    for m in ("stage_ms_per_GB.stitch", "stage_ms_per_GB.halo",
              "mesh_host_ms_per_GB"):
        assert cell.reader(m).read(empty) is None
