"""The reduction from a device trace to metrics, on a small trace recorded
on one TPU v5e (``record_fixture.py``: one ``taxi-bulk`` partition), and on
hand-made intervals."""
import gzip
import json
import os
import shutil

import pytest

import cpu_cells  # noqa: F401  (puts bench/ on the path)
from benchlib import trace, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def pb(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "taxi_step.xplane.pb")
    with gzip.open(os.path.join(DATA, "taxi_step.xplane.pb.gz"), "rb") as g, \
            open(path, "wb") as f:
        shutil.copyfileobj(g, f)
    return path


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "taxi_step.json")) as f:
        return json.load(f)


def test_decoder_agrees_with_jax_profile_data(pb):
    from jax.profiler import ProfileData

    ours = {p.name: p for p in xplane.planes(pb)}
    theirs = {p.name: p for p in ProfileData.from_file(pb).planes}
    assert set(ours) == set(theirs)
    dev = ours["/device:TPU:0"]
    ref = {ln.name: list(ln.events) for ln in theirs["/device:TPU:0"].lines}
    for line in dev.lines:
        assert len(line.events) == len(ref[line.name])
        for a, b in zip(line.events, ref[line.name]):
            assert abs(a.start_ns - b.start_ns) < 1.0
            assert abs(a.duration_ns - b.duration_ns) < 1.0


def test_every_device_op_is_labelled(pb):
    t = trace.read(pb)
    assert t.devices == 1 and t.ops
    labelled = [op for op in t.ops if op.stack.startswith("jit(step_one)")]
    assert len(labelled) > 0.9 * len(t.ops)


def test_layers_split_the_busy_time(pb):
    import run
    from benchlib import cells, peaks

    t = trace.read(pb)
    cell = cells.load("taxi-bulk")
    r = run.Readings(cell, t, peaks.peaks("TPU v5 lite"),
                     source_bytes=4 << 20, out_bytes=3e6)
    got = {m["name"]: cell.reader(m["name"]).read(r) for m in cell.per_layer}
    layers = ("scan_ms_per_GB", "partition_ms_per_GB", "typeconv_ms_per_GB",
              "xla_other_ms_per_GB")
    assert all(got[k] > 0 for k in layers)
    busy_ms_per_gb = 1e3 * t.busy_s() / ((4 << 20) / 1e9)
    assert sum(got[k] for k in layers) == pytest.approx(busy_ms_per_gb, rel=1e-9)
    assert 0 <= got["device_idle_pct.bulk"] < 100
    assert 0 < got["step_hbm_roofline"] < 100


def test_reduction_repeats_the_recorded_run(pb, recorded):
    t = trace.read(pb)
    dev = recorded["result"]["device"]
    assert t.busy_s() == pytest.approx(dev["busy_s"], rel=1e-12)
    assert t.window_s == pytest.approx(dev["window_s"], rel=1e-12)
    assert [list(x) for x in t.top_ops(10)] == recorded["result"]["breakdown"]["device_ops"]


def _op(s, e, stack="jit(step_one)/x @ repro/core/x.py"):
    return trace.Op("%x.1 = f32[] x()", stack, s, e - s)


def test_union_counts_nested_and_overlapping_ops_once():
    ops = [_op(0, 10), _op(2, 5), _op(8, 20), _op(30, 40)]
    t = trace.Trace(ops, [("window", 0, 50)], (0, 50))
    assert t.busy_s() == pytest.approx(30e-9)
    assert t.matching_s([r"x\.py"]) == pytest.approx(30e-9)
    assert t.matching_s([r"nothing"]) == 0


def test_idle_gaps_are_named_by_the_innermost_span():
    ops = [_op(0, 10), _op(30, 40)]
    spans = [("window", 0, 60), ("in parse_streams", 5, 35),
             ("feeding source", 15, 25)]
    t = trace.Trace(ops, spans, (0, 60))
    assert t.idle_gaps() == [("feeding source", 20e-9), ("no span", 20e-9)]


def test_window_clips_ops():
    t = trace.Trace([_op(-5, 5), _op(95, 120)], [("window", 0, 100)], (0, 100))
    assert t.busy_s() == pytest.approx(10e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_label_takes_name_stack_and_program_module():
    lab = trace.label({"tf_op": "jit(step_one)/gather:",
                       "source_stack": "/x/src/repro/core/partition.py:113:16\n"
                                       "/x/src/repro/core/stages.py:546:38"})
    assert lab == "jit(step_one)/gather @ repro/core/partition.py"
    assert trace.label({}) == " @ "
