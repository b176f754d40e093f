"""Ahead-of-time compiles of the staged pallas parse path for a TPU v5e.

Each test compiles one main-path kernel (or one whole staged parse step)
at real widths — 64-byte chunks, partitions of several MiB, 64K+ field
rows — for a *described* v5e that is not attached, and asserts that the
compiled program holds a Mosaic kernel (``tpu_custom_call``).  Nothing
runs, so these say nothing about results or speed; they catch, without a
chip, what the chip's compiler would refuse: an unlowered primitive, a
block that does not tile, a kernel that outgrows VMEM.

The topology is described inside a module fixture, never at import time,
so every test worker collects the same tests and only the worker given
this file loads the TPU compiler.  Where no topology can be described the
fixture skips the file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ParserConfig, Schema, make_csv_dfa
from repro.core import backends as backends_mod
from repro.core import stages as stages_mod
from repro.data import synth

CHUNK = 64                  # bytes per chunk, the default chunk_size
PARTITION = 4 << 20         # bytes per parse partition
ROWS = 1 << 16              # field rows per converted column


@pytest.fixture(scope="module")
def v5e():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(name, fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"{name}: temp_bytes={temp} "
          f"kernels={text.count('custom_call_target=\"tpu_custom_call\"')}")
    assert "tpu_custom_call" in text, name
    return text


def test_dfa_scan_chunk_vectors_lowers(v5e):
    from repro.kernels.dfa_scan import ops

    dfa = make_csv_dfa()
    _compile("chunk_vectors",
             lambda c: ops.chunk_vectors(c, dfa, interpret=False),
             _spec(v5e, (PARTITION // CHUNK, CHUNK), jnp.uint8))


def test_dfa_scan_replay_fused_lowers(v5e):
    from repro.kernels.dfa_scan import ops

    dfa = make_csv_dfa()
    c = PARTITION // CHUNK
    _compile("replay_fused",
             lambda ch, st: ops.replay_fused(ch, st, dfa, interpret=False),
             _spec(v5e, (c, CHUNK), jnp.uint8), _spec(v5e, (c,), jnp.int32))


def test_partition_tags_lowers(v5e):
    from repro.kernels.partition import ops

    n_cols = len(synth.TAXI_SCHEMA)
    _compile("partition_tags",
             lambda t: ops.partition_tags(t, n_cols, interpret=False),
             _spec(v5e, (PARTITION,), jnp.int32))


@pytest.mark.parametrize("dtype", ["int32", "float32", "date"])
def test_fused_converter_lowers_both_branches(v5e, dtype):
    """The windowed kernel and its per-row-window fallback sit in the two
    branches of one ``lax.cond``; both must lower."""
    from repro.kernels.numparse import ops

    fn = {"int32": ops.parse_int_column_fused,
          "float32": ops.parse_float_column_fused,
          "date": ops.parse_date_column_fused}[dtype]
    text = _compile(f"{dtype} fused column",
                    functools.partial(fn, interpret=False),
                    _spec(v5e, (PARTITION,), jnp.uint8),
                    _spec(v5e, (ROWS,), jnp.int32),
                    _spec(v5e, (ROWS,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') >= 2


def test_fused_converter_lowers_batched(v5e):
    """Under ``vmap`` (batched streams, the service) the converter keeps a
    branch: windowed kernels on every lane, the per-row kernel only on
    the branch taken when some lane does not fit.  It must lower."""
    from repro.kernels.numparse import ops

    lanes = 4
    text = _compile("int32 fused column, 4 lanes",
                    jax.vmap(functools.partial(ops.parse_int_column_fused,
                                               interpret=False)),
                    _spec(v5e, (lanes, PARTITION), jnp.uint8),
                    _spec(v5e, (lanes, ROWS), jnp.int32),
                    _spec(v5e, (lanes, ROWS), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') >= 2
    assert "conditional" in text


@pytest.fixture(scope="module")
def staged_step(v5e):
    """One whole staged ``execute_plan`` step on ``backend="pallas"``,
    planned as on a TPU, compiled once for the tests below: its plan and
    the compiled program's text."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backends_mod, "interpret_kernels", lambda: False)
        cfg = ParserConfig(dfa=make_csv_dfa(),
                           schema=Schema.of(*synth.TAXI_SCHEMA),
                           max_records=ROWS, backend="pallas")
        backend = backends_mod.get_backend("pallas")
        plan = stages_mod.plan_parse(cfg, backend)
        text = _compile(
            "staged pallas step",
            lambda ch, st: stages_mod.execute_plan(ch, plan, cfg, backend,
                                                   initial_state=st),
            _spec(v5e, (PARTITION // CHUNK, CHUNK), jnp.uint8),
            _spec(v5e, (), jnp.int32))
    return plan, text


def test_staged_pallas_step_lowers(staged_step):
    """The staged step compiles with its kernels, and "auto" resolves on a
    TPU to the payload-carrying sort partition."""
    plan, text = staged_step
    assert not plan.interpret
    assert plan.materialize.partition_impl == "sort"
    assert text.count('custom_call_target="tpu_custom_call"') >= 4


def _ops_under(text, scope):
    """``(opcode, result type)`` of every instruction of the compiled
    program whose ``op_name`` lies under the scope ``scope``, fused
    computations included."""
    ops = []
    for line in text.splitlines():
        if f'/{scope}/' not in line or "op_name=" not in line:
            continue
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z0-9-]*)\(", line)
        if m:
            ops.append((m.group(2), m.group(1)))
    return ops


def test_staged_step_partitions_by_sort(staged_step):
    """Under ``stage.gather`` the compiled step sorts the partition's
    symbols and tags into column order: one N-sized ``sort`` and no
    ``gather`` through a permutation."""
    _, text = staged_step
    ops = _ops_under(text, "stage.gather")
    assert any(op == "sort" and f"[{PARTITION}]" in ty for op, ty in ops), ops
    assert not [op for op, _ in ops if op == "gather"], ops


def test_megakernel_refused_on_tpu(monkeypatch):
    """``fuse_pipeline=True`` names the missing lowering on a TPU instead of
    resolving to the staged path or the interpreter."""
    monkeypatch.setattr(backends_mod, "interpret_kernels", lambda: False)
    with pytest.raises(ValueError, match="no Mosaic lowering"):
        ParserConfig(dfa=make_csv_dfa(), schema=Schema.of(("a", "int32")),
                     max_records=8, backend="pallas", fuse_pipeline=True)
    ref_cfg = ParserConfig(dfa=make_csv_dfa(), schema=Schema.of(("a", "int32")),
                           max_records=8, backend="reference",
                           fuse_pipeline=True)
    plan = stages_mod.plan_parse(ref_cfg, backends_mod.get_backend("reference"))
    assert plan.execute_path == "staged"
