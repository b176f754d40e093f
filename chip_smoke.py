"""Chip smoke test: the staged pallas parse path, end to end, on a TPU.

    python chip_smoke.py              # one chip: one-shot, bulk load,
                                      # fallback, serve
    python chip_smoke.py --chips 4    # four chips: the mesh paths only

Phases (one chip):

* **one-shot** — one ``Parser.parse`` on ``backend="pallas"`` of seeded
  yelp-like CSV, sized from ``compiled.memory_analysis()``; the compiled
  step must hold Mosaic kernels, and ``to_arrow`` must equal the
  ``reference`` backend's bit for bit and Python's ``csv`` / ``int`` /
  ``float`` / ``datetime`` on the same bytes.
* **bulk load** — ``StreamSession`` over ≥ 1 GiB of yelp-like and 256 MiB
  of taxi-like CSV (one seeded block per format, repeated): every
  partition validates and the record counts add up; the first, middle and
  last partitions equal the ``reference`` backend bit for bit.
* **fallback** — taxi-like CSV with multi-KiB int, float and date fields
  (malformed input), so those columns' windows overflow and the
  converters take their per-row-window fallback: one ``Parser.parse`` and
  a 2-lane batched ``StreamSession`` (one lane clean, one malformed) equal
  the ``reference`` backend bit for bit, and the converters fed shuffled
  (out-of-order) offsets equal ``typeconv``.
* **serve** — ``ParseService`` with 4 tenants under one plan key: every
  tenant's results equal its solo parse bit for bit.

``--chips 4`` runs only the paths that exist across chips:
``DistributedParser`` on a 4-device mesh over yelp text (each chip's own
columns, and ``assemble``'s table) against single-device
``Parser.to_arrow``, and a 4-lane ``StreamSession(mesh=...)`` against the
unmeshed batched session.

Every phase raises on a fault.  Each prints one ``smoke-reading`` line
(sizes, compile and wall seconds, the resolved path, peak device memory):
readings of this smoke run, not benchmark metrics.  The last line is the
JSON result; it is printed only when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import csv
import datetime
import io
import itertools
import json
import os
import sys
import threading
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import compile_cache  # noqa: E402
from repro.core import Parser, ParserConfig, Schema, make_csv_dfa  # noqa: E402
from repro.core.dfa import PAD_BYTE  # noqa: E402
from repro.data import synth  # noqa: E402

MiB = 1 << 20
SEED = 0
#: Device bytes the reference backend's step may take, as a share of HBM.
HBM_SHARE = 0.6
#: Reference-backend temp bytes per input byte (ROADMAP S3), the prior
#: that picks the one-shot size before memory_analysis confirms it.
REF_TEMP_PER_BYTE = 1100


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------

class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching from
    the persistent cache), from its own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


CLOCK = None


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _reading(phase: str, t0: float, c0: float, **facts) -> None:
    facts.update(compile_s=round(CLOCK.seconds - c0, 3),
                 wall_s=round(time.perf_counter() - t0, 3),
                 peak_bytes_in_use=_peak_bytes())
    print(f"smoke-reading {phase}: "
          + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def _plan_facts(parser: Parser) -> dict:
    return dict(partition_impl=parser.plan.materialize.partition_impl,
                execute_path=parser.plan.execute_path,
                interpret=parser.plan.interpret)


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def _same_tree(a, b, label: str) -> None:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        raise AssertionError(f"{label}: result structure differs")
    for i, (x, y) in enumerate(zip(la, lb)):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype or not np.array_equal(
                x.reshape(-1).view(np.uint8), y.reshape(-1).view(np.uint8)):
            raise AssertionError(f"{label}: leaf {i} differs")


def _same_arrow(a: dict, b: dict, label: str) -> None:
    if a.keys() != b.keys():
        raise AssertionError(f"{label}: columns differ")
    for col in a:
        _same_tree(a[col], b[col], f"{label}[{col}]")


def _check_python(arrow: dict, schema, data: bytes) -> int:
    """``arrow`` against Python's csv/int/float/datetime on ``data``: ints,
    strings and dates exactly, floats to float32 rounding (rtol 2e-5, the
    tolerance the repo's differential tests hold the parser to)."""
    rows = list(csv.reader(io.StringIO(data.decode(), newline="")))
    epoch = datetime.datetime(1970, 1, 1)
    for c, (name, dtype) in enumerate(schema):
        col = arrow[name]
        valid = np.unpackbits(col["validity"], bitorder="little")[:len(rows)]
        if not valid.all():
            raise AssertionError(f"python check: {name} has invalid fields")
        want = [r[c] for r in rows]
        if dtype == "str":
            off, raw = col["offsets"], col["data"].tobytes()
            got = [raw[off[i]:off[i + 1]].decode() for i in range(len(rows))]
            ok = got == want
        elif dtype == "int32":
            ok = np.array_equal(col["values"], np.array(want, np.int64))
        elif dtype == "float32":
            ok = np.allclose(col["values"], np.array(want, np.float32),
                             rtol=2e-5, atol=0)
        else:
            secs = [int((datetime.datetime.fromisoformat(s) - epoch)
                        .total_seconds()) for s in want]
            ok = np.array_equal(col["values"], np.array(secs, np.int64))
        if not ok:
            raise AssertionError(f"python check: column {name} differs")
    return len(rows)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _cfg(schema, max_records: int, backend: str) -> ParserConfig:
    return ParserConfig(dfa=make_csv_dfa(), schema=Schema.of(*schema),
                        max_records=max_records, backend=backend,
                        validate_columns=True)


def _yelp(nbytes: int, seed: int = SEED) -> bytes:
    """Seeded yelp-like CSV of about ``nbytes`` bytes (whole records)."""
    rng = np.random.default_rng(seed)
    return synth.yelp_like(rng, max(1, nbytes // 720))


def _taxi(nbytes: int, seed: int = SEED) -> bytes:
    rng = np.random.default_rng(seed)
    return synth.taxi_like(rng, max(1, nbytes // 88))


def _max_records(nbytes: int, min_record: int) -> int:
    return 1 << max(4, int(np.ceil(np.log2(nbytes / min_record + 1))))


def one_shot(hbm_bytes: int, nbytes: int = 0) -> None:
    """One ``Parser.parse`` on the pallas backend vs the reference and
    Python.  ``nbytes=0`` sizes the input from the device's memory."""
    t0, c0 = time.perf_counter(), CLOCK.seconds
    if not nbytes:
        nbytes = 1 << int(np.log2(HBM_SHARE * hbm_bytes / REF_TEMP_PER_BYTE))
    data = _yelp(nbytes)
    mr = _max_records(len(data), 400)
    pal = Parser(_cfg(synth.YELP_SCHEMA, mr, "pallas"))
    ref = Parser(_cfg(synth.YELP_SCHEMA, mr, "reference"))
    chunks = jax.device_put(pal.prepare(data))
    st = jax.numpy.int32(pal.cfg.dfa.start_state)
    temp, steps = {}, {}
    for name, p in (("pallas", pal), ("reference", ref)):
        compiled = steps[name] = p._jit.lower(chunks, st).compile()
        mem = compiled.memory_analysis()
        temp[name] = int(mem.temp_size_in_bytes)
        need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes)
        if need > hbm_bytes:
            raise AssertionError(f"one-shot: the {name} step needs {need} "
                                 f"bytes, more than the device's {hbm_bytes}")
        if name == "pallas" and "tpu_custom_call" not in compiled.as_text():
            raise AssertionError("one-shot: no Mosaic kernel in the pallas step")
    got = steps["pallas"](chunks, st)
    want = steps["reference"](chunks, st)
    if not bool(got.validation.ok):
        raise AssertionError("one-shot: validation failed")
    arrow = pal.to_arrow(got)
    _same_arrow(arrow, ref.to_arrow(want), "one-shot pallas vs reference")
    n = _check_python(arrow, synth.YELP_SCHEMA, data)
    if int(got.validation.n_records) != n:
        raise AssertionError("one-shot: record count differs from csv")
    _reading("one-shot", t0, c0, bytes=len(data), partitions=1, records=n,
             pallas_temp_bytes=temp["pallas"],
             pallas_temp_per_byte=round(temp["pallas"] / len(data), 1),
             reference_temp_bytes=temp["reference"], **_plan_facts(pal))


def _ref_partition(ref: Parser, capacity: int, buf: bytes, flush: bool):
    """The reference parse of one session partition's assembled buffer."""
    k = ref.cfg.chunk_size
    host = np.full(capacity, PAD_BYTE, np.uint8)
    host[:len(buf)] = np.frombuffer(buf, np.uint8)
    if flush and buf and buf[-1] != ref.cfg.record_delim_byte:
        host[len(buf)] = ref.cfg.record_delim_byte
    return ref.parse_chunks(host.reshape(-1, k))


def bulk_load(kind: str, total_bytes: int, block_bytes: int,
              partition_bytes: int) -> None:
    """Stream ``total_bytes`` of one repeated seeded block through a
    ``StreamSession``; validate every partition, compare three."""
    from repro.core.streaming import StreamSession

    t0, c0 = time.perf_counter(), CLOCK.seconds
    schema, gen, min_rec = {
        "yelp": (synth.YELP_SCHEMA, _yelp, 100),
        "taxi": (synth.TAXI_SCHEMA, _taxi, 60)}[kind]
    block = gen(block_bytes)
    reps = -(-total_bytes // len(block))
    records_per_block = block.count(b"\n") if kind == "taxi" else \
        sum(1 for _ in csv.reader(io.StringIO(block.decode(), newline="")))
    max_carry = 64 << 10
    mr = _max_records(partition_bytes + max_carry, min_rec)
    pal = Parser(_cfg(schema, mr, "pallas"))
    ref = Parser(_cfg(schema, mr, "reference"))
    sess = StreamSession(pal, partition_bytes, max_carry_bytes=max_carry)
    stream = memoryview(block)
    total = reps * len(block)
    # one take per partition_bytes, then the flush take (empty when the
    # stream ends on a partition boundary)
    n_parts = total // partition_bytes + 1
    check = {0, n_parts // 2, n_parts - 1}

    def buf_of(start: int, end: int) -> bytes:
        # bytes [start, end) of the repeated stream
        out, pos = [], start
        while pos < end:
            off = pos % len(block)
            take = min(end - pos, len(block) - off)
            out.append(bytes(stream[off:off + take]))
            pos += take
        return b"".join(out)

    records, parts, carry_start = 0, 0, 0
    for _s, res, n_complete in sess.parse_streams(
            [itertools.repeat(block, reps)]):
        if not isinstance(n_complete, int) or not hasattr(res, "validation"):
            raise AssertionError(f"bulk {kind}: partition {parts} failed: {res}")
        if not bool(res.validation.no_invalid):
            raise AssertionError(f"bulk {kind}: partition {parts} hit the "
                                 f"invalid state")
        rec_ok = np.asarray(res.validation.record_ok)[:n_complete]
        if not rec_ok.all():
            raise AssertionError(f"bulk {kind}: partition {parts} has "
                                 f"malformed records")
        consumed = min((parts + 1) * partition_bytes, total)
        flush = parts == n_parts - 1
        if flush and not bool(res.validation.ok):
            raise AssertionError(f"bulk {kind}: final partition not ok")
        if parts in check:
            want = _ref_partition(ref, sess.capacity,
                                  buf_of(carry_start, consumed), flush)
            _same_tree(res, want, f"bulk {kind} partition {parts}")
        carry_start += int(res.last_record_end) + 1
        records += n_complete
        parts += 1
    if parts != n_parts:
        raise AssertionError(f"bulk {kind}: {parts} partitions, "
                             f"expected {n_parts}")
    if records != reps * records_per_block:
        raise AssertionError(f"bulk {kind}: {records} records, expected "
                             f"{reps * records_per_block}")
    _reading(f"bulk-{kind}", t0, c0, bytes=total, partitions=parts,
             records=records, partition_bytes=partition_bytes,
             compared_partitions=sorted(check), **_plan_facts(pal))


#: Taxi columns given a mega-field by :func:`_malformed`, one per
#: converter dtype, and the bytes of each mega-field: more than any
#: window tile, so the column cannot take the windowed kernel.
MEGA_COLUMNS = ("pu_loc", "distance", "pickup")
MEGA_BYTES = 20_000


def _malformed(data: bytes) -> bytes:
    """``data`` (taxi-like) with one record's field in each of
    :data:`MEGA_COLUMNS` replaced by :data:`MEGA_BYTES` digits."""
    names = [name for name, _ in synth.TAXI_SCHEMA]
    lines = data.split(b"\n")
    for k, col in enumerate(MEGA_COLUMNS):
        i = (k + 1) * len(lines) // (len(MEGA_COLUMNS) + 2)
        fields = lines[i].split(b",")
        fields[names.index(col)] = b"7" * MEGA_BYTES
        lines[i] = b",".join(fields)
    return b"\n".join(lines)


def fallback(nbytes: int, partition_bytes: int) -> None:
    """The converters' per-row-window fallback on malformed input and on
    out-of-order offsets, against the reference backend and typeconv."""
    from repro.core import typeconv
    from repro.core.streaming import StreamSession
    from repro.kernels.numparse import ops as numparse_ops

    t0, c0 = time.perf_counter(), CLOCK.seconds
    clean = _taxi(nbytes)
    data = _malformed(clean)
    mr = _max_records(len(data), 60)
    pal = Parser(_cfg(synth.TAXI_SCHEMA, mr, "pallas"))
    ref = Parser(_cfg(synth.TAXI_SCHEMA, mr, "reference"))
    got = pal.parse(data)
    _same_arrow(pal.to_arrow(got), ref.to_arrow(ref.parse(data)),
                "fallback pallas vs reference")
    names = [name for name, _ in synth.TAXI_SCHEMA]
    widths = {"int32": pal.cfg.int_width, "float32": pal.cfg.float_width,
              "date": numparse_ops.numparse.DATE_WIDTH}
    n = int(got.validation.n_records)
    for name, dtype in synth.TAXI_SCHEMA:
        c = names.index(name)
        per_row = bool(numparse_ops.takes_per_row_fallback(
            got.css.shape[0], got.field_offset[c], got.field_length[c],
            widths[dtype]))
        if per_row != (name in MEGA_COLUMNS):
            raise AssertionError(f"fallback: column {name} per-row={per_row}")

    # out-of-order offsets: the converters against typeconv
    convert = {"int32": (numparse_ops.parse_int_column_fused, typeconv.parse_int),
               "float32": (numparse_ops.parse_float_column_fused,
                           typeconv.parse_float),
               "date": (numparse_ops.parse_date_column_fused,
                        typeconv.parse_date)}
    perm = np.random.default_rng(SEED).permutation(n)
    for name in ("do_loc", "fare", "dropoff"):
        c = names.index(name)
        dtype = dict(synth.TAXI_SCHEMA)[name]
        kernel, oracle = convert[dtype]
        kw = {} if dtype == "date" else {"width": widths[dtype]}
        off = got.field_offset[c][:n][perm]
        ln = got.field_length[c][:n][perm]
        if not bool(numparse_ops.takes_per_row_fallback(
                got.css.shape[0], off, ln, widths[dtype])):
            raise AssertionError(f"fallback: shuffled {name} fits windows")
        a = kernel(got.css, off, ln, interpret=pal.plan.interpret, **kw)
        b = oracle(got.css, off, ln, **kw)
        valid = np.asarray(b.valid)
        if not (np.array_equal(np.asarray(a.valid), valid)
                and np.array_equal(np.asarray(a.empty), np.asarray(b.empty))
                and np.array_equal(np.asarray(a.value)[valid],
                                   np.asarray(b.value)[valid])):
            raise AssertionError(f"fallback: shuffled {name} differs")

    # a batch mixing a clean lane with a malformed one
    max_carry = 64 << 10
    smr = _max_records(partition_bytes + max_carry, 60)
    sessions = [StreamSession(Parser(_cfg(synth.TAXI_SCHEMA, smr, backend)),
                              partition_bytes, max_carry_bytes=max_carry,
                              n_streams=2)
                for backend in ("pallas", "reference")]
    lanes = [[clean], [data]]
    a, b = (list(sess.parse_streams(lanes)) for sess in sessions)
    if len(a) != len(b):
        raise AssertionError("fallback stream: partition count differs")
    for i, ((s1, r1, n1), (s2, r2, n2)) in enumerate(zip(a, b)):
        if (s1, n1) != (s2, n2):
            raise AssertionError(f"fallback stream: partition {i} differs")
        _same_tree(r1, r2, f"fallback stream partition {i}")
    _reading("fallback", t0, c0, bytes=len(data), records=n,
             per_row_columns=",".join(MEGA_COLUMNS),
             shuffled_columns="do_loc,fare,dropoff",
             stream_bytes=len(clean) + len(data), stream_partitions=len(a),
             **_plan_facts(pal))


def serve(tenant_bytes: int, partition_bytes: int, n_tenants: int = 4) -> None:
    """``ParseService``: tenants of one plan key vs their solo parses."""
    from repro.core.streaming import StreamSession
    from repro.serve import ParseService, TenantResult

    t0, c0 = time.perf_counter(), CLOCK.seconds
    max_carry = 64 << 10
    mr = _max_records(partition_bytes + max_carry, 100)
    cfg = _cfg(synth.YELP_SCHEMA, mr, "pallas")
    sources = [_yelp(tenant_bytes, seed=SEED + 1 + i) for i in range(n_tenants)]
    out = {}

    def drain(tenant):
        out[tenant.name] = list(tenant.results())

    with ParseService(tiers=(n_tenants,)) as svc:
        tenants = [svc.submit(cfg, [src], partition_bytes=partition_bytes,
                              max_carry_bytes=max_carry, name=f"t{i}")
                   for i, src in enumerate(sources)]
        threads = [threading.Thread(target=drain, args=(t,)) for t in tenants]
        for th in threads:
            th.start()
        for t in tenants:
            t.wait(timeout=900)
        for th in threads:
            th.join(timeout=60)
    if svc.registry.parser_builds != 1:
        raise AssertionError(f"serve: {svc.registry.parser_builds} parsers "
                             f"built for one plan key")
    solo = StreamSession(Parser(cfg), partition_bytes, max_carry_bytes=max_carry)
    records = parts = 0
    for t, src in zip(tenants, sources):
        items = out[t.name]
        bad = [i for i in items if not isinstance(i, TenantResult)]
        if bad:
            raise AssertionError(f"serve: {t.name} got {bad[0]!r}")
        want = [(r, n) for _s, r, n in solo.parse_streams([[src]])]
        if len(items) != len(want):
            raise AssertionError(f"serve: {t.name} partition count differs")
        for i, (item, (r, n)) in enumerate(zip(items, want)):
            if item.n_records != n:
                raise AssertionError(f"serve: {t.name} partition {i} records")
            _same_tree(item.result, r, f"serve {t.name} partition {i}")
        records += sum(i.n_records for i in items)
        parts += len(items)
    _reading("serve", t0, c0, bytes=sum(map(len, sources)), partitions=parts,
             records=records, tenants=n_tenants, **_plan_facts(solo.parser))


def _same_shards(sharded, want: dict, cfg: ParserConfig, n_devices: int) -> None:
    """Each chip's device columns hold its records whole: chip ``d``'s
    records ``rec_base[d]`` on, field by field, against the single-device
    table ``want`` (``Parser.to_arrow``)."""
    if bool(sharded.overflow):
        raise AssertionError("mesh: a chip's tail overflowed")
    m, n_cols = cfg.max_records, len(cfg.schema.columns)
    base = np.asarray(sharded.rec_base).reshape(n_devices)
    ends = np.append(base[1:], int(sharded.n_records))
    css = np.asarray(sharded.css).reshape(n_devices, -1)
    off = np.asarray(sharded.field_offset).reshape(n_devices, n_cols, m)
    ln = np.asarray(sharded.field_length).reshape(n_devices, n_cols, m)
    for c, col in enumerate(cfg.schema.columns):
        ref = want[col.name]
        valid = np.unpackbits(ref["validity"], bitorder="little")
        if col.dtype != "str":
            value = np.asarray(sharded.values[col.name].value).reshape(n_devices, m)
            ok = np.asarray(sharded.values[col.name].valid).reshape(n_devices, m)
        for d in range(n_devices):
            a, b = int(base[d]), int(ends[d])
            if col.dtype == "str":
                o = ref["offsets"]
                for j in range(b - a):
                    got = css[d, off[d, c, j]:off[d, c, j] + ln[d, c, j]]
                    if got.tobytes() != ref["data"][o[a + j]:o[a + j + 1]].tobytes():
                        raise AssertionError(
                            f"mesh: chip {d} record {a + j} {col.name} differs")
            elif (not np.array_equal(value[d, :b - a], ref["values"][a:b])
                  or not np.array_equal(ok[d, :b - a], valid[a:b].astype(bool))):
                raise AssertionError(f"mesh: chip {d} {col.name} differs")


def mesh_phase(n_devices: int, table_bytes: int = 8 * MiB,
               lane_bytes: int = 4 * MiB, partition_bytes: int = MiB) -> None:
    """The paths that exist only across chips, each against its
    single-device twin."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.distributed import DistributedParser
    from repro.core.streaming import StreamSession

    devices = jax.devices()[:n_devices]
    if len(devices) != n_devices:
        raise AssertionError(f"--chips {n_devices}: JAX sees {len(devices)}")

    # DistributedParser vs single-device Parser.to_arrow, on yelp text whose
    # quoted fields the chips' cuts land in: each chip's own columns, and
    # assemble's table
    t0, c0 = time.perf_counter(), CLOCK.seconds
    data = _yelp(table_bytes)
    cfg = _cfg(synth.YELP_SCHEMA, _max_records(len(data), 100), "pallas")
    mesh = Mesh(np.array(devices), ("data",))
    dp = DistributedParser(cfg, mesh)
    chunks = jax.device_put(dp.prepare(data), NamedSharding(mesh, PartitionSpec("data")))
    holders = {s.device for s in chunks.addressable_shards if s.data.size}
    if len(holders) != n_devices:
        raise AssertionError(f"mesh: input on {len(holders)} devices")
    sharded = dp.parse_chunks(chunks)
    single = Parser(_cfg(synth.YELP_SCHEMA, cfg.max_records, "pallas"))
    want = single.to_arrow(single.parse_chunks(single.prepare(data)))
    _same_shards(sharded, want, cfg, n_devices)
    _same_arrow(dp.assemble(sharded), want, "distributed vs single-device")
    _reading("distributed", t0, c0, bytes=len(data), devices=n_devices,
             devices_holding_input=len(holders),
             halo_bytes=np.asarray(sharded.halo_bytes).tolist(),
             **_plan_facts(single))

    # 4-lane StreamSession over a mesh vs the unmeshed batched session
    t0, c0 = time.perf_counter(), CLOCK.seconds
    srcs = [_yelp(lane_bytes, seed=SEED + 10 + i) for i in range(n_devices)]
    pb, mc = partition_bytes, 64 << 10
    yc = _cfg(synth.YELP_SCHEMA, _max_records(pb + mc, 100), "pallas")
    lanes = StreamSession(Parser(yc), pb, max_carry_bytes=mc,
                          n_streams=n_devices,
                          mesh=Mesh(np.array(devices), ("streams",)))
    flat = StreamSession(Parser(yc), pb, max_carry_bytes=mc,
                         n_streams=n_devices)
    got = list(lanes.parse_streams([[s] for s in srcs]))
    want = list(flat.parse_streams([[s] for s in srcs]))
    if len(got) != len(want):
        raise AssertionError("mesh stream: partition count differs")
    for i, ((s1, r1, n1), (s2, r2, n2)) in enumerate(zip(got, want)):
        if (s1, n1) != (s2, n2):
            raise AssertionError(f"mesh stream: partition {i} differs")
        _same_tree(r1, r2, f"mesh stream partition {i}")
    _reading("mesh-stream", t0, c0, bytes=sum(map(len, srcs)),
             partitions=len(got), lanes=n_devices,
             records=sum(n for _s, _r, n in got), **_plan_facts(lanes.parser))


def main(argv=None) -> int:
    global CLOCK
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    CLOCK = _CompileClock()
    print(f"smoke-reading setup: device_kind={dev.device_kind} "
          f"devices={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)
    if args.chips == 4:
        mesh_phase(4)
    else:
        hbm = int((dev.memory_stats() or {}).get("bytes_limit", 16 * 10**9))
        one_shot(hbm)
        bulk_load("yelp", 1 << 30, 8 * MiB, 4 * MiB)
        bulk_load("taxi", 256 * MiB, 4 * MiB, 4 * MiB)
        fallback(2 * MiB, 512 << 10)
        serve(4 * MiB, 1 * MiB)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
