"""The one-shot mesh load window (traffic ``kind: oneshot``): one
``DistributedParser`` over the cell's chips loads a seeded table, piece by
piece, until the window closes; every call's typed columns stay on the
chips as the loaded table.

The table is the seeded ``Repeated`` stream, cut into consecutive pieces
that each end at the last record end at or before ``piece_bytes``, padded
with PAD to exactly ``piece_bytes`` (one shape, compiled in set-up).  Each
piece is one call: ``prepare``, ``put`` and dispatch of piece ``k+1``
before the wait on piece ``k``, so at most ``in_flight`` (2) calls are in
flight, as a loader overlapping transfer with parse would run them.  The
chips split every piece into equal byte ranges with no record-start pass.

``load_GBps`` is the source bytes handed to ``prepare`` over the time from
the first ``prepare`` to ``block_until_ready`` on the last call.  Nothing
is copied to the host inside the window; the calls' counters
(``halo_bytes``, ``overflow``, ``rec_base``) are read after it.  The
columns stay on the chips up to ``keep_device_bytes`` a chip; past that
the oldest call is handed off (released).

After the window, ``correct`` holds every call's record count per chip and
in all against the generator's record ends, and its overflow flag at 0;
on the first and the last call kept and ``sample_calls`` more drawn from
the seed, every boundary record (the first record each chip but the first
owns) and its two neighbours, and one seeded record-aligned
``slice_bytes`` slice of every chip, field by field against the plain
reference, from each chip's own columns.
"""
from __future__ import annotations

import collections
import time

import jax
import numpy as np
from jax.sharding import Mesh

from benchlib import check, oracle, program
from benchlib.stream import Repeated

#: The program's host spans of a call (``repro.core.distributed``), kept
#: from the trace for ``mesh_host_ms_per_GB``.
MESH_SPANS = ("mesh.prepare", "mesh.put", "mesh.dispatch")


def parser(cell):
    """The cell's ``DistributedParser``; a program whose shards do not hold
    their records whole cannot run this window."""
    from repro.core import distributed

    if "overflow" not in distributed.ShardedParse._fields:
        raise RuntimeError("this program's DistributedParser leaves records "
                           "cut at a chip's bytes incomplete there")
    cfg, chips = cell.config, cell.chips
    shard = int(cell.traffic["piece_bytes"]) // chips
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    return distributed.DistributedParser(
        program.parser_config(cfg, shard), mesh,
        max_carry_bytes=cfg["parser"]["max_carry_bytes"])


def peak_bytes(chips: int) -> int:
    """The fullest chip's peak bytes in use."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in jax.devices()[:chips])


def run(cell, seed: int, seconds: float, tracer, clock, control: bool = False):
    cfg, tr = cell.config, cell.traffic
    schema = program.schema_of(cfg)
    chips = cell.chips
    piece = int(tr["piece_bytes"])
    shard = piece // chips
    in_flight = int(tr["in_flight"])
    keep_limit = int(tr["keep_device_bytes"]) * chips
    dp = parser(cell)
    block, rec_end, str_bytes = cell.block(seed)
    stream = Repeated(block, rec_end)

    def piece_end(start: int) -> int:
        return stream.record_start(stream.records_before(start + piece))

    # set-up: the one shape, compiled and run once
    jax.block_until_ready(dp.load(stream.bytes(0, piece_end(0)), pad_to=piece))

    ends = [0]                 # the stream position each piece ends at
    counters = {}              # call -> its counters, read after the window
    pending = collections.deque()
    kept = collections.OrderedDict()
    kept_bytes = released = 0
    tracer.names.update(MESH_SPANS)
    data = stream.bytes(0, piece_end(0))
    c0 = clock.events
    tracer.start()
    with tracer.span("window"):
        t_start = time.perf_counter()
        t_stop = t_start + seconds
        while True:
            res = dp.load(data, pad_to=piece)
            k = len(ends) - 1
            ends.append(ends[-1] + len(data))
            counters[k] = (res.rec_base, res.n_records, res.halo_bytes,
                           res.overflow)
            cols = check.columns(res, schema)
            kept[k] = (cols, check.nbytes(cols))
            kept_bytes += kept[k][1]
            while kept_bytes > keep_limit and len(kept) > 1:
                kept_bytes -= kept.popitem(last=False)[1][1]
                released += 1
            pending.append(res)
            if len(pending) >= in_flight:
                with tracer.span("wait"):
                    jax.block_until_ready(pending.popleft())
                if time.perf_counter() >= t_stop:
                    break
            with tracer.span("source"):
                data = stream.bytes(ends[-1], piece_end(ends[-1]))
        with tracer.span("wait"):
            jax.block_until_ready(list(pending))
        t_done = time.perf_counter()
    xplane = tracer.stop()
    compiles_in_window = clock.events - c0
    peak = peak_bytes(chips)
    window = t_done - t_start
    fed = ends[-1]
    calls = len(ends) - 1
    pending = res = cols = data = None

    # the comparison, once the window has closed
    counters = jax.device_get(counters)
    tally = check.Tally()
    halo, overflowed = [], 0
    for k, (rec_base, n_records, halo_bytes, overflow) in sorted(counters.items()):
        a, b = ends[k], ends[k + 1]
        got = np.diff(np.append(np.asarray(rec_base).reshape(chips), n_records))
        want = [stream.records_before(a + min((d + 1) * shard, b - a))
                - stream.records_before(a + d * shard) for d in range(chips)]
        tally.miss(np.sum(np.abs(got - want)), "records per chip")
        tally.miss(abs(int(n_records) - (stream.records_before(b)
                                         - stream.records_before(a))),
                   "records per call")
        overflowed += int(bool(overflow))
        halo.append(np.asarray(halo_bytes).reshape(chips))
    tally.miss(overflowed, "calls whose tail overflowed")

    held = sorted(kept)
    rest = held[1:-1]
    pick = np.random.default_rng([seed, 1]).choice(
        len(rest), min(len(rest), int(tr["sample_calls"])), replace=False)
    compared = sorted({held[0], held[-1], *(rest[i] for i in pick)})
    rng = np.random.default_rng([seed, 2])
    for k in compared:
        view = check.host_view(kept[k][0])
        tally.add(compare_call(cell, stream, view, counters[k][0], ends[k],
                               ends[k + 1], shard, int(tr["slice_bytes"]),
                               rng, control))
    halo = np.stack(halo)
    facts = dict(program.plan_facts(dp), calls=calls, bytes_fed=fed,
                 kept_bytes=kept_bytes, calls_released=released,
                 halo_bytes_max=int(halo.max()),
                 halo_bytes_mean=float(halo[:, :-1].mean()),
                 compared_calls=",".join(map(str, compared)),
                 compiles_in_window=compiles_in_window)
    readings = dict(
        source_bytes=fed,
        out_bytes=least_out_bytes(schema, stream.records_before(fed),
                                  str_bytes, stream.n),
        xplane=xplane)
    return dict(metrics={"load_GBps": fed / window / 1e9},
                attempted=calls, failed=overflowed, tally=tally,
                setup_end=t_start,
                peak=peak, facts=facts, readings=readings)


def shard_view(view: dict, d: int, chips: int, a: int, b: int) -> dict:
    """Chip ``d``'s columns, records ``a`` up to ``b`` of its own."""
    def mine(x, rows=None):
        x = np.asarray(x)
        x = x.reshape((chips, -1) + x.shape[1:]) if rows is None else \
            x.reshape(chips, rows, -1)
        return x[d]

    ncols = view["off"].shape[0] // chips
    recs = slice(a, b)
    return dict(
        css=mine(view["css"]), off=mine(view["off"], ncols)[:, recs],
        len=mine(view["len"], ncols)[:, recs], n=b - a,
        record_ok=mine(view["record_ok"])[recs], no_invalid=view["no_invalid"],
        ok=view["ok"],
        values={name: tuple(mine(x)[recs] for x in v)
                for name, v in view["values"].items()})


def compare_call(cell, stream, view, rec_base, a, b, shard, slice_bytes, rng,
                 control: bool) -> check.Tally:
    """One call's boundary records and one slice of every chip, from each
    chip's own columns, against the reference."""
    schema = program.schema_of(cell.config)
    chips = cell.chips
    rb = np.asarray(rec_base).reshape(chips).astype(np.int64)
    j0 = stream.records_before(a)
    n = stream.records_before(b) - j0
    counts = np.diff(np.append(rb, n))
    t = check.Tally()

    def held(d: int, lo: int, hi: int):
        # records lo..hi of the call, which chip d owns
        lo, hi = max(lo, rb[d]), min(hi, rb[d] + counts[d])
        if hi <= lo:
            return
        data = stream.bytes(stream.record_start(j0 + lo),
                            stream.record_start(j0 + hi))
        got = (cell.control().view(data, schema) if control
               else shard_view(view, d, chips, lo - rb[d], hi - rb[d]))
        t.add(check.compare(got, oracle.parse(data), schema, False))

    for d in range(1, chips):
        # the first record chip d owns and its neighbours on either side
        held(d - 1, rb[d] - 1, rb[d])
        held(d, rb[d], rb[d] + 2)
    for d in range(chips):
        lo_byte = a + d * shard
        hi_byte = min(a + (d + 1) * shard, b)
        off = int(rng.integers(0, max(hi_byte - lo_byte - slice_bytes, 0) + 1))
        held(d, stream.records_before(lo_byte + off) - j0,
             stream.records_before(min(lo_byte + off + slice_bytes, hi_byte)) - j0)
    return t


def least_out_bytes(schema, records: int, str_bytes: int, block_records: int) -> float:
    """Typed columns written once: 4 bytes a value and a validity bit per
    field; a string column's 4-byte offsets and its bytes (as the stream
    window reckons them)."""
    per_record = sum(4 + 1 / 8 for _ in schema)
    return records * per_record + records * str_bytes / block_records
