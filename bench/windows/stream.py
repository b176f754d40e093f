"""The bulk-load window (traffic ``kind: stream``): one ``StreamSession``
fed an endless stream of a seeded block from host memory until the window
closes, every partition's typed columns kept on the device as a loaded
table.

``load_GBps`` is the source bytes the benchmark's own source handed to
``parse_streams`` (checked against ``StreamStats.bytes_in``) over the time
from the first take to ``block_until_ready`` on the last partition's
result.  The source reads ``read_bytes`` at a time, each read ending on a
record, and stops at the first read past a partition boundary once the
window's seconds are up, so every run ends on a full partition and a flush
of less than one record.  Nothing is copied to the host inside the window.
The delivered columns stay on the device up to ``keep_device_bytes``;
past that the oldest partition is handed off (released).  After the window
the first and the last partition kept, and ``sample_partitions`` more
drawn from the seed, are compared with the plain reference.
"""
from __future__ import annotations

import collections
import time

import jax
import numpy as np

from benchlib import check, oracle, program
from benchlib.stream import Repeated


def run(cell, seed: int, seconds: float, tracer, clock, control: bool = False):
    cfg, tr = cell.config, cell.traffic
    schema = program.schema_of(cfg)
    pb, rb = int(tr["partition_bytes"]), int(tr["read_bytes"])
    keep_limit = int(tr["keep_device_bytes"])
    block, rec_end, str_bytes = cell.block(seed)
    stream = Repeated(block, rec_end)
    sess = program.session(cfg, pb)

    # set-up: the step's one shape, a full take and the flush, compiled
    warm = list(sess.parse_streams([[stream.bytes(0, pb + pb // 2)]]))
    jax.block_until_ready([r for _s, r, _n in warm])
    del warm

    fed = 0
    t_stop = None

    def source():
        nonlocal fed
        while True:
            with tracer.span("feeding source"):
                end = stream.next_end((fed // rb + 1) * rb)
                piece = stream.bytes(fed, end)
                crossed = end // pb > fed // pb
                fed = end
            yield piece
            if crossed and time.perf_counter() >= t_stop:
                return

    counts, failed = [], 0
    kept = collections.OrderedDict()
    kept_bytes = released = 0
    last = None
    c0 = clock.events
    tracer.start()
    with tracer.span("window"):
        t_start = time.perf_counter()
        setup_end = t_start
        t_stop = t_start + seconds
        it = sess.parse_streams([source()])
        while True:
            with tracer.span("in parse_streams"):
                item = next(it, None)
            if item is None:
                break
            _s, res, n = item
            if isinstance(res, program.StreamOverflow):
                failed += 1
                counts.append(0)
                continue
            cols = check.columns(res, schema)
            size = check.nbytes(cols)
            kept[len(counts)] = (cols, size)
            kept_bytes += size
            while kept_bytes > keep_limit and len(kept) > 1:
                kept_bytes -= kept.popitem(last=False)[1][1]
                released += 1
            counts.append(n)
            last = res
        jax.block_until_ready(last)
        t_done = time.perf_counter()
    xplane = tracer.stop()
    compiles_in_window = clock.events - c0
    peak = program.peak_bytes()
    window = t_done - t_start
    stats = sess.call_stats[0]
    facts = dict(program.plan_facts(sess.parser), partitions=len(counts),
                 bytes_in=stats.bytes_in, bytes_fed=fed,
                 bytes_reparsed=stats.bytes_reparsed, kept_bytes=kept_bytes,
                 partitions_released=released,
                 compiles_in_window=compiles_in_window)

    # the comparison, once the window has closed
    held = sorted(kept)
    rest = held[1:-1]
    pick = np.random.default_rng([seed, 1]).choice(
        len(rest), min(len(rest), int(tr["sample_partitions"])), replace=False)
    compared = sorted({held[0], held[-1], *(rest[i] for i in pick)}) if held else []
    views = {k: check.host_view(kept[k][0]) for k in compared}
    kept = last = res = it = sess = item = None
    tally = check.Tally()
    tally.miss(abs(stats.bytes_in - fed), "bytes_in against bytes fed")
    tally.miss(abs(sum(counts) - stream.records_before(fed)), "records in the window")
    for k, n in enumerate(counts):
        end = fed if k == len(counts) - 1 else (k + 1) * pb
        want = stream.records_before(end) - stream.records_before(k * pb)
        tally.miss(abs(n - want), "records per partition")
    tally.miss(failed, "partitions failed")
    for k in compared:
        final = k == len(counts) - 1
        data = stream.partition(k, pb, fed, final)
        view = cell.control().view(data, schema) if control else views[k]
        tally.add(check.compare(view, oracle.parse(data), schema, final))
    facts["compared_partitions"] = ",".join(map(str, compared))
    readings = dict(
        source_bytes=fed,
        out_bytes=least_out_bytes(schema, sum(counts), str_bytes, stream.n),
        xplane=xplane)
    return dict(metrics={"load_GBps": fed / window / 1e9},
                attempted=len(counts), failed=failed, tally=tally,
                setup_end=setup_end, peak=peak, facts=facts,
                readings=readings)


def least_out_bytes(schema, records: int, str_bytes: int, block_records: int) -> float:
    """Typed columns written once: 4 bytes a value and a validity bit per
    field; a string column's 4-byte offsets and its bytes."""
    per_record = sum(4 + 1 / 8 for _ in schema)
    return records * per_record + records * str_bytes / block_records
