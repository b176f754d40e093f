"""End-to-end streaming parse (paper §4.4 analogue): partitions flow through
the device-resident ``StreamSession`` engine — the carry-over lives on the
device, results are fetched one partition behind dispatch, and with
``--streams S`` S independent sources parse batched in one dispatch per
round (per-stream carry state, bit-identical to S sequential runs).

    PYTHONPATH=src python examples/streaming_parse.py [--records 20000]
        [--backend pallas] [--streams 4]

``--backend pallas`` streams every partition through the Pallas kernel path
(DFA-scan, radix partition and fused gather+convert kernels; interpret mode
on CPU hosts, so expect it slower here — the point is exercising the kernel
pipeline end to end, bit-identically to the reference).
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro import compile_cache
from repro.core import Parser, ParserConfig, Schema, available_backends, make_csv_dfa
from repro.core.streaming import StreamSession
from repro.data import synth


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=20000,
                    help="yelp-like records per stream")
    ap.add_argument("--partition-kib", type=int, default=512)
    ap.add_argument("--streams", type=int, default=1,
                    help="independent sources batched per dispatch")
    ap.add_argument("--backend", default="reference",
                    choices=available_backends())
    args = ap.parse_args()

    datas = []
    for s in range(args.streams):
        rng = np.random.default_rng(s)
        datas.append(synth.yelp_like(rng, args.records))
    total_bytes = sum(len(d) for d in datas)
    print(f"dataset: {args.streams} stream(s) x {len(datas[0])/1e6:.1f} MB "
          f"({args.records} yelp-like records each, quoted text with "
          f"embedded delimiters)")
    print(f"backend: {args.backend}")

    parser = Parser(ParserConfig(
        dfa=make_csv_dfa(), schema=Schema.of(*synth.YELP_SCHEMA),
        max_records=1 << 14, chunk_size=64, backend=args.backend,
        # pin the radix partition kernel so the example (and the CI smoke
        # job) exercises it — interpret-mode "auto" picks the jnp pass
        partition_impl="kernel" if args.backend == "pallas" else "auto",
    ))
    sess = StreamSession(parser, args.partition_kib * 1024,
                         max_carry_bytes=1 << 16, n_streams=args.streams)

    def source(data):
        for i in range(0, len(data), 1 << 20):
            yield data[i : i + (1 << 20)]

    t0 = time.perf_counter()
    stars_sum = 0
    n = 0
    for _stream, result, n_complete in sess.parse_streams([source(d) for d in datas]):
        stars = np.asarray(result.values["stars"].value[:n_complete])
        stars_sum += int(stars.sum())
        n += n_complete
    dt = time.perf_counter() - t0

    st = sess.stats[0]
    print(f"parsed {n} records in {dt:.3f}s "
          f"({total_bytes/dt/1e6:.1f} MB/s on this CPU host)")
    print(f"stream 0: partitions {st.partitions}, max carry-over {st.max_carry} B, "
          f"bytes re-parsed {st.bytes_reparsed} "
          f"({st.bytes_reparsed/max(st.bytes_in,1)*100:.2f}% of input)")
    print(f"mean stars: {stars_sum/n:.3f}")


if __name__ == "__main__":
    main()
