"""Quickstart: parse RFC4180 CSV (quotes, embedded delimiters, comments)
on-device with ParPaRaw and read back Arrow-layout columns.

    PYTHONPATH=src python examples/quickstart.py [--backend pallas]

``--backend pallas`` runs the Pallas kernel path (DFA-scan, radix partition
and windowed fused gather+convert kernels, in interpret mode on CPU hosts)
instead of the jnp reference — the outputs are bit-identical.  See the
top-level README.md for the backend matrix and docs/ARCHITECTURE.md for the
paper→module map.
"""
import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from repro import compile_cache
from repro.core import Parser, ParserConfig, Schema, available_backends, make_csv_dfa

CSV = (
    b'# inventory export 2026-07-15\n'
    b'1,"Apples, ""Gala""",0.89,2026-07-01\n'
    b'2,"Pears\n(two-line note)",1.25,2026-07-02\n'
    b'3,,0.50,2026-07-03\n'
)

def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="reference",
                    choices=available_backends())
    args = ap.parse_args()

    schema = Schema.of(("id", "int32"), ("name", "str"),
                       ("price", "float32"), ("updated", "date"))
    parser = Parser(ParserConfig(
        dfa=make_csv_dfa(comment=b"#"),   # line comments — beyond quote-parity tricks
        schema=schema,
        max_records=16,
        backend=args.backend,
        # pin the radix partition kernel so the example (and the CI smoke
        # job) exercises it — interpret-mode "auto" picks the jnp pass
        partition_impl="kernel" if args.backend == "pallas" else "auto",
    ))
    result = parser.parse(CSV)
    assert bool(result.validation.ok), "input should validate"
    n = int(result.validation.n_records)
    print(f"backend: {args.backend}")
    print(f"records: {n}  (comment line produced none)")

    arrow = parser.to_arrow(result)
    ids = arrow["id"]["values"][:n]
    prices = arrow["price"]["values"][:n]
    names = arrow["name"]
    for r in range(n):
        s = bytes(names["data"][names["offsets"][r]: names["offsets"][r + 1]])
        print(f"  id={ids[r]} name={s.decode()!r} price={prices[r]:.2f}")

    # empty field -> NULL (validity bit clear)
    validity = np.unpackbits(arrow["name"]["validity"], bitorder="little")[:n]
    print("name validity:", validity.tolist())


if __name__ == "__main__":
    main()
