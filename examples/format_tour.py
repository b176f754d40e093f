"""Format tour: parse JSON-Lines and a DNS zone file through the format
registry — same FSM engine, different transition tables (ROADMAP item 4).

    PYTHONPATH=src python examples/format_tour.py [--backend pallas]

Each format is looked up by name in ``repro.core.formats``; the registry
supplies the DFA and default tagging mode, ``repro.configs`` supplies the
per-format tuning (chunk size, typeconv widths).  ``--backend pallas`` runs
the kernel path (interpret mode on CPU hosts) with bit-identical outputs.
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro import compile_cache
from repro.configs import tuned_parser_config
from repro.core import Parser, available_backends, formats

JSONL = (
    b'{"id": 7, "name": "ok", "score": 1.5}\n'
    b'{"id": 8, "name": "x\\"y", "score": -2}\n'
    b'\n'
    b'{"id": 9, "name": {"first": "a", "last": "b"}, "score": 0.25}\n'
)

ZONE = (
    b'example.com 3600 IN A 93.184.216.34\n'
    b'www 600 IN CNAME example.com; alias for the apex\n'
    b'; full-line comment: produces no record\n'
    b'mail 7200 ( IN\n'
    b'   MX ) 10mail.example.com\n'
)


def tour(fmt: str, data: bytes, backend: str) -> None:
    spec = formats.get_format(fmt)
    parser = Parser(tuned_parser_config(
        fmt, max_records=16, backend=backend,
        partition_impl="kernel" if backend == "pallas" else "auto",
    ))
    result = parser.parse(data)
    n = int(result.validation.n_records)
    print(f"{fmt}: {n} records  ({spec.doc.split(':')[0]})")

    arrow = parser.to_arrow(result)
    for column in spec.default_schema.columns[:5]:
        col = column.name
        a = arrow[col]
        if column.dtype == "str":
            vals = [bytes(a["data"][a["offsets"][r]: a["offsets"][r + 1]])
                    for r in range(n)]
            print(f"  {col:>6}: {[v.decode('utf-8', 'replace') for v in vals]}")
        else:
            print(f"  {col:>6}: {a['values'][:n].tolist()}")


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="reference",
                    choices=available_backends())
    args = ap.parse_args()
    print(f"backend: {args.backend}")
    print(f"registered formats: {', '.join(formats.available_formats())}")
    tour("jsonl", JSONL, args.backend)
    tour("zone", ZONE, args.backend)


if __name__ == "__main__":
    main()
