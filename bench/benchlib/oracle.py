"""The plain reference: a character-level RFC 4180 CSV parser (a copy of
the repository's test oracle, written without the parser's DFA tables),
Python's ``int`` / ``float`` / ``datetime`` for the typed columns, and the
comparison that decides ``correct``.

Nothing here imports the program.  The comparison reads a partition's
result as host arrays (``css``, field offsets and lengths, converted
values and validity, record flags) and holds it to what the reference
makes of the same bytes.
"""
from __future__ import annotations

import datetime
from typing import Dict, List, Optional

import numpy as np

LF, CR = 0x0A, 0x0D
EPOCH = datetime.datetime(1970, 1, 1)


def parse(data: bytes, delimiter: bytes = b",", quote: bytes = b'"',
          comment: Optional[bytes] = None,
          handle_cr: bool = True) -> List[List[bytes]]:
    """Records of ``data`` as lists of field bytes (quotes removed,
    doubled quotes unescaped)."""
    d, q = delimiter[0], quote[0]
    c = comment[0] if comment is not None else None
    if not data or data[-1] != LF:
        data += b"\n"

    records: List[List[bytes]] = []
    fields: List[bytes] = []
    cur = bytearray()
    state = "EOR"

    def end_field():
        fields.append(bytes(cur))
        cur.clear()

    def end_record():
        nonlocal fields
        fields.append(bytes(cur))
        cur.clear()
        records.append(fields)
        fields = []

    for b in data:
        if state == "EOR":
            if b == LF:
                end_record()
            elif b == q:
                state = "ENC"
            elif b == d:
                end_field(); state = "EOF"
            elif c is not None and b == c:
                state = "CMT"
            elif handle_cr and b == CR:
                pass
            else:
                cur.append(b); state = "FLD"
        elif state == "ENC":
            if b == q:
                state = "ESC"
            else:
                cur.append(b)  # delimiters, newlines, CR: data inside quotes
        elif state == "ESC":
            if b == q:
                cur.append(q); state = "ENC"  # doubled quote -> one literal
            elif b == LF:
                end_record(); state = "EOR"
            elif b == d:
                end_field(); state = "EOF"
            elif handle_cr and b == CR:
                pass
            else:
                raise ValueError(f"junk byte {b:#x} after closing quote")
        elif state == "FLD":
            if b == LF:
                end_record(); state = "EOR"
            elif b == d:
                end_field(); state = "EOF"
            elif b == q:
                raise ValueError("quote inside unquoted field")
            elif handle_cr and b == CR:
                pass
            else:
                cur.append(b)  # '#' mid-record is plain data
        elif state == "EOF":
            if b == LF:
                end_record(); state = "EOR"
            elif b == q:
                state = "ENC"
            elif b == d:
                end_field()
            elif handle_cr and b == CR:
                pass
            else:
                cur.append(b); state = "FLD"  # '#' after a delim is data too
        else:  # CMT: swallow to newline; comment lines emit no record
            if b == LF:
                state = "EOR"
    return records


def to_int(field: bytes) -> int:
    return int(field)


def to_float(field: bytes) -> float:
    return float(np.float32(float(field)))


def to_date(field: bytes) -> int:
    return int((datetime.datetime.fromisoformat(field.decode()) - EPOCH)
               .total_seconds())


CONVERT = {"int32": to_int, "float32": to_float, "date": to_date}
