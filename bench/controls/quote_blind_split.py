"""The control for a configuration that states no float: the plain
reference splitting on every ``\\n`` and ``,`` with quotes taken as data,
which breaks the guarantee that delimiters and newlines inside quotes are
data."""
from __future__ import annotations

from typing import List

from benchlib import check


def split(data: bytes) -> List[List[bytes]]:
    return [line.split(b",") for line in data.split(b"\n")[:-1]]


def view(data: bytes, schema) -> dict:
    return check.reference_view(split(data), schema)
