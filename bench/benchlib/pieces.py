"""Vectorised building blocks of the seeded CSV generators (``bench/gen``).

A *piece* is one field of every record at once: a ``(rows, width)`` uint8
matrix, left-aligned, and each row's length.  Generators build their
fields as pieces and join them into records, so that a block of millions
of fields is made by numpy, not by a Python loop per field.

Every generator returns ``(data, rec_end, str_bytes)``: the bytes, whole
records ending in ``\\n``; the offset one past each record's ``\\n``; and
the bytes of the string fields once quotes are taken off, which the
roofline's least bytes count.
"""
from __future__ import annotations

import numpy as np

#: The seconds from 1970-01-01 to 2005-01-01, 2018-01-01 and 2019-01-01.
T2005, T2018, T2019 = 1104537600, 1514764800, 1546300800

ID_ALPHABET = np.frombuffer(
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_", np.uint8)


def const(n: int, text: str, present=None):
    """The same bytes in every row (or only where ``present``)."""
    b = np.frombuffer(text.encode(), np.uint8)
    mat = np.broadcast_to(b, (n, b.size))
    ln = np.full(n, b.size, np.int64)
    if present is not None:
        ln = np.where(present, ln, 0)
    return mat, ln


def integer(v: np.ndarray, width: int = 0):
    """``v`` (non-negative ints) in decimal, zero-padded to ``width``
    digits, else as few as it needs."""
    v = np.asarray(v, np.int64)
    nd = np.maximum(1, np.floor(np.log10(np.maximum(v, 1))).astype(np.int64) + 1)
    nd = np.maximum(nd, width)
    w = int(nd.max())
    p = np.arange(w)[None, :]
    power = nd[:, None] - 1 - p
    digit = (v[:, None] // 10 ** np.clip(power, 0, None)) % 10
    mat = np.where(power >= 0, digit + 48, 0).astype(np.uint8)
    return mat, nd


def cents(c: np.ndarray, style: str):
    """``c`` cents as a decimal of ``c / 100``.

    ``"short"``: as few digits as the value needs (``14``, ``6.5``,
    ``0.3``, ``12.35``); ``"2dp"``: two decimals with no zero before the
    point (``.50``, ``2.70``)."""
    c = np.asarray(c, np.int64)
    n = c.size
    whole, frac = c // 100, c % 100
    if style == "2dp":
        lead = integer(whole)
        lead = (lead[0], np.where(whole > 0, lead[1], 0))
        return join([lead, const(n, "."), integer(frac, 2)])
    if style != "short":
        raise ValueError(f"unknown style {style!r}")
    tenths = frac % 10 == 0
    digits = np.where(tenths, frac // 10, frac)
    fmat, _ = integer(digits, 2)
    fln = np.where(frac == 0, 0, np.where(tenths, 1, 2))
    fmat = np.where(tenths[:, None], np.roll(fmat, -1, axis=1), fmat)
    return join([integer(whole), const(n, ".", frac != 0), (fmat, fln)])


def datetime(seconds: np.ndarray):
    """``YYYY-MM-DD HH:MM:SS`` of seconds since 1970-01-01."""
    t = np.asarray(seconds, np.int64).astype("datetime64[s]")
    text = np.datetime_as_string(t, unit="s").astype("S19")
    mat = text.view(np.uint8).reshape(-1, 19).copy()
    mat[:, 10] = ord(" ")
    return mat, np.full(mat.shape[0], 19, np.int64)


def ident(rng: np.random.Generator, n: int, width: int = 22):
    """Base64url ids of ``width`` characters, as Yelp's 22-character ids."""
    mat = ID_ALPHABET[rng.integers(0, ID_ALPHABET.size, (n, width))]
    return mat, np.full(n, width, np.int64)


def join(pieces):
    """Concatenate pieces row by row into one left-aligned piece."""
    flat, ln = rows(pieces)
    w = int(ln.max())
    out = np.zeros((ln.size, w), np.uint8)
    out[np.arange(w)[None, :] < ln[:, None]] = flat
    return out, ln


def rows(pieces):
    """The flat bytes of every row's pieces in order, and each row's length."""
    mats = [np.asarray(m) for m, _ in pieces]
    mask = np.concatenate(
        [np.arange(m.shape[1])[None, :] < ln[:, None]
         for m, (_, ln) in zip(mats, pieces)], axis=1)
    flat = np.concatenate(mats, axis=1)[mask]
    return flat, sum(ln for _, ln in pieces)


def take(piece, order: np.ndarray):
    """The rows of ``piece`` in ``order``."""
    mat, ln = piece
    return np.asarray(mat)[order], np.asarray(ln)[order]


def records(pieces, sep: str = ",", str_bytes: int = 0):
    """Rows joined by ``sep`` between pieces and ``\\n`` after the last."""
    n = pieces[0][0].shape[0]
    out = []
    for k, p in enumerate(pieces):
        out.append(p)
        out.append(const(n, sep if k < len(pieces) - 1 else "\n"))
    flat, ln = rows(out)
    return flat.tobytes(), np.cumsum(ln), int(str_bytes)
