"""Yelp reviews as CSV, in the published review schema (Yelp Open Dataset,
``review.json``): ``review_id,user_id,business_id,stars,useful,funny,cool,
"text",date``.  The ids are 22-character base64url strings, the date is
``YYYY-MM-DD HH:MM:SS``, and the text is quoted, holding commas, newlines
and doubled quotes as ``synth.yelp_like`` makes it: about 100 words
(Poisson) with ``, really`` (p 0.8), ``\\nsecond line`` (p 0.5) and
``said ""wow"" loudly`` (p 0.3).

Every seed gets the same set of record sizes: the texts and the counts
come from the configuration's ``pool_seed``; ``--seed`` draws their order,
the ids and the dates, which have one width.
"""
from __future__ import annotations

import numpy as np

from benchlib import pieces as P

WORDS = (
    "the food was great amazing terrible service slow fast delicious cold "
    "warm friendly staff would recommend never again five stars one star "
    "best worst pizza burger sushi coffee place downtown"
).split()


def _text(rng: np.random.Generator, n: int, avg_text: int):
    words = [w.encode() for w in WORDS]
    width = max(map(len, words)) + 1
    table = np.zeros((len(words), width), np.uint8)
    wlen = np.array([len(w) for w in words], np.int64)
    for i, w in enumerate(words):
        table[i, :len(w)] = np.frombuffer(w, np.uint8)
        table[i, len(w)] = ord(" ")
    n_words = np.maximum(3, rng.poisson(avg_text / 6, n))
    ids = rng.integers(0, len(words), (n, int(n_words.max())))
    parts = []
    for k in range(ids.shape[1]):
        last = k == n_words - 1
        ln = np.where(k < n_words, wlen[ids[:, k]] + ~last, 0)
        parts.append((table[ids[:, k]], ln))
    u = rng.random((n, 3))
    parts.append(P.const(n, ", really", u[:, 0] < 0.8))
    parts.append(P.const(n, "\nsecond line", u[:, 1] < 0.5))
    wow = u[:, 2] < 0.3
    parts.append(P.const(n, ' said ""wow"" loudly', wow))
    text, ln = P.join(parts)
    # each doubled quote is one byte of the field
    return P.join([P.const(n, '"'), (text, ln), P.const(n, '"')]), \
        int(ln.sum() - 2 * wow.sum())


def make(seed: int, nbytes: int, spec: dict):
    n = max(1, int(nbytes) // int(spec["record_bytes"]))
    pool = np.random.default_rng(int(spec["pool_seed"]))
    text, text_bytes = _text(pool, n, int(spec["avg_text_bytes"]))
    count = lambda: P.integer(pool.geometric(0.4, n) - 1)  # noqa: E731
    stars, useful, funny, cool = (P.integer(pool.integers(1, 6, n)), count(),
                                  count(), count())
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    ids = [P.ident(rng, n) for _ in range(3)]
    date = P.datetime(rng.integers(P.T2005, P.T2019, n))
    shuffled = [P.take(p, order) for p in (stars, useful, funny, cool, text)]
    return P.records(ids + shuffled + [date], ",", text_bytes + 66 * n)
