"""NYC yellow taxi trips as CSV, in the TLC's 2018 yellow trip record
layout (17 columns: ``VendorID``, pickup and dropoff datetimes,
``passenger_count``, ``trip_distance``, ``RatecodeID``,
``store_and_fwd_flag``, ``PULocationID``, ``DOLocationID``,
``payment_type``, then seven money columns to ``total_amount``) and its
number formatting: ``trip_distance`` with two decimals and no zero before
the point (``.50``, ``2.70``), money with as few digits as the value
needs (``14``, ``6.5``, ``0.3``, ``15.3``).

Every seed gets the same set of record sizes: the numbers come from the
configuration's ``pool_seed``; ``--seed`` draws the records' order, the
vendor, the flag and the datetimes, which have one width.
"""
from __future__ import annotations

import numpy as np

from benchlib import pieces as P


def make(seed: int, nbytes: int, spec: dict):
    n = max(1, int(nbytes) // int(spec["record_bytes"]))
    pool = np.random.default_rng(int(spec["pool_seed"]))
    pick = lambda vals, p: pool.choice(np.array(vals), n, p=p)  # noqa: E731
    passengers = pick([1, 2, 3, 4, 5, 6], [.70, .14, .04, .02, .06, .04])
    dist = np.minimum(pool.exponential(290.0, n).astype(np.int64), 9999)
    ratecode = pick([1, 2, 3, 4, 5], [.97, .02, .004, .002, .004])
    pu, do = pool.integers(1, 266, n), pool.integers(1, 266, n)
    payment = pick([1, 2, 3, 4], [.70, .28, .01, .01])
    fare = 250 + 50 * np.round(dist * 5 / 100).astype(np.int64)
    extra = pick([0, 50, 100], [.5, .35, .15])
    mta = np.full(n, 50)
    tip = np.where(payment == 1, (fare * pool.uniform(0.1, 0.3, n)).astype(np.int64), 0)
    tolls = np.where(pool.random(n) < 0.05, 576, 0)
    surcharge = np.full(n, 30)
    total = fare + extra + mta + tip + tolls + surcharge
    numbers = [P.integer(passengers), P.cents(dist, "2dp"), P.integer(ratecode),
               None, P.integer(pu), P.integer(do), P.integer(payment)] + [
        P.cents(c, "short") for c in (fare, extra, mta, tip, tolls, surcharge, total)]
    duration = pool.integers(60, 3600, n)

    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    pickup = rng.integers(P.T2018, P.T2019 - 3600, n)
    flag = np.where(rng.random(n) < 0.005, ord("Y"), ord("N")).astype(np.uint8)
    numbers[3] = (flag[:, None], np.ones(n, np.int64))
    numbers = [p if k == 3 else P.take(p, order) for k, p in enumerate(numbers)]
    head = [P.integer(rng.integers(1, 3, n)), P.datetime(pickup),
            P.datetime(pickup + duration[order])]
    return P.records(head + numbers, ",", n)
