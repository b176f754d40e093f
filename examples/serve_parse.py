"""Multi-tenant parse service demo: four tenants with mixed schemas share
one :class:`~repro.serve.ParseService`, one tenant is fed a record longer
than its carry capacity, and the service proves ISSUE-7's contract —

  * tenants with equal plan keys (the two well-behaved yelp tenants plus
    the faulty one) batch into ONE vmapped streaming session; the taxi
    tenant compiles its own plan and runs in a separate tier-1 batch;
  * the induced overflow surfaces as a typed ``TenantOverflow`` on the
    faulty tenant's channel only — the other tenants of the same batched
    session finish bit-identical to solo runs;
  * a second wave of tenants is admitted onto the SAME session object
    (no recompile), i.e. the failed tenant's lane is reclaimed within one
    service lifetime.

    PYTHONPATH=src python examples/serve_parse.py [--records 200]
        [--backend pallas]

Exits nonzero if any of the above fails — CI runs this as the serving
smoke.
"""
import argparse
import sys
import threading

sys.path.insert(0, "src")

import numpy as np

from repro import compile_cache
from repro.core import ParserConfig, Schema, available_backends, make_csv_dfa
from repro.data import synth
from repro.serve import ParseService, TenantOverflow, TenantResult


def drain(tenant, out):
    """Consumer thread body: split a tenant's channel by result type."""
    res, ovf = [], []
    for item in tenant.results():
        (res if isinstance(item, TenantResult) else ovf).append(item)
    out[tenant.name] = (res, ovf)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=200,
                    help="records per well-behaved tenant")
    ap.add_argument("--backend", default="reference",
                    choices=available_backends())
    args = ap.parse_args()

    rng = np.random.default_rng(7)
    yelp_cfg = ParserConfig(
        dfa=make_csv_dfa(), schema=Schema.of(*synth.YELP_SCHEMA),
        max_records=128, backend=args.backend)
    taxi_cfg = ParserConfig(
        dfa=make_csv_dfa(), schema=Schema.of(*synth.TAXI_SCHEMA),
        max_records=128, backend=args.backend)
    pb, mcb = 8192, 16384

    yelp_a = synth.yelp_like(rng, args.records)
    yelp_b = synth.yelp_like(rng, args.records)
    taxi = synth.taxi_like(rng, args.records)
    # one record longer than max_carry_bytes: no partitioning can ever
    # complete it, so its lane must overflow (and only its lane)
    bad = (synth.yelp_like(rng, 5)
           + b'9,5,0,"' + b"x" * (2 * mcb) + b'",2020-01-01\n')

    svc = ParseService()          # threaded front end (dispatcher + workers)
    out, consumers = {}, []
    with svc:
        tenants = [
            svc.submit(yelp_cfg, [yelp_a], partition_bytes=pb,
                       max_carry_bytes=mcb, name="yelp-a"),
            svc.submit(yelp_cfg, [yelp_b], partition_bytes=pb,
                       max_carry_bytes=mcb, name="yelp-b"),
            svc.submit(yelp_cfg, [bad], partition_bytes=pb,
                       max_carry_bytes=mcb, name="yelp-bad"),
            svc.submit(taxi_cfg, [taxi], partition_bytes=pb,
                       max_carry_bytes=mcb, name="taxi"),
        ]
        for t in tenants:
            th = threading.Thread(target=drain, args=(t, out), daemon=True)
            th.start()
            consumers.append(th)
        for t in tenants:
            t.wait(timeout=600)
        for th in consumers:
            th.join(timeout=60)

        # -- fault isolation ------------------------------------------------
        assert len(out["yelp-bad"][1]) == 1, "expected exactly one overflow"
        ovf = out["yelp-bad"][1][0]
        assert isinstance(ovf, TenantOverflow)
        assert "record longer than capacity" in str(ovf.error)
        for name in ("yelp-a", "yelp-b", "taxi"):
            assert not out[name][1], f"{name} must not see the overflow"

        # healthy tenants completed in full (the bit-identical-to-solo
        # pinning lives in tests/test_serving.py's acceptance test)
        for name in ("yelp-a", "yelp-b"):
            got = sum(r.n_records for r in out[name][0])
            assert got == args.records, (name, got)
        got = sum(r.n_records for r in out["taxi"][0])
        assert got == args.records, ("taxi", got)

        # plan-key sharing: yelp×3 share one parser, taxi adds a second
        assert svc.registry.parser_builds == 2, svc.registry.parser_builds
        yelp_key = tenants[0].session_key
        assert tenants[1].session_key == yelp_key
        assert tenants[2].session_key == yelp_key
        assert tenants[3].session_key != yelp_key

        # -- lane reclaim ---------------------------------------------------
        # a second 3-wide yelp wave lands on the SAME session (same tier,
        # same plan key) — including the lane the faulty tenant burned
        builds = svc.registry.session_builds
        wave2 = [svc.submit(yelp_cfg, [synth.yelp_like(rng, 20)],
                            partition_bytes=pb, max_carry_bytes=mcb,
                            name=f"wave2-{i}") for i in range(3)]
        out2 = {}
        ths = [threading.Thread(target=drain, args=(t, out2), daemon=True)
               for t in wave2]
        for th in ths:
            th.start()
        for t in wave2:
            t.wait(timeout=600)
        for th in ths:
            th.join(timeout=60)
        for t in wave2:
            assert t.session_key == yelp_key, "wave 2 must reuse the session"
            res, ovf2 = out2[t.name]
            assert not ovf2 and sum(r.n_records for r in res) == 20
        assert svc.registry.session_builds == builds, "no recompile on reuse"

    gbs = {t.name: t.stats.bytes_in for t in tenants}
    print(f"backend: {args.backend}")
    print(f"parsers compiled: {svc.registry.parser_builds}  "
          f"sessions built: {svc.registry.session_builds}")
    for t in tenants:
        tag = "OVERFLOW (isolated)" if t.failed else "ok"
        print(f"  {t.name:9s} bytes_in={gbs[t.name]:8d} "
              f"records={t.stats.records:5d} {tag}")
    print("wave 2: 3 tenants reclaimed the same session — no recompile")
    print("OK")


if __name__ == "__main__":
    main()
