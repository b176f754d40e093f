"""Distributed parser: multi-device shard_map pipeline equals single-device
parse — end to end, *including* materialization/typeconv on every shard.

Every test runs through ``conftest.run_with_devices`` (subprocess-isolated
``--xla_force_host_platform_device_count``, explicit skip if the topology
is unavailable), so the overrides never leak into other tests.

Coverage:
  * index-only sharded parse reassembles the oracle strings (legacy pin);
  * converted sharded parse + host ``assemble`` is bit-identical to
    ``Parser.to_arrow`` for D∈{1,2,4,8} across backends × tagging modes ×
    ``fuse_pipeline`` (the tentpole guarantee);
  * every shard's device-resident columns, without ``assemble``, hold its
    records whole (the cross-device carry of each cut record's head) and
    agree with the sequential oracle: cuts inside quoted text, a record
    across a whole shard, a cut right after a newline, and a tail past
    ``max_carry_bytes`` that is flagged;
  * the compiled sharded executable's collective traffic is O(D·|S|) —
    byte-for-byte identical across a 4× input-size change;
  * lane-sharded ``StreamSession`` is bit-identical to the single-device
    batched engine and its step compiles with ZERO collectives (the
    carry-locality invariant);
  * the mesh-aware ``ParseService`` serves identical tenant results.
"""
import pytest

from conftest import run_with_devices

_COMMON = """
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import Parser, ParserConfig, Schema, make_csv_dfa
from repro.core.distributed import DistributedParser

def csv_data(n_rows, seed=7):
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n_rows):
        body = "".join(rng.choice(list('ab,\\n"x'))
                       for _ in range(int(rng.integers(0, 12))))
        rows.append((str(i), body.replace('"', '""'), f"{i}.25"))
    return rows, "".join('%s,"%s",%s\\n' % r for r in rows).encode()

SCHEMA = Schema.of(("a", "int32"), ("b", "str"), ("c", "float32"))
"""


@pytest.mark.slow
def test_distributed_matches_single():
    """Legacy pin: per-shard field index over an 8-device (4, 2) mesh
    reassembles every oracle string, including mid-record shard cuts."""
    out = run_with_devices(_COMMON + """
mesh = jax.make_mesh((4, 2), ("data", "model"))
rows, data = csv_data(200)
cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMA, max_records=256,
                   chunk_size=32)

dp = DistributedParser(cfg, mesh, axis_names=("data", "model"))
chunks = dp.prepare(data)
got = dp.parse_chunks(chunks)

from repro.core.transition import transition_pipeline
cls_ref, _, _ = transition_pipeline(chunks, cfg.dfa)
np.testing.assert_array_equal(
    np.asarray(got.classes).reshape(-1), np.asarray(cls_ref).reshape(-1))
assert int(np.asarray(got.n_records).reshape(-1)[0]) == len(rows)

arrow = dp.assemble(got)
off, dat = arrow["b"]["offsets"], arrow["b"]["data"]
for i, row in enumerate(rows):
    want = row[1].replace('""', '"')
    assert bytes(dat[off[i]:off[i + 1]]).decode() == want, (i, want)
print("DISTRIBUTED_OK", len(rows))
""", 8)
    assert "DISTRIBUTED_OK" in out


# (backend, fuse_pipeline, tagging) — three combos per device count cover
# both backends, both execute paths, and all three tagging layouts across
# the sweep without a full 9-way matrix per D.
_COMBOS = (("reference", False, "tagged"),
           ("pallas", False, "vector"),
           ("pallas", True, "inline"))


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", (1, 2, 4, 8))
def test_sharded_converted_bit_identity(n_dev):
    """The tentpole guarantee: sharded end-to-end parse *with conversion*
    (``assemble``) is bit-identical to ``Parser.to_arrow`` — validation
    scalars included — for every backend/path/tagging combo."""
    out = run_with_devices(_COMMON + f"COMBOS = {_COMBOS!r}\n" + """
mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
rows, data = csv_data(60)
for be, fuse, tagging in COMBOS:
    cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMA, max_records=128,
                       chunk_size=16, backend=be, tagging=tagging,
                       fuse_pipeline=fuse)
    p = Parser(cfg)
    res = p.parse_chunks(p.prepare(data))
    ref = p.to_arrow(res)

    dp = DistributedParser(cfg, mesh)
    sh = dp.parse_chunks(dp.prepare(data))
    got = dp.assemble(sh)

    key = (be, fuse, tagging)
    assert int(sh.n_records) == int(res.validation.n_records), key
    for f in ("ok", "end_state_ok", "no_invalid", "min_columns",
              "max_columns"):
        a = np.asarray(getattr(sh.validation, f)).reshape(-1)[0]
        b = np.asarray(getattr(res.validation, f))
        assert np.array_equal(a, b), (key, f, a, b)
    for col in got:
        for k in got[col]:
            a, b = np.asarray(got[col][k]), np.asarray(ref[col][k])
            assert a.dtype == b.dtype and np.array_equal(a, b), (key, col, k)
    print("OK", key)
print("CONVERTED_OK")
""", n_dev)
    assert "CONVERTED_OK" in out


_HALO = """
import os
sys.path.insert(0, os.path.dirname(%r))
from oracles.csvlike import parse as oracle_parse

SHARD, K, CARRY = 64, 16, 96

def table(lengths, cuts_in_quotes=()):
    # records of the given byte lengths: an int, quoted text holding a
    # comma, a newline and a doubled quote, and a float
    out = b""
    for i, n in enumerate(lengths):
        head, tail = b'%%d,"' %% i, b'",%%d.5\\n' %% i
        fill = n - len(head) - len(tail)
        text = (b'x,\\n""y' * n)[:fill]
        if text.endswith(b'"') and not text.endswith(b'""'):
            text = text[:-1] + b"z"
        out += head + text + tail
    assert len(out) <= 4 * SHARD, len(out)
    return out

def quoted_at(data, cut):
    inside = False
    for b in data[:cut]:
        if b == 0x22:
            inside = not inside
    return inside

CASES = {
    # every cut inside quoted text
    "quoted_cuts": table([40, 50, 50, 70, 36]),
    # one record from byte 50 to 146 holds all of shard 1
    "whole_shard": table([50, 96, 40, 60]),
    # a newline at byte 63: shard 0 passes on an empty tail
    "empty_tail": table([30, 34, 64, 64, 40]),
    # a record from byte 40 to 200: shard 2 would pass on 152 bytes
    "overflow": table([40, 160, 50]),
}
assert all(quoted_at(CASES["quoted_cuts"], c) for c in (64, 128, 192))
assert CASES["empty_tail"][63:64] == b"\\n"

mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
be, fuse, tagging = COMBO
cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMA, max_records=16,
                   chunk_size=K, backend=be, tagging=tagging,
                   fuse_pipeline=fuse)
dp = DistributedParser(cfg, mesh, max_carry_bytes=CARRY)
for name, data in CASES.items():
    sh = dp.parse_chunks(dp.prepare(data, pad_to=4 * SHARD))
    sent = np.asarray(sh.halo_bytes)
    if name == "overflow":
        assert bool(sh.overflow) and sent.max() > CARRY, sent
        try:
            dp.assemble(sh)
        except ValueError:
            continue
        raise AssertionError("assemble took an overflowed parse")
    assert not bool(sh.overflow), (name, sent)
    if name == "empty_tail":
        assert sent[0] == 0, sent
    if name == "whole_shard":
        assert sent[1] > SHARD, sent
    want = oracle_parse(data)
    base = np.asarray(sh.rec_base)
    assert int(sh.n_records) == len(want), name
    assert base[0] == 0, base
    css = np.asarray(sh.css).reshape(4, -1)
    off = np.asarray(sh.field_offset).reshape(4, 3, -1)
    ln = np.asarray(sh.field_length).reshape(4, 3, -1)
    vals = {c: [np.asarray(a).reshape(4, -1) for a in sh.values[c]]
            for c in ("a", "b", "c")}
    ends = list(base[1:]) + [len(want)]
    nl = [i for i, b in enumerate(data) if b == 0x0A and not quoted_at(data, i)]
    for d in range(4):
        # shard d owns the records whose newline lies in its bytes
        owned = [r for r, e in enumerate(nl) if d * SHARD <= e < (d + 1) * SHARD]
        assert list(range(base[d], ends[d])) == owned, (name, d, owned)
        for j, r in enumerate(owned):
            f = want[r]
            got = bytes(css[d, off[d, 1, j]:off[d, 1, j] + ln[d, 1, j]])
            assert got == f[1], (name, d, j, got, f[1])
            assert vals["a"][0][d, j] == int(f[0]) and vals["a"][1][d, j]
            assert vals["c"][0][d, j] == np.float32(float(f[2]))
    print("HALO_OK", name, list(sent))
print("HALO_DONE")
"""


@pytest.mark.slow
@pytest.mark.parametrize("combo", _COMBOS,
                         ids=lambda c: "-".join(map(str, c)))
def test_shard_columns_hold_whole_records(combo):
    """Each shard's device-resident columns, read without ``assemble``,
    hold exactly the records whose newline lies in its bytes, each whole,
    field by field as the sequential oracle parses them: cuts inside
    quoted text (a comma, a newline, a doubled quote), a record across a
    whole shard, a cut right after a newline (an empty tail), and a tail
    past ``max_carry_bytes``, which sets ``overflow`` and which
    ``assemble`` refuses."""
    out = run_with_devices(_COMMON + f"COMBO = {combo!r}\n"
                           + _HALO % __file__, 4)
    assert "HALO_DONE" in out, out


@pytest.mark.slow
def test_collectives_are_input_size_independent():
    """The O(D·|S|) pin: the compiled sharded executable's collective
    traffic is summary-sized — byte-for-byte identical across a 4× change
    in input size (no collective ever moves input-sized data)."""
    out = run_with_devices(_COMMON + """
from repro.launch.dryrun import parse_collective_bytes

mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMA, max_records=128,
                   chunk_size=32)
dp = DistributedParser(cfg, mesh)

stats = []
for n_chunks in (16, 64):  # 4x apart, both divisible by 8 devices
    hlo = dp.lower(n_chunks, 32).compile().as_text()
    stats.append(parse_collective_bytes(hlo))
(small_b, small_c), (big_b, big_c) = stats
assert sum(small_c.values()) > 0, small_c     # the stitch does gather
assert small_b == big_b, (small_b, big_b)     # ...but O(D*|S|) only
assert small_c == big_c, (small_c, big_c)
print("COLLECTIVES_OK", small_b)
""", 8)
    assert "COLLECTIVES_OK" in out


@pytest.mark.slow
def test_lane_sharded_streaming_bit_identity():
    """Lane-sharded StreamSession: identical yields + stats vs the
    unmeshed batched engine, and ZERO collectives in the compiled step
    (each device owns its lanes' carry — the carry-locality invariant)."""
    out = run_with_devices(_COMMON + """
from repro.core.streaming import StreamSession, StreamOverflow

devs = jax.devices()
mesh = jax.sharding.Mesh(np.array(devs), ("streams",))
S = 2 * len(devs)
cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMA, max_records=64,
                   chunk_size=16, backend="pallas")
p = Parser(cfg)
sources = [[("".join("%d,lane%d,%d.5\\n" % (s * 100 + i, s, i)
                     for i in range(7 + s))).encode()]
           for s in range(S)]

def run(mesh_arg):
    sess = StreamSession(p, partition_bytes=48, max_carry_bytes=48,
                         n_streams=S, mesh=mesh_arg)
    rounds = []
    for s, r, n in sess.parse_streams(sources):
        assert not isinstance(r, StreamOverflow)
        rounds.append((s, n, jax.tree_util.tree_map(np.asarray, r)))
    return sess, rounds

base_sess, base = run(None)
shd_sess, shd = run(mesh)
assert len(base) == len(shd)
for (s0, n0, r0), (s1, n1, r1) in zip(base, shd):
    assert (s0, n0) == (s1, n1)
    for a, b in zip(jax.tree_util.tree_leaves(r0),
                    jax.tree_util.tree_leaves(r1)):
        assert np.array_equal(a, b), s0
assert ([vars(a) for a in base_sess.stats]
        == [vars(b) for b in shd_sess.stats])

# zero-collectives pin on the compiled lane-sharded step
cb, cl = shd_sess._init_carry()
txt = shd_sess._step.lower(
    cb, cl, jnp.zeros((S, 48), jnp.uint8), jnp.zeros((S,), jnp.int32),
    jnp.zeros((S,), bool)).compile().as_text()
bad = [l for l in txt.splitlines()
       if any(c in l for c in ("all-gather", "all-reduce",
                               "collective-permute", "all-to-all"))]
assert not bad, bad[:5]
print("STREAMING_OK", S)
""", 4)
    assert "STREAMING_OK" in out


@pytest.mark.slow
def test_mesh_aware_service():
    """ParseService(mesh=...): tiers filter to multiples of the axis size
    and tenants get results identical to the unmeshed service."""
    out = run_with_devices(_COMMON + """
from repro.serve.service import ParseService, TenantResult

cfg = ParserConfig(dfa=make_csv_dfa(), schema=SCHEMA, max_records=64,
                   chunk_size=16, backend="pallas")
mesh = jax.sharding.Mesh(np.array(jax.devices()), ("streams",))

def serve(svc):
    data = [("%d,x%d,%d.5\\n" % (i, i, i)).encode() * 4 for i in range(5)]
    ts = [svc.submit(cfg, d, partition_bytes=64, name=f"t{i}")
          for i, d in enumerate(data)]
    while svc.step() is not None:
        pass
    out = {}
    for t in ts:
        st = t.wait(timeout=60)
        assert not t.failed
        chunks = []
        for item in t.results():
            assert isinstance(item, TenantResult), item
            arrow = svc.registry.parser(cfg)[1].to_arrow(item.result)
            chunks.append(np.asarray(arrow["a"]["values"])[:item.n_records])
        out[t.name] = (st.records, [c.tolist() for c in chunks])
    return out

base = serve(ParseService(tiers=(1, 4, 16), start=False))
svc = ParseService(tiers=(1, 4, 16), mesh=mesh, start=False)
assert svc.tiers == (4, 16), svc.tiers
assert serve(svc) == base
print("SERVICE_OK")
""", 4)
    assert "SERVICE_OK" in out
