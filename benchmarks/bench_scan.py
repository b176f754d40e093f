"""Micro-benchmarks of ParPaRaw's algorithmic alternatives (the §Perf
hypothesis material):

  * composite-scan operator: gather (VPU) vs one-hot matmul (MXU)
  * partition: counting-scatter (single radix pass) vs stable argsort
  * numeric conversion: fixed-width gather Horner vs segmented-scan Horner
  * dfa_scan Pallas kernel (interpret) vs jnp reference — correctness-cost
    visibility only; interpret-mode timings are not TPU timings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import dataset, emit, time_fn, yelp_parser
from repro import compile_cache
from repro.core import make_csv_dfa
from repro.core import partition as partition_mod
from repro.core import transition as tr
from repro.core import typeconv


def scan_variants():
    dfa = make_csv_dfa()
    data = dataset("yelp", 2000)
    p = yelp_parser()
    chunks = jnp.asarray(p.prepare(data))
    groups = tr.byte_groups(chunks, dfa)
    vecs = tr.chunk_transition_vectors(groups, dfa)

    f_g = jax.jit(lambda v: tr.exclusive_scan_vectors(v, use_matmul=False))
    f_m = jax.jit(lambda v: tr.exclusive_scan_vectors(v, use_matmul=True))
    dt, _ = time_fn(f_g, vecs)
    emit("scan/compose_gather", dt * 1e6, f"chunks={vecs.shape[0]}")
    dt, _ = time_fn(f_m, vecs)
    emit("scan/compose_matmul", dt * 1e6, f"chunks={vecs.shape[0]}")


def partition_variants():
    rng = np.random.default_rng(0)
    tags = jnp.asarray(rng.integers(0, 6, size=1 << 20), jnp.int32)
    f_sc = jax.jit(lambda t: partition_mod.partition_scatter(t, 5).perm)
    f_as = jax.jit(lambda t: partition_mod.partition_argsort(t, 5).perm)
    dt, _ = time_fn(f_sc, tags)
    emit("partition/counting_scatter", dt * 1e6, "n=1M,c=5")
    dt, _ = time_fn(f_as, tags)
    emit("partition/argsort", dt * 1e6, "n=1M,c=5")


def typeconv_variants():
    rng = np.random.default_rng(0)
    n_fields = 1 << 14
    strs = [str(int(rng.integers(0, 10**8))) for _ in range(n_fields)]
    css = np.frombuffer(("".join(strs)).encode(), np.uint8)
    lens = np.asarray([len(s) for s in strs], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)

    f_g = jax.jit(lambda c, o, l: typeconv.parse_int(c, o, l, width=9).value)
    dt, _ = time_fn(f_g, jnp.asarray(css), jnp.asarray(offs), jnp.asarray(lens))
    emit("typeconv/gather_horner", dt * 1e6, f"fields={n_fields}")

    fid = np.repeat(np.arange(n_fields), lens).astype(np.int32)
    fstart = np.zeros(css.size, bool)
    fstart[offs] = True
    f_s = jax.jit(lambda c, s, i: typeconv.parse_int_segmented(c, s, i, n_fields).value)
    dt, _ = time_fn(f_s, jnp.asarray(css), jnp.asarray(fstart), jnp.asarray(fid))
    emit("typeconv/segmented_horner", dt * 1e6, f"css={css.size}B")


def kernel_vs_ref():
    from repro.core.backends import interpret_kernels
    from repro.kernels.dfa_scan import ops as kops
    from repro.kernels.dfa_scan import ref as kref
    dfa = make_csv_dfa()
    rng = np.random.default_rng(0)
    alphabet = np.frombuffer(b',"\nabcd ', np.uint8)
    chunks = jnp.asarray(
        alphabet[rng.integers(0, len(alphabet), size=4096 * 64)].reshape(4096, 64))
    interpret = interpret_kernels()
    dt, _ = time_fn(lambda c: kops.chunk_vectors(c, dfa, interpret=interpret),
                    chunks, iters=2)
    emit("kernel/dfa_scan" + ("_interpret" if interpret else ""), dt * 1e6,
         "4096x64B" + (";interpret-mode" if interpret else ""))
    dt, _ = time_fn(lambda c: kref.chunk_vectors(c, dfa), chunks, iters=2)
    emit("kernel/dfa_scan_jnp_ref", dt * 1e6, "4096x64B")


def run():
    compile_cache.enable()
    scan_variants()
    partition_variants()
    typeconv_variants()
    kernel_vs_ref()
