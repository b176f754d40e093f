"""Device time of the §3.1 context scan and §3.2 replay kernels, per GB of
source bytes in the traced window.

An op is theirs when its name stack runs through their jitted wrappers,
or, since the step calls the kernels without those wrappers today (the
ops' name stack is ``jit(step_one)/pallas_call``), when JAX recorded its
source in the ``repro/kernels/dfa_scan`` package."""

PATTERNS = (r"jit\(chunk_vectors\)", r"jit\(replay_fused\)",
            r" @ repro/kernels/dfa_scan/")


def read(r):
    if r.trace is None or not r.source_bytes:
        return None
    s = r.trace.matching_s(PATTERNS)
    return 1e3 * s / (r.source_bytes / 1e9) if s > 0 else None
