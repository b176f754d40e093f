"""Persistent cache of measured per-device parse configurations.

Layout: versioned JSON, one entry per *tuning key* — the digest of
``(backend, device_kind, platform, interpret, DFA content, schema dtypes,
tagging, chunk_size, conversion widths)``.  Deliberately NOT
``stages.plan_key``: the plan key fingerprints the executable a config
compiles to *including* the knobs under tuning, which would make every
candidate its own cache line; the tuning key fingerprints the workload
shape the knobs are being tuned *for* (same machinery, knob fields
excluded) plus the device, so one entry answers every config that parses
that format on that device.

Two layers, looked up in order:

  * the **user cache** — ``~/.cache/repro-tune/cache.json`` (override with
    ``$REPRO_TUNE_CACHE``), written by ``python -m repro.tune`` runs on
    this machine;
  * the committed **seed cache** — ``src/repro/tune/default_cache.json``,
    interpret-CPU measurements refreshed by the nightly sweep, so a fresh
    checkout resolves to measured configs (e.g. clf/jsonl/zone staged, the
    BENCH-observed megakernel regressions) before anyone tunes locally.

Robustness contract: a missing, corrupt, or version-mismatched cache file
is an *empty* cache, never an exception — the resolver falls back to the
heuristic defaults, exactly the pre-autotuner behaviour.  Entries carry
the full human-readable key echo next to the digest so a cache file can
be audited (and hand-pruned) without re-deriving hashes.

Entry schema::

    {
      "version": 1,
      "entries": {
        "<digest>": {
          "key": {...},                  # human-readable tune_key echo
          "knobs": {"partition_impl": "scatter2", "fuse_pipeline": false, ...},
          "score": {"us_per_call": ..., "gbps": ..., "n_bytes": ...},
          "stream": {"partition_bytes": ..., "serve_tiers": [1, 4], ...},
          "meta": {"jax": "...", "records": ..., "budget_exhausted": ...}
        }
      }
    }
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any, Dict, Optional, Tuple

VERSION = 1

_ENV_PATH = "REPRO_TUNE_CACHE"


def user_cache_path() -> str:
    """The writable per-machine cache file (``$REPRO_TUNE_CACHE`` wins)."""
    env = os.environ.get(_ENV_PATH)
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return os.path.join(base, "repro-tune", "cache.json")


def seed_cache_path() -> str:
    """The committed interpret-CPU seed cache shipped with the package."""
    return os.path.join(os.path.dirname(__file__), "default_cache.json")


def tune_key(cfg, device=None) -> Tuple[str, Dict[str, Any]]:
    """``(digest, echo)`` for ``cfg`` on ``device`` (default: the process's
    first jax device).

    The echo is the digest's preimage — stored alongside entries so cache
    files stay auditable.  Knob fields (``repro.tune.space.SPACE``) are
    excluded by construction: two configs differing only in tuned knobs
    share one entry.
    """
    from repro.core import backends as backends_mod
    from repro.core import stages as stages_mod

    if device is None:
        import jax

        device = jax.devices()[0]
    dfa_digest = hashlib.sha256(
        repr(stages_mod.dfa_key(cfg.dfa)).encode()).hexdigest()[:12]
    echo = {
        "backend": cfg.backend,
        "device_kind": str(device.device_kind),
        "platform": str(device.platform),
        "interpret": backends_mod.interpret_kernels(),
        "dfa": dfa_digest,
        "schema": [[c.dtype, bool(c.selected)] for c in cfg.schema.columns],
        "tagging": cfg.tagging,
        "chunk_size": int(cfg.chunk_size),
        "int_width": int(cfg.int_width),
        "float_width": int(cfg.float_width),
    }
    digest = hashlib.sha256(
        json.dumps(echo, sort_keys=True).encode()).hexdigest()[:16]
    return digest, echo


class TuneCache:
    """One cache file: load-tolerant, thread-safe, explicit ``save()``.

    ``lookup`` returns the stored entry dict (or ``None``); ``store``
    merges an entry under its digest (section-level merge, so a stream-only
    refresh keeps the knob section and vice versa).
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._entries: Dict[str, dict] = self._load(path)

    @staticmethod
    def _load(path: str) -> Dict[str, dict]:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return {}
        if not isinstance(data, dict) or data.get("version") != VERSION:
            return {}
        entries = data.get("entries")
        return dict(entries) if isinstance(entries, dict) else {}

    def lookup(self, digest: str) -> Optional[dict]:
        with self._lock:
            e = self._entries.get(digest)
            return json.loads(json.dumps(e)) if e is not None else None

    def store(self, digest: str, entry: dict) -> None:
        with self._lock:
            merged = dict(self._entries.get(digest, {}))
            merged.update(json.loads(json.dumps(entry)))
            self._entries[digest] = merged

    def save(self) -> str:
        with self._lock:
            payload = {"version": VERSION, "entries": self._entries}
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)
        return self.path

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# -- the process-wide lookup chain (user cache over seed cache) -------------

_chain_lock = threading.Lock()
_chain: Optional[Tuple[TuneCache, ...]] = None


def _get_chain() -> Tuple[TuneCache, ...]:
    global _chain
    with _chain_lock:
        if _chain is None:
            _chain = (TuneCache(user_cache_path()), TuneCache(seed_cache_path()))
        return _chain


def chain_lookup(digest: str) -> Optional[dict]:
    """Lookup through the user-over-seed chain (memoized load; call
    :func:`reset` after changing ``$REPRO_TUNE_CACHE`` or cache files)."""
    for c in _get_chain():
        e = c.lookup(digest)
        if e is not None:
            return e
    return None


def reset() -> None:
    """Drop the memoized chain (tests and the CLI re-point caches)."""
    global _chain
    with _chain_lock:
        _chain = None
