"""Persistent compile cache for the serving dispatch set.

The dominant cold-start term for a scale-up replica is XLA compilation:
the continuous decoder's dispatch set (one admit executable per prefill
bucket, the fused decode/verify steps, the chunked-prefill shapes) is
recompiled from scratch by every newborn even though an identical
replica finished the exact same compiles seconds earlier. This module
keys that work by an **engine fingerprint** — a digest of everything
that selects a compiled executable: model config, mesh shape
(tp/cp/pp), KV layout/dtype, the bucket set, and the decode knobs —
and keeps two layers of reuse apart:

- **XLA's persistent compilation cache** holds the serialized
  executables themselves. Its directory is placed from OUTSIDE this
  module (``utils/jaxenv.place_compile_cache``, called by the entry
  points): ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
  else one fixed path in the checkout. A pool that wants its replicas to
  share executables points that variable at the shared volume.
- A **fingerprint-checked manifest** (this module's own store, under
  ``--compile-cache-dir``) records
  which dispatch keys a prior replica of the SAME fingerprint already
  compiled. It is the hit/miss accounting surface
  (``serving_compile_cache_{hits,misses}_total``) and the invalidation
  rule: a config change — different buckets, different mesh, different
  jax — changes the fingerprint, so stale executables are never
  *counted* as coverage and XLA's own key check never deserializes a
  mismatched binary.

The decoder pre-warms at construction by RUNNING the dispatch set
(dummy generations through the real submit path — see
``ContinuousDecoder.warm``), which populates both the in-process jit
cache and, when configured, XLA's persistent store; the manifest then
records the warmed keys for the next birth's accounting.

Manifest writes are atomic (tmp + rename) and merging, so concurrent
newborns racing on the shared volume converge instead of clobbering.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile

# Manifest schema version: bump when the dispatch-key naming changes so
# old manifests read as empty instead of mis-counting coverage.
MANIFEST_VERSION = 1


def engine_fingerprint(model_config, **knobs) -> str:
    """Digest of everything that selects a compiled executable.

    ``model_config`` is the model's config dataclass (every field lands
    in the key — a d_model change is a different program); ``knobs``
    are the engine/decoder shape parameters (tp/cp/pp, kv layout/dtype,
    bucket set, decode chunk, speculative_k, ...). The jax version and
    backend ride the key too: a serialized executable is only valid for
    the compiler that produced it."""
    import jax

    payload = {
        "manifest_version": MANIFEST_VERSION,
        "model_config": {k: str(v) for k, v in
                         sorted(vars(model_config).items())},
        "knobs": {k: str(v) for k, v in sorted(knobs.items())},
        "jax": jax.__version__,
        "backend": jax.default_backend(),
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.blake2b(blob, digest_size=16).hexdigest()


def dispatch_keys(*, slots: int, prefill_len: int, prefill_len_buckets: int,
                  chunk_size: int, speculative_k: int,
                  prefill_chunk_tokens: int) -> list[str]:
    """The decoder's full dispatch set as stable string keys — one per
    distinct compiled executable shape the serving loop can reach.

    Mirrors the decoder's shape-selection rules: admit executables ride
    the pow2 prefill buckets (``prefill_len >> buckets`` floor), decode
    is one fused executable per chunk width, verify exists only under
    speculation, and chunked prefill adds its interior-chunk shape."""
    keys = []
    floor = (prefill_len >> prefill_len_buckets
             if prefill_len_buckets else prefill_len)
    width = max(1, floor)
    while True:
        keys.append(f"admit:s{width}")
        if width >= prefill_len:
            break
        width *= 2
    keys.append(f"decode:c{max(1, chunk_size)}")
    if speculative_k > 0:
        keys.append(f"verify:k{speculative_k}")
    if prefill_chunk_tokens > 0:
        keys.append(f"chunk:w{prefill_chunk_tokens}")
    return keys


class CompileCache:
    """Fingerprint-keyed manifest of warmed dispatch keys under a
    shared directory. One instance per decoder; hit/miss counts
    accumulate on the instance and surface through the decoder's
    metrics. Where XLA keeps the executables is not decided here."""

    def __init__(self, cache_dir: str):
        self.cache_dir = str(cache_dir)
        os.makedirs(self.cache_dir, exist_ok=True)
        self.hits = 0
        self.misses = 0

    def _manifest_path(self, fingerprint: str) -> str:
        return os.path.join(self.cache_dir, f"manifest-{fingerprint}.json")

    def load(self, fingerprint: str) -> set[str]:
        """Dispatch keys a prior replica of this fingerprint recorded.
        A torn/garbage manifest reads as empty — the newborn then just
        compiles; it must never crash a birth."""
        try:
            with open(self._manifest_path(fingerprint)) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return set()
        if not isinstance(data, dict) or \
                data.get("version") != MANIFEST_VERSION:
            return set()
        keys = data.get("keys")
        return {str(k) for k in keys} if isinstance(keys, list) else set()

    def record(self, fingerprint: str, keys) -> None:
        """Merge ``keys`` into the fingerprint's manifest atomically
        (tmp + rename): concurrent newborns on the shared volume merge
        with whatever landed since their read instead of clobbering."""
        merged = self.load(fingerprint) | {str(k) for k in keys}
        payload = {"version": MANIFEST_VERSION,
                   "fingerprint": fingerprint,
                   "keys": sorted(merged)}
        fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, self._manifest_path(fingerprint))
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def account(self, fingerprint: str, keys) -> tuple[int, int]:
        """Split ``keys`` against the manifest: (hits, misses). Hits are
        keys a prior same-fingerprint replica already compiled (this
        birth deserializes / reuses); misses are newly compiled here and
        recorded for the next birth."""
        known = self.load(fingerprint)
        keys = [str(k) for k in keys]
        hits = sum(1 for k in keys if k in known)
        misses = len(keys) - hits
        self.hits += hits
        self.misses += misses
        if misses:
            self.record(fingerprint, keys)
        return hits, misses
