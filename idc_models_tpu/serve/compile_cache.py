"""Persistent compiled-program cache — warm replica spin-up.

A cold replica pays XLA compilation before it serves a single token,
for programs this process (or a sibling) has compiled before (on the
chip the `gpt2-large` cells' first set-up takes 155-191 s against
35-38 s once compiled: PERF.md §6, PR 22, through jax's own cache). This module makes that cost durable-once
per (program, config, mesh, toolchain): `SlotEngine._warm_aot` lowers
each fixed-shape serve program AOT, and the resulting executable is
serialized to disk (`jax.experimental.serialize_executable`); the next
replica with the SAME key deserializes it in milliseconds instead of
re-running XLA.

The key is everything an executable is valid for, nothing more:

- the engine's `cache_fingerprint()` — every `_ServeConfig` field,
  the engine knobs that reach tracing (slots, chunk, quant, draft,
  pages, tenants, partition rules), the mesh axes AND the concrete
  device assignment (serialized executables replay onto the exact
  devices they were compiled against — a different device set is a
  MISS, never a mis-placed load);
- the program name and its static shape parameters (window steps);
- jax + jaxlib versions and the backend platform — a toolchain bump
  invalidates every entry by keying it out, no sweeper needed.

Entries are one file per key, written atomically (tmp + `os.replace`)
so a concurrently spinning-up replica never reads a torn blob; a blob
that still fails to deserialize (truncated disk, foreign toolchain
writing under the same path) is EVICTED and counted as a miss — spin-up
falls back to a real compile and overwrites it. That handler is the one
deliberate swallow in this module (documented in the static-scan
allowlist): a corrupt best-effort cache must never be able to take a
replica down.

jax's own persistent compilation cache is a separate layer, placed
once per process by `idc_models_tpu.runtime.setup_compile_cache`
(`cli.main` calls it): it caches XLA IR→binary for EVERY jit in the
process (training steps included), complementing this executable
store, which skips tracing/lowering too. What is stored HERE is never
taken from it (`_compiled_afresh`): an executable jax loaded from its
own cache serializes again, on jaxlib's CPU backend, without its
standalone kernels, and the blob then loads and dies at its first
dispatch ("Function wrapped_iota not found").

Counters (hits/misses/stores/evictions, deserialize + compile seconds)
feed the `serve_compile_cache_*` gauges (serve/metrics.py) and the
`stats` CLI rollup, so warm-vs-cold is visible in the epilogue.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import time
from pathlib import Path

import jax


@contextlib.contextmanager
def _compiled_afresh():
    """jax's persistent compilation cache off for the compiles inside:
    the flag is read once and remembered, so it takes a reset on both
    sides. A compile another thread makes meanwhile misses that cache,
    no more."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


class CompileCache:
    """On-disk store of AOT-serialized executables, one file per key.

    `key()` hashes the full validity fingerprint; `load()` returns a
    ready-to-call Compiled (hit) or None (miss); `compile_and_store()`
    finishes a miss by compiling the caller's Lowered and persisting
    the result. All counters are cumulative for the life of this
    handle — `summary()` is what serve/metrics.py reads."""

    def __init__(self, path, *, logger=None):
        self.path = Path(path)
        self.path.mkdir(parents=True, exist_ok=True)
        self.logger = logger
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.evicted_corrupt = 0
        self.deserialize_s = 0.0
        self.compile_s = 0.0

    def _log(self, **kw) -> None:
        if self.logger is not None:
            self.logger.log(**kw)

    def key(self, *, program: str, fingerprint: dict) -> str:
        """Content-address of one executable: program name + engine
        fingerprint + toolchain (jax/jaxlib/backend). Any drift in any
        component is a different key — invalidation IS the key."""
        material = {
            # schema 2: entries are donation-free twins of the jitted
            # bodies (see SlotEngine._warm_aot) — blobs serialized
            # with donated buffers replay unsoundly cross-process on
            # CPU, so they must key out, not load. Schema 3: the window
            # program returns the attention's rows read as one more
            # result. Schema 4: the programs take their caches in the
            # stored form `ring_decode.cache_shape` declares. Schema 5:
            # the window appends with one scatter; a blob of schema 4
            # would load and give the same tokens a third slower
            "schema": 5,
            "jax": jax.__version__,
            "jaxlib": jax.lib.__version__,
            "backend": jax.default_backend(),
            "program": program,
            "fingerprint": fingerprint,
        }
        blob = json.dumps(material, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:32]

    def _file(self, key: str) -> Path:
        return self.path / f"{key}.jaxexe"

    def load(self, key: str, *, devices):
        """Deserialize the stored executable for `key` onto `devices`
        — the devices it was compiled for, in assignment order (the
        key already pins them; jax would otherwise load it onto EVERY
        device of the backend). None on a miss. A file that exists but
        cannot load (torn write survived a crash, foreign-toolchain
        blob under a colliding path) is evicted and reported as a
        miss: the cache is best-effort by contract — spin-up must fall
        back to a real compile, never die on a bad cache entry (the
        rebuilt entry then replaces it)."""
        from jax.experimental import serialize_executable as se

        f = self._file(key)
        if not f.exists():
            self.misses += 1
            self._log(event="compile_cache", outcome="miss", key=key)
            return None
        t0 = time.perf_counter()
        try:
            payload, in_tree, out_tree = pickle.loads(f.read_bytes())
            exe = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=list(devices))
        except Exception as e:
            f.unlink(missing_ok=True)
            self.evicted_corrupt += 1
            self.misses += 1
            self._log(event="compile_cache", outcome="evict_corrupt",
                      key=key, error=f"{type(e).__name__}: {e}")
            return None
        dt = time.perf_counter() - t0
        self.deserialize_s += dt
        self.hits += 1
        self._log(event="compile_cache", outcome="hit", key=key,
                  deserialize_ms=round(dt * 1e3, 3))
        return exe

    def compile_and_store(self, key: str, lowered):
        """Finish a miss: compile the Lowered, serialize, and persist
        atomically (tmp + `os.replace` — a reader either sees the old
        complete file or the new complete file, never a torn one; two
        replicas racing the same key write identical content and last
        one wins). Returns the compiled executable, so the cold path
        runs the SAME AOT object a warm hit would — cold-vs-warm
        timings compare the cache, not dispatch mechanisms."""
        from jax.experimental import serialize_executable as se

        t0 = time.perf_counter()
        with _compiled_afresh():
            exe = lowered.compile()
        dt = time.perf_counter() - t0
        self.compile_s += dt
        payload, in_tree, out_tree = se.serialize(exe)
        f = self._file(key)
        tmp = f.with_name(f.name + f".tmp{os.getpid()}")
        tmp.write_bytes(pickle.dumps((payload, in_tree, out_tree)))
        os.replace(tmp, f)
        self.stores += 1
        self._log(event="compile_cache", outcome="store", key=key,
                  compile_ms=round(dt * 1e3, 3),
                  bytes=f.stat().st_size)
        return exe

    def summary(self) -> dict:
        """The frozen-schema rollup serve/metrics.py reads."""
        return {
            "hits": self.hits,
            "misses": self.misses,
            "stores": self.stores,
            "evicted_corrupt": self.evicted_corrupt,
            "deserialize_s": round(self.deserialize_s, 6),
            "compile_s": round(self.compile_s, 6),
        }
