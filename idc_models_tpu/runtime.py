"""Process start-up shared by every entry point: where jax's persistent
compile cache lives, and what devices the process runs on.

`cli.main` calls both before any verb runs, so a run always says what it
ran on (a libtpu that failed to initialise makes jax fall back to the
CPU with only a warning — the device line is what tells the two apart)
and a second process finds what the first one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# A cache is found again only under the path it was written to, so the
# default is a fixed place in the checkout (gitignored) — never a
# temporary name, a pid or a time.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def setup_compile_cache() -> Path:
    """Place jax's persistent compilation cache — the ONE site in the
    package that may (tests/test_static_robustness.py scans for a
    second). With JAX_COMPILATION_CACHE_DIR set, jax already read it at
    import and nothing is set here; otherwise the cache goes to
    `DEFAULT_CACHE_DIR`. Returns the directory in effect."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return Path(placed)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR


def device_summary() -> dict:
    """What jax runs this process on, as jax reports it. Initialises
    the backend."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
