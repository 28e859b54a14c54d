"""Process-level runtime settings shared by the entry points.

``enable_compile_cache`` turns on JAX's persistent compilation cache;
entry points call it from ``main`` (never at import), so tests and
library users keep JAX's defaults.  ``runtime_fingerprint`` is the
device record every artifact meta carries.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it
    and no other directory is set.  Otherwise the cache lives at the
    fixed ``<repo>/.jax_cache``: the path is part of what a later
    process must find, so it never carries a temp name, pid or time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def runtime_fingerprint() -> dict:
    """``{"jax": version, "device": kind}`` for bench/serve artifact
    metas.  ONE spelling for every artifact writer (benchmarks/run.py,
    benchmarks/bench_serve.py, repro.launch.graph_serve):
    benchmarks/compare.py keys its cross-config skip on these exact
    strings, so divergent copies would desynchronize the metas and
    silently re-trigger gate skips."""
    d = jax.devices()[0]
    return {"jax": jax.__version__,
            "device": getattr(d, "device_kind", d.platform)}
