"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` before their first jit.  The
cache path is part of what a cached program is found under, so it must
not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment
names one (JAX reads that variable itself), otherwise ``.jax_cache/`` at
the root of the checkout.  No other code sets a cache path.

A cached program is found under its operations' metadata too (their
names, named scopes and source lines), so that an executable loaded from
the cache never names its operations in a profile by the scopes of
another version of the program.  Source paths enter that key relative to
the checkout, so a checkout that moves keeps its cache.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Every compiled program is cached, however
    quick its compile: the advisor service compiles one small program per
    (machine, thread budget, batch bucket), and each is worth keeping."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(str(REPO_ROOT) + os.sep),
    )
    return path
