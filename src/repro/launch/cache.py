"""Persistent XLA compilation cache for the entry points.

A full-width training step takes minutes to compile, and every new process
starts with no compiled code.  The persistent cache lets the processes of
one command, and later commands on the same disk, reuse what was compiled.
JAX only finds entries again under the same directory, so the default is a
fixed path inside the checkout, never a temporary or per-process name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing; otherwise the cache lives at `<repo>/.jax_cache`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
