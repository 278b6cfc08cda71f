"""Process environment of every benchmark entry point, set before JAX is
imported: the compile cache inside the checkout, at a fixed path, with
every program written to it, so that only a checkout's first run
compiles."""

from __future__ import annotations

import os
import pathlib


def setup(root: pathlib.Path) -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
