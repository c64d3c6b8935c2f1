"""Where JAX's persistent compilation cache lives, for every entry point.

``chip_smoke.py`` calls :func:`enable_compile_cache` before its first
compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
in code. Otherwise the cache goes to a fixed directory inside the checkout
``strom`` was imported from (listed in its ``.gitignore``): the path is part
of what a later run must match, so it is never a temporary name, a pid or a
timestamp. An installed copy has no checkout, and then the variable is
required.
"""

from __future__ import annotations

import os
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_NAME = ".jax_compile_cache"


def checkout_root(package_file: str = __file__) -> str | None:
    """The checkout holding this package: the directory above ``strom/``
    when its ``.gitignore`` lists the cache directory; None for an installed
    copy (a site-packages directory is no checkout)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(package_file))))
    try:
        with open(os.path.join(root, ".gitignore")) as f:
            listed = CACHE_NAME + "/" in f.read().split()
    except FileNotFoundError:
        return None
    return root if listed else None


REPO_ROOT = checkout_root()
DEFAULT_DIR = None if REPO_ROOT is None else os.path.join(REPO_ROOT,
                                                          CACHE_NAME)


def compile_cache_dir(env: Mapping[str, str] | None = None) -> tuple[str, bool]:
    """(directory, whether it came from ``JAX_COMPILATION_CACHE_DIR``)."""
    env = os.environ if env is None else env
    d = env.get(ENV_VAR)
    if d:
        return d, True
    if DEFAULT_DIR is None:
        raise RuntimeError(f"strom is not imported from a checkout, so it "
                           f"has no fixed compile-cache directory: set "
                           f"{ENV_VAR}")
    return DEFAULT_DIR, False


def cache_entries(path: str) -> int:
    """Number of entries in the cache directory (0 when it does not exist)."""
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return the directory. Must run before the first compile."""
    import jax

    d, from_env = compile_cache_dir()
    if not from_env:
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    return d
