"""Where the persistent XLA compile cache lives.

One rule for every entry point that compiles on the chip (chip_smoke.py,
bench.py, scripts/tpu-host-run.sh): where `JAX_COMPILATION_CACHE_DIR` is
set, JAX reads it itself and nothing is set in code; where it is not,
the cache goes to ONE fixed directory inside the checkout, `.jax_cache`
(git-ignored). The path is part of the cache key, so it never holds a
pid, a time or a temp name: a second run in the same checkout hits what
the first one compiled.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the compile cache is kept in: the environment's, or
    `<checkout>/.jax_cache`."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def configure() -> str:
    """Point JAX at `cache_dir()` unless the environment already did;
    returns the directory in use. Call before the first compile."""
    path = cache_dir()
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


if __name__ == "__main__":  # scripts/tpu-host-run.sh reads the default
    print(cache_dir())
