"""Where the entry points keep JAX's persistent compilation cache.

The library sets nothing on import. Entry points (``chip_smoke.py``'s
children, ``bench.py``, the ``benchmarks/`` and ``examples/`` mains) call
:func:`enable_compilation_cache` once, before first device use.
"""

from __future__ import annotations

import os

# The directory is part of the cache key's lookup, so it must not move
# between processes: a fixed path under the checkout, never tempfile/pid.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set the caller placed the cache:
    JAX reads the variable itself and no directory is set in code.
    Otherwise the cache lives at ``<checkout>/.jax_cache``.

    Two settings make a resumed job actually hit (PERF.md, Findings, PR 21):

    - The init, save and restore paths compile many programs that finish
      well under JAX's default one-second caching floor (per-leaf-shape
      init, device slices per shard piece, the fingerprint jits), and a
      resumed process recompiles every one of them, so the floor is zero.
      On the v5e, init + save + restore of the 5.6 GB train state is 31
      programs: at the default floor 5 are kept and a warm process still
      spends 4.8 s compiling the other 26; at zero all 31 are kept (3.0 MB
      instead of 2.6 MB) and a warm process spends 0.8 s.
    - A Pallas kernel is embedded in its program with its MLIR locations,
      and by default those carry the whole Python call stack. JAX strips
      locations from the cache key, but cannot see inside the kernel, so
      the train step's key would change with any line above the call that
      built it. Locations are cut to the innermost frame.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path


def cache_entry_count(path: str) -> int:
    """Compiled programs stored under ``path`` (0 when it does not exist)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
