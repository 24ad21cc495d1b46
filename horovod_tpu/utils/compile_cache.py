"""Where JAX's persistent compilation cache lives for this repo's scripts.

The path is part of the cache's key, so it never holds a temporary name, a
pid or a time: either the directory the environment names, or one fixed
directory inside the checkout.  Called from a script's ``main()`` only —
importing a module never turns the cache on, so tests stay off it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and nothing
    is set in code.  Otherwise the cache goes to ``<checkout>/.jax_cache``,
    exported into the environment so that worker processes spawned later
    share it.  Touches no backend: a launcher parent may call it.

    On both paths the key holds the program's debug information
    (``jax_compilation_cache_include_metadata_in_key``): a program that
    differs from a cached one in its ``hvd_*`` scopes alone is another entry,
    because a trace's names are read out of the executable.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[ENV_VAR] = path
    # jax reads the variable when it is imported, which may have happened.
    jax.config.update("jax_compilation_cache_dir", path)
    return path
