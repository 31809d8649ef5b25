"""JAX's persistent compilation cache, configured in one place.

Every process that builds a :class:`~yjs_tpu.ops.engine.BatchEngine`
passes through :func:`ensure_compile_cache` before it compiles anything.
A served process compiles many small programs — ``apply_plan2`` once per
``(k_dn, k_sp, k_h, k_d)`` lane-width key, eight widths per octave — and
without the cache every start pays for all of them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and no directory is set in code.  Otherwise the cache lives in
``.jax_compile_cache/`` at the root of the checkout (git-ignored): a
fixed path, so the next process of the same checkout finds it.

The CPU backend is left alone.  It is the test platform, its programs
compile in milliseconds, and XLA:CPU reloads a cached executable with a
machine-feature complaint on standard error for every entry.
"""

from __future__ import annotations

import functools
import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


@functools.cache
def ensure_compile_cache() -> None:
    """Runs once per process; must precede its first compilation."""
    if jax.default_backend() == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # JAX's defaults skip programs that compile in under a second: that
    # is every scatter program this engine has
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
