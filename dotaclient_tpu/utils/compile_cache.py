"""One persistent XLA compile cache, placeable from outside.

Every entry point that compiles for a device calls :func:`enable` first
thing — the learner, the serve server, ``chip_smoke.py``, the ``scripts/``
probes — so a second process (a restart, the serve server after the
trainer, the next benchmark run) loads the compiled
programs instead of rebuilding them.

Where it goes: ``JAX_COMPILATION_CACHE_DIR`` is the outside handle. JAX reads
that variable itself into ``jax_compilation_cache_dir``; when it is set this
module sets no directory at all. Unset, the cache lives at ONE fixed path
inside the checkout (``<repo>/.jax_cache``, git-ignored). The path is never
built from a pid, a temp name or the clock: a directory that moves between
runs never hits.

The key of an entry includes the program's metadata (scope names, source
lines): JAX leaves them out by default, and a process would then load a
program compiled before a ``jax.named_scope`` was added or renamed and its
profiler trace would name every operation as the old code did. A restart of
the same code hits as before; a changed file on the traced path compiles
again, once.

The CPU backend is left exactly as JAX configured it. Programs at the sizes
the CPU runs (tests, rehearsals, actors) compile in seconds, XLA:CPU logs an
error line for every entry it loads back, and a test run must not write
into the checkout.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable() -> Optional[str]:
    """Turn the persistent cache on for this process; returns the directory
    in use (``None``: no cache — the CPU backend with the variable unset).

    Call before the first compile: JAX opens the cache once, at the first
    program it compiles, and ignores a directory set after that. Initializes
    the backend (it asks which one this is), so on a multi-host job call it
    after ``jax.distributed.initialize``."""
    if jax.default_backend() != "cpu":
        if not os.environ.get(ENV_VAR):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # JAX's default stores only programs that took >= 1 s to compile;
        # the serve dispatch, the snapshot copies and the gathers are
        # quicker than that and would be rebuilt by every process
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # a trace must name operations as THIS code scopes them (see above)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir
