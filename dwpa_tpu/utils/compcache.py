"""Persistent XLA compilation cache wiring.

The first compile of the 4096-iteration PBKDF2 step takes tens of
seconds, and a freshly restarted client would pay it again before its
first work unit (the reference client has no analog: hashcat ships
precompiled GPU kernels).  JAX's persistent compilation cache turns
that into a disk hit across restarts.

Placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets no directory.  Otherwise the cache lives at
one fixed path in the checkout (``<repo>/.xla_cache`` unless a caller
names another fixed path), never under the cwd, a workdir, a temporary
name, a pid or the time: a directory that moves never hits.

Separate module (not utils/__init__) so importing it never drags jax in
before ``jax.distributed.initialize`` runs on multi-host clients.
"""

import logging
import os

log = logging.getLogger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The checkout root (this file is ``<repo>/dwpa_tpu/utils/compcache.py``).
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: The cache directory when the environment names none.
DEFAULT_DIR = os.path.join(REPO_ROOT, ".xla_cache")


def enable_compilation_cache(default_dir: str = DEFAULT_DIR) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already taken the
    directory from the environment and nothing here overrides it.
    Otherwise ``default_dir`` (a fixed path) is created and used.  The
    0.5 s floor keeps trivial host-side jits out of the cache while
    every kernel that matters (all >1 s) persists.  An unwritable
    directory logs and returns None: the cache is a cold-start
    optimization, never a requirement.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    try:
        os.makedirs(default_dir, exist_ok=True)
    except OSError as e:
        log.warning("persistent compilation cache unavailable: %s", e)
        return None
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
