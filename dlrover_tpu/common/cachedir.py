"""Where the host-local caches live, and the hardening of the default.

The XLA compile cache (deserialized executables,
trainer/compile_cache.py) is host-local state that a restarted worker
will TRUST.

Placement has one knob, JAX's own: where ``JAX_COMPILATION_CACHE_DIR``
is set, every process of a job (launcher, agent, worker, restarted
worker) keeps its caches there, as given, and no code names another
directory. Where it is not set, the default is one fixed directory in
the checkout — the path is part of the compile cache's key, so a
directory that moves never hits — and the agent exports it to its
workers under JAX's variable.

The default gets two defenses before anything is loaded from it:

 - never adopt a directory owned by another uid (a pre-created trap
   would let another local user seed entries we load);
 - enforce the 0700 contract even on ADOPTED dirs — ``makedirs(mode=
   0o700)`` applies the mode only on creation, so a pre-existing
   same-uid dir with group/world access must be re-tightened (or
   refused if that fails).
"""

import os
import stat
from typing import Optional

from dlrover_tpu.common.log import default_logger as logger

#: JAX's own variable; jax.config reads it when jax is imported
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_cache_dir() -> str:
    """The fixed, git-ignored cache directory inside the checkout."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def resolve_cache_dir() -> Optional[str]:
    """The directory this process keeps its caches in: JAX's variable
    as given, else the hardened default (None if it cannot be
    trusted)."""
    return os.environ.get(ENV_JAX_CACHE_DIR) or ensure_private_dir(
        default_cache_dir()
    )


def ensure_private_dir(path: str) -> Optional[str]:
    """Create-or-adopt ``path`` as a 0700 directory private to this
    uid; returns the path, or None when it cannot be trusted.

    Refuses foreign-owned dirs outright. A same-uid dir with group or
    world bits set is re-tightened with chmod; if the chmod does not
    stick (e.g. an ACL-restricted mount) the dir is refused rather
    than used loose.
    """
    try:
        os.makedirs(path, mode=0o700, exist_ok=True)
        st = os.stat(path)
    except OSError as e:
        logger.error("cannot create cache dir %s: %s", path, e)
        return None
    if st.st_uid != os.getuid():
        logger.error(
            "cache dir %s is owned by uid %d (we are %d); refusing to "
            "trust its contents",
            path, st.st_uid, os.getuid(),
        )
        return None
    if stat.S_IMODE(st.st_mode) & 0o077:
        # adopted dir looser than the contract: tighten, then verify
        try:
            os.chmod(path, 0o700)
            st = os.stat(path)
        except OSError as e:
            logger.error("chmod 0700 on cache dir %s failed: %s", path, e)
            return None
        if stat.S_IMODE(st.st_mode) & 0o077:
            logger.error(
                "cache dir %s remains group/world-accessible after "
                "chmod; refusing to use it",
                path,
            )
            return None
        logger.warning(
            "cache dir %s was group/world-accessible; tightened to 0700",
            path,
        )
    return path
