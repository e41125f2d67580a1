"""Where the persistent XLA compilation cache lives — one rule, one helper.

The cache directory is part of what makes a compiled program findable
again, so it sits at a fixed path:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it at import, and
  the program uses exactly that directory and sets no other;
- unset: ``<checkout>/.jax_cache`` (git-ignored), never a per-host,
  per-process or per-time name — JAX's own cache key already covers the
  platform and the compiler;
- ``SPARK_EXAMPLES_TPU_COMPILE_CACHE=0``: no persistent cache at all
  (tests that must not read entries another process wrote) — JAX's
  ``jax_enable_compilation_cache`` is switched off, so this holds even
  when ``JAX_COMPILATION_CACHE_DIR`` is set.

A default directory that cannot be created or written (a read-only
install) is logged, and the program runs without a persistent cache.

The CLI, ``bench.py``, ``chip_smoke.py`` and ``tests_tpu/`` all call
:func:`enable_persistent_cache`.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

__all__ = ["compilation_cache_dir", "enable_persistent_cache"]

_log = logging.getLogger(__name__)

# spark_examples_tpu/utils/compile_cache.py → the checkout root.
_CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compilation_cache_dir() -> Optional[str]:
    """The directory the cache uses, or ``None`` when it is switched off."""
    if os.environ.get("SPARK_EXAMPLES_TPU_COMPILE_CACHE", "") == "0":
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT_ROOT, ".jax_cache"
    )


def enable_persistent_cache() -> Optional[str]:
    """Turn the cache on at :func:`compilation_cache_dir` (or off, for
    ``=0``); → the dir in use, ``None`` when there is none."""
    import jax

    path = compilation_cache_dir()
    if path is not None and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            os.makedirs(path, exist_ok=True)
            if not os.access(path, os.W_OK):
                raise PermissionError(f"{path} is not writable")
        except OSError as e:
            _log.warning(
                "compile cache: cannot use %s (%s); running without a "
                "persistent cache", path, e,
            )
            path = None
        else:
            jax.config.update("jax_compilation_cache_dir", path)
    _set_enabled(path is not None)
    return path


def _set_enabled(on: bool) -> None:
    """JAX decides once per process whether the cache is used; a change
    of the switch resets that decision so it takes effect."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if jax.config.jax_enable_compilation_cache != on:
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()
