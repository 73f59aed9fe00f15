"""The one cache-location rule every persisted cache shares.

Four things persist across processes — the autotuner's winner table
(``ops/autotune.py``), the compiled-executable cache (``.cache``), the
prediction plane's calibration store (``analysis/predict.py``) and jax's
own persistent compilation cache — and all of them live under ONE fixed,
git-ignored directory inside the checkout, ``<checkout>/.cache/``
(:func:`cache_root`), never under ``~``: a cache left behind by another
checkout or another run must not be able to change which blocks or which
backend a kernel gets here, and a cache whose path moves never hits.

* jax's cache goes where ``JAX_COMPILATION_CACHE_DIR`` says when that is
  set (jax reads the variable itself and nothing here overrides it), else
  to :func:`jax_cache_dir`; ``mxnet_tpu/__init__.py`` is the one place
  that sets it;
* for the repo's own three, an explicit ``MXNET_TPU_<NAME>_CACHE`` env
  value wins outright (a file path for file-shaped caches, a directory
  for directory-shaped ones; ``0``/``off``-style values mean *disabled*
  where the cache supports disabling), else the cache lives under
  :func:`cache_root`.

This module is import-light on purpose (stdlib only): both
``mxnet_tpu.ops`` and ``mxnet_tpu.compile`` reach it without creating
an import cycle.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["cache_root", "jax_cache_dir", "cache_location", "env_disabled",
           "ENV_OFF"]

# env values that mean "explicitly off" wherever a cache is optional
ENV_OFF = ("0", "off", "false", "no", "disabled")


def cache_root() -> str:
    """``<checkout>/.cache`` — the base every default cache path hangs
    off (not created here; callers mkdir when they first write)."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".cache")


def jax_cache_dir() -> str:
    """Where jax's persistent compilation cache lives when
    ``JAX_COMPILATION_CACHE_DIR`` does not place it."""
    return os.path.join(cache_root(), "jax")


def env_disabled(env_name: str) -> bool:
    """True when ``env_name`` is set to an explicit off value."""
    return os.environ.get(env_name, "").strip().lower() in ENV_OFF and \
        os.environ.get(env_name, "").strip() != ""


def cache_location(env_name: str, default_name: str) -> Optional[str]:
    """Resolve one cache's on-disk location: the ``env_name`` override
    when set (and not an off value), else ``<cache_root>/
    <default_name>``.  Returns None when the env explicitly disables the
    cache.  ``1``/``on``-style values select the default location (the
    common "just turn it on" spelling for opt-in caches)."""
    raw = os.environ.get(env_name, "").strip()
    if raw:
        if raw.lower() in ENV_OFF:
            return None
        if raw.lower() not in ("1", "on", "true", "yes", "default"):
            return raw
    return os.path.join(cache_root(), default_name)
