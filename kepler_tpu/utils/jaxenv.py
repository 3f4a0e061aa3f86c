"""Process-level JAX set-up every binary shares: which platform the
process may use, and where its persistent compile cache lives.

Both must run before anything initialises the JAX backend, so the entry
points (``cmd/aggregator``, ``cmd/main``, ``cmd/train``, ``bench.py``, the
``benchmarks/`` mains) call them first. jax is imported lazily: importing
this module touches no backend.

One chip belongs to one process. The aggregator owns it; a node agent on
the same host computes on the CPU (``tpu.platform: auto`` resolves to
``cpu`` there), or it would take the chip or hang waiting for it.
"""

from __future__ import annotations

import os
from typing import NamedTuple

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed default: ``<checkout>/.jax_cache``. The path is part of the
#: cache key, so it never carries a pid, a time or a temporary name.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


class DeviceInfo(NamedTuple):
    platform: str  # jax.devices()[0].platform
    device_kind: str  # jax.devices()[0].device_kind
    count: int  # len(jax.devices())

    def __str__(self) -> str:
        return (f"platform={self.platform} device_kind={self.device_kind} "
                f"devices={self.count}")


def compile_cache_dir(configured: str = "") -> str:
    """The cache directory in effect: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``configured`` (``tpu.compilationCacheDir``), else
    :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get(CACHE_ENV) or configured or DEFAULT_CACHE_DIR


def configure_compile_cache(configured: str = "") -> str:
    """Place the persistent XLA compile cache → the directory in effect.

    With ``JAX_COMPILATION_CACHE_DIR`` set jax reads the directory itself
    and no directory is set in code. Every compile is kept, however
    short: a restarted aggregator then serves its first window from disk
    hits for the scatter-updates as well as the fleet program."""
    import jax

    path = compile_cache_dir(configured)
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def select_platform(platform: str) -> None:
    """Pin this process to ``tpu`` or ``cpu``; ``auto`` leaves jax's own
    choice (and whatever ``JAX_PLATFORMS`` says) alone."""
    if platform != "auto":
        import jax

        jax.config.update("jax_platforms", platform)


def require_devices(platform: str) -> DeviceInfo:
    """Initialise the backend and report what it found. Raises
    ``RuntimeError`` naming ``tpu.platform`` when the pinned platform has
    no device — a host without a chip must not serve from the CPU
    unannounced."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        raise RuntimeError(
            f"tpu.platform={platform} but JAX found no {platform} device: "
            f"{err}") from err
    found = devices[0].platform
    if platform != "auto" and found != platform:
        raise RuntimeError(
            f"tpu.platform={platform} but JAX initialised {found!r} "
            "(the backend was up before the platform was pinned)")
    return DeviceInfo(found, devices[0].device_kind, len(devices))
