"""Small shared helpers (reference: `alphatriangle/utils/helpers.py:12-108`)."""

import logging
import os
import random
from pathlib import Path

import jax
import numpy as np

logger = logging.getLogger(__name__)


def enforce_platform(device: str = "auto") -> None:
    """Pin the JAX platform BEFORE any backend initializes.

    `"auto"` leaves the choice to JAX, which reads `JAX_PLATFORMS`
    itself: an explicit `JAX_PLATFORMS=cpu` means CPU, no variable means
    the accelerator when there is one. Any other value pins that
    platform, so a run that asked for `"tpu"` and finds none fails at
    backend start-up instead of training on the host.
    """
    if device == "auto":
        return
    if device == "cpu":
        # Children of a CPU-pinned run (drivers, replicas) stay on CPU.
        os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", device)


def compilation_cache_root() -> str:
    """The one directory every compile artifact lives under: the XLA
    persistent cache itself and the AOT executables in `aot/`
    (compile_cache.py). `JAX_COMPILATION_CACHE_DIR` places it from
    outside; otherwise it is a fixed path inside the checkout, because
    the path is part of JAX's cache key and a directory that moves
    between runs never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parents[2] / ".cache" / "jax"
    )


def enable_persistent_compilation_cache() -> None:
    """Cache compiled XLA executables on disk across processes.

    Every entry point calls this before its first compile; it resolves
    the backend itself (initializing it), so call it after anything
    that must precede backend start-up. The persistent cache keys
    serialized executables by HLO + backend, so repeat invocations skip
    straight to dispatch.

    ACCELERATOR BACKENDS ONLY: XLA:CPU's cached AOT results record
    compile-time tuning pseudo-features (`+prefer-no-scatter`, ...)
    that fail the host feature check on reload, and a reloaded learner
    step returns its donated state unchanged (tests/conftest.py) — and
    CPU compiles are cheap anyway.
    """
    if os.environ.get("ALPHATRIANGLE_NO_COMPILE_CACHE") == "1":
        return  # operator opt-out (e.g. suspected stale/corrupt cache)
    if jax.default_backend() == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # With the variable set JAX has already read it into this
        # option; no code names another directory.
        jax.config.update(
            "jax_compilation_cache_dir", compilation_cache_root()
        )


def get_device(preference: str = "auto") -> jax.Device:
    """Pick the compute device: TPU > GPU > CPU (reference picked CUDA>MPS>CPU).

    An explicit preference that cannot be satisfied raises (RuntimeError
    from `jax.devices(platform)`) — it never silently falls back to CPU.
    """
    if preference not in ("auto", "tpu", "gpu", "cpu"):
        raise ValueError(f"unknown device preference: {preference}")
    if preference != "auto":
        return jax.devices(preference)[0]
    return jax.devices()[0]


def set_random_seeds(seed: int) -> jax.Array:
    """Seed python/numpy and return the root JAX PRNG key.

    JAX randomness is functional: unlike the reference's global
    torch/cuda seeding (`helpers.py:51-77`), all device-side randomness
    flows from this key explicitly.
    """
    random.seed(seed)
    np.random.seed(seed)
    return jax.random.PRNGKey(seed)


def format_eta(seconds: float | None) -> str:
    """Seconds → 'Xd HH:MM:SS' (reference: `helpers.py:80-95`)."""
    if seconds is None or not np.isfinite(seconds) or seconds < 0:
        return "N/A"
    seconds = int(seconds)
    days, rem = divmod(seconds, 86400)
    hours, rem = divmod(rem, 3600)
    minutes, secs = divmod(rem, 60)
    if days > 0:
        return f"{days}d {hours:02d}:{minutes:02d}:{secs:02d}"
    return f"{hours:02d}:{minutes:02d}:{secs:02d}"


def normalize_color_for_matplotlib(color_tuple_0_255: tuple) -> tuple:
    """(r,g,b) in 0..255 → 0..1 floats (reference: `helpers.py:98-108`)."""
    return tuple(max(0.0, min(1.0, c / 255.0)) for c in color_tuple_0_255)
