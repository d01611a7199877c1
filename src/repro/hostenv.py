"""Pre-jax environment setup. jax-free on purpose: callers (launch scripts,
tests/conftest.py) must run this BEFORE anything imports jax, because XLA
reads XLA_FLAGS exactly once at backend initialization and jax reads its
``JAX_*`` configuration variables when it is imported."""
import os
from pathlib import Path

#: the checkout root (``src/repro/hostenv.py`` -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[2]


def force_host_devices() -> None:
    """Translate ``STADI_HOST_DEVICES=N`` into N forced XLA host platform
    devices (CPU SPMD). No-op when unset or 0."""
    n = os.environ.get("STADI_HOST_DEVICES", "")
    if n not in ("", "0"):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory and return
    it. A ``JAX_COMPILATION_CACHE_DIR`` set from outside is left alone;
    otherwise the cache sits at ``<checkout>/.jax_cache``. The path is
    fixed (never a temp name, pid or time) so that every process of the
    checkout finds what an earlier one compiled."""
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(CHECKOUT / ".jax_cache"))
