"""Runtime policy shared by every Pallas launch: kernel mode and compile cache.

The backend decides the kernel mode, with no switch: launchers default to
``interpret=None`` and resolve it here, compiled on a TPU backend and
interpreted on the CPU one (tests pin the CPU with ``JAX_PLATFORMS=cpu``).
Any other backend is an error, never a silent fallback.  An explicit
per-call ``interpret=`` still wins, which is how a test compiles a kernel
for a described TPU from a CPU host.

``enable_compile_cache`` is the one place that points JAX's persistent
compilation cache somewhere: ``$JAX_COMPILATION_CACHE_DIR`` when it is
set, else the fixed ``<repo>/.cache/jax``.
"""
from __future__ import annotations

import os
from typing import Optional

#: the persistent compile cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset — a fixed path, because the path is part of the cache key
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".cache", "jax")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit per-call ``interpret=`` wins; ``None`` follows the
    backend: compiled on TPU, interpreted on CPU, an error elsewhere."""
    if interpret is not None:
        return bool(interpret)
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile on a TPU backend and are interpreted on "
        f"the CPU one; the default backend {backend!r} is neither")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
