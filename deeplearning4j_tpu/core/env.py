"""Process-level environment & flag tiers.

TPU-native equivalent of the reference's three config tiers (SURVEY.md §5.6):
  (a) per-model config  -> the dataclass config DSL (core/config.py)
  (b) process flags     -> ``ND4JSystemProperties`` / ``ND4JEnvironmentVars``
                           (canonical: org.nd4j.common.config.*) -> env vars here
  (c) runtime mutable   -> ``Nd4j.getEnvironment()`` proxying libnd4j
                           ``sd::Environment`` (canonical:
                           libnd4j/include/system/Environment.h) -> the
                           :class:`Environment` singleton here.

Unlike the reference there is no native singleton to proxy: flags that matter to
the compiler are forwarded to ``jax.config`` (e.g. ``debug_nans``); the rest are
plain process state read by our own runtime (profiling, verbosity, helper
selection).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

# Tier (b): environment variables understood by the framework. Mirrors the
# reference's ND4JEnvironmentVars vocabulary where a TPU equivalent exists.
ENV_VARS = {
    "DL4J_TPU_DTYPE": "default floating dtype: float32|bfloat16|float64",
    "DL4J_TPU_DEBUG": "1 enables debug mode (per-op logging)",
    "DL4J_TPU_VERBOSE": "1 enables verbose mode",
    "DL4J_TPU_DETERMINISTIC": "1 requests deterministic reductions",
    "DL4J_TPU_HELPERS": "0 disables accelerated (pallas) helpers",
    "DL4J_TPU_NAN_PANIC": "1 enables NaN checking on op outputs",
    "DL4J_TPU_PROFILING": "1 enables the op profiler",
    "DL4J_TPU_LOG_INIT": "0 silences backend init logging",
}


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v not in ("0", "false", "False", "")


class Environment:
    """Runtime-mutable global flags (tier c).

    Singleton accessed via :func:`get_environment` — the equivalent of
    ``Nd4j.getEnvironment()``.
    """

    _instance: Optional["Environment"] = None
    _lock = threading.Lock()

    def __init__(self) -> None:
        self.debug: bool = _env_flag("DL4J_TPU_DEBUG", False)
        self.verbose: bool = _env_flag("DL4J_TPU_VERBOSE", False)
        self.deterministic: bool = _env_flag("DL4J_TPU_DETERMINISTIC", False)
        self.allow_helpers: bool = _env_flag("DL4J_TPU_HELPERS", True)
        self.nan_panic: bool = _env_flag("DL4J_TPU_NAN_PANIC", False)
        self.inf_panic: bool = False
        self.profiling: bool = _env_flag("DL4J_TPU_PROFILING", False)
        self.log_initialization: bool = _env_flag("DL4J_TPU_LOG_INIT", True)
        self.default_dtype: str = os.environ.get("DL4J_TPU_DTYPE", "float32")
        self.extra: Dict[str, Any] = {}

    @classmethod
    def instance(cls) -> "Environment":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance

    # -- forwarding to jax.config where the compiler owns the behavior -------
    def enable_nan_panic(self, enabled: bool = True) -> None:
        import jax

        self.nan_panic = enabled
        jax.config.update("jax_debug_nans", enabled)

    def enable_x64(self, enabled: bool = True) -> None:
        import jax

        jax.config.update("jax_enable_x64", enabled)

    def reset(self) -> None:
        """Restore constructor defaults (used by tests)."""
        self.__init__()  # type: ignore[misc]


def get_environment() -> Environment:
    return Environment.instance()


# A compile cache only hits when the next process looks in the same place,
# so the default is one fixed, git-ignored path under the checkout.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Entry points (``chip_smoke.py``, ``bench.py``'s measurement child,
    ``__graft_entry__.py``) call this before their first compile; the
    library and the tests never do. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set JAX has already read it and nothing here overrides it; otherwise
    the cache lives in ``<checkout>/.jax_cache``."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
