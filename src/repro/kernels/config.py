"""Kernel execution config: interpret-mode resolution for Pallas calls.

Pallas kernels compile natively on TPU/GPU; on CPU they only run in
``interpret=True`` mode (the kernel body emulated through jax.lax).  The
ops wrappers historically hardcoded ``interpret=True``, which silently
pinned a compiled backend to the emulator.  ``resolve_interpret`` fixes
the default: resolved ONCE from the active JAX backend, overridable per
call (the explicit engine option), with a warning when a compiled
backend is forced back into interpret mode.

``enable_compile_cache`` places JAX's persistent compilation cache (the
one call every entry point makes before its first compile), and
``device_summary`` says where a run executes (the run log's manifest).
"""

from __future__ import annotations

import os
import pathlib
import warnings

import jax

#: backends with a compiled Pallas lowering (everything else interprets)
COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")

_default: bool | None = None  # resolved once per process
_warned = False  # fallback warning fires once per process


def default_interpret() -> bool:
    """True iff the active JAX backend needs interpret-mode Pallas (CPU)."""
    global _default
    if _default is None:
        _default = jax.default_backend() not in COMPILED_BACKENDS
    return _default


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a per-call ``interpret`` option to a concrete bool.

    ``None`` means "whatever the backend needs" (interpret on CPU,
    compiled on TPU/GPU).  An explicit ``True`` on a compiled backend is
    honored but warned about once — it usually means a debug knob leaked
    into a production run.
    """
    if interpret is None:
        return default_interpret()
    if interpret and not default_interpret():
        global _warned
        if not _warned:
            _warned = True
            warnings.warn(
                f"Pallas kernels forced to interpret mode on the compiled "
                f"{jax.default_backend()!r} backend — expect a large "
                f"slowdown (pass interpret=None to use the native path)",
                RuntimeWarning,
                stacklevel=3,
            )
    return interpret


#: the checkout this package runs from (src layout: <repo>/src/repro/...)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache at one fixed place.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and no other directory is set here.  Otherwise the cache is
    ``<repo>/.jax_cache`` when running from a checkout (a fixed path: the
    path is part of the cache key).  Every compile is cached, including
    the one-to-two-second Pallas kernel compiles.  A process that turned
    the cache off (``jax_enable_compilation_cache=False``, as the tests
    do) keeps it off.  Returns the cache directory, or None.
    """
    if not jax.config.jax_enable_compilation_cache:
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if not (REPO_ROOT / "pyproject.toml").is_file():
            return None
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_summary(engine: str | None = None,
                   kernel_interpret: bool | None = None) -> dict:
    """Where this process runs, for a run log's manifest: the device as
    JAX reports it and, for the ``pallas`` engine (the one that dispatches
    Pallas kernels), the kernel mode its ``kernel_interpret`` option
    resolves to."""
    devices = jax.devices()
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if engine == "pallas":
        itp = (default_interpret() if kernel_interpret is None
               else kernel_interpret)
        out["kernel_mode"] = "interpret" if itp else "compiled"
    return out
