"""Where the process runs and where it keeps compiled programs — the
two start-up facts every entry point reports (cli/run.py's hook for the
runner and the socket roles, chip_smoke.py).

Compile cache: a cold process recompiles every solver, apply, eval-width
and scatter-bucket program, and the chip tool keeps nothing between
calls except what the caller places.  `JAX_COMPILATION_CACHE_DIR` set →
JAX reads it itself and this module sets no directory in code.  Unset →
one fixed path inside the checkout (the path is part of JAX's cache key,
so a directory that moves never hits).  The minimum-compile-time
threshold drops to 0 so the many sub-second programs (apply, log
stacker, scatter buckets) are kept too.  Operation metadata (named
scopes, source lines) is part of the key: left out, as JAX's default
has it, a cache warmed by an older build hands back executables that
carry that build's metadata, and a device trace then shows none of the
`kps.*` scopes (measured on the chip, PERF.md PR 24).  The price is one
compile of a program after an edit that moves the lines it was traced
from.
"""

from __future__ import annotations

import os
from importlib import metadata

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> None:
    """Point JAX's persistent compilation cache somewhere durable; call
    before the first compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def compile_cache_dir() -> str:
    """The directory in use — JAX's own setting, whoever placed it."""
    import jax
    return jax.config.jax_compilation_cache_dir


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "absent"


def device_summary() -> dict:
    """The device as JAX reports it, plus the stack versions — first
    backend use happens here."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "jax": jax.__version__,
            "jaxlib": _version("jaxlib"),
            "libtpu": _version("libtpu")}


def startup_line(solver: str | None = None) -> str:
    d = device_summary()
    line = (f"[device] platform={d['platform']} kind={d['kind']!r} "
            f"count={d['count']} jax={d['jax']} jaxlib={d['jaxlib']} "
            f"libtpu={d['libtpu']} compile_cache={compile_cache_dir()}")
    if solver is not None:
        line += f" solver={solver}"
    return line
