"""Persistent XLA compilation cache for the serving tier.

A TPU executable takes tens of seconds to compile; with the persistent
cache each (model, shape, dtype) bucket compiles once per machine instead
of once per process, so engine restarts and benchmark reruns start serving
at full speed immediately.

The reference engine has no analog (an interpreted CPU data plane never
compiles); this is TPU-native operational hygiene, same motivation as the
executable warm-up hook (SURVEY.md §7.5: keep the compiled model fed, never
stall steady-state on a compile).

Placement comes from outside. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX's own handling of it is the only thing that places the cache — this
module sets no directory. Where it is not, the cache goes to a fixed path
(the path is part of the cache key, so a directory that moves never hits):
``.jax_cache`` next to the package for accelerator backends, a
host-feature-keyed ``.jax_cache_cpu-<hash>`` for the CPU backend.
``ARKFLOW_JAX_CACHE=0`` disables.

A program's key does not depend on where its kernels stand in their files
(``jax_traceback_in_locations_limit`` 0, set with the cache).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger("arkflow.tpu")

_configured: Optional[str] = None
_attempted = False


def _host_key() -> str:
    """Short stable hash of this host's CPU feature set.

    XLA:CPU AOT executables are ISA-specific; keying the CPU cache dir by
    the cpuinfo flags guarantees a repo checked out on different silicon
    starts a fresh cache instead of loading foreign AOT code (SIGILL risk).
    """
    import hashlib
    import platform

    feats = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    feats += line
                    break
    except OSError:
        pass
    return hashlib.sha256(feats.encode()).hexdigest()[:10]


def enable_persistent_cache() -> Optional[str]:
    """Idempotently turn on JAX's on-disk compilation cache.

    Returns the cache directory in use, or None when disabled/unavailable.
    Must run before the first compile to help that compile; safe any time.
    """
    global _configured, _attempted
    if _attempted:
        return _configured
    _attempted = True
    if os.environ.get("ARKFLOW_JAX_CACHE", "1") == "0":
        return None
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # placed from outside: JAX read the variable itself, and no code
        # here may move the cache elsewhere
        path = jax.config.jax_compilation_cache_dir
    else:
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        if "cpu" in os.environ.get("JAX_PLATFORMS", "").lower():
            # CPU backend. XLA:CPU AOT entries embed the build machine's
            # feature set and the loader re-checks it against a host list
            # that never includes XLA's prefer-no-gather/scatter
            # pseudo-features — so every reload logs two C++-level E lines
            # (cosmetic on the same host; a cross-host reload risks SIGILL).
            # The cache is worth ~9 min/run of recompiles to the test suite,
            # so keep it on, keyed by host CPU features so a copied repo on
            # different silicon recompiles, and silence the loader lines via
            # TF_CPP_MIN_LOG_LEVEL (set before jax import by
            # cleanenv.pin_cpu_env).
            path = os.path.join(repo_root, f".jax_cache_cpu-{_host_key()}")
        else:
            path = os.path.join(repo_root, ".jax_cache")
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:  # read-only checkout: serve without the cache
            logger.warning("persistent compilation cache unavailable: %s", e)
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable regardless of compile time (jax's default
    # threshold of 1s would skip the small bucket-grid executables that
    # recompile on every engine restart)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no Python frame in an operation's location: a Pallas kernel's body is
    # serialised WITH its locations into the program the cache keys on, and
    # with frames in them (jax's default: ten) a line added anywhere above a
    # kernel, in its own file or in any caller's, re-keys every program that
    # runs it. A kernel that fails to compile still names itself (its
    # ``name=``) and the operation at fault; only the line is not said
    jax.config.update("jax_traceback_in_locations_limit", 0)
    _configured = path
    logger.debug("persistent XLA compilation cache at %s", path)
    return _configured


def cache_info() -> dict:
    """JSON-able snapshot of the persistent compile cache — the shape
    tuner's warm phase reports through this so an operator can tell whether
    a retune's compiles were real or cache replays."""
    if not _configured:
        return {"enabled": False}
    try:
        entries = sum(1 for e in os.scandir(_configured) if e.is_file())
    except OSError:
        entries = None
    return {"enabled": True, "dir": _configured, "entries": entries}
