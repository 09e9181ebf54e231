"""Environment helpers for CPU-only jax runs.

A chip belongs to one process at a time, so every surface that is CPU-only
by design (tests, multichip dryrun, soak workers) pins the CPU platform in
its OWN environment before jax is imported — a child that inherited an
accelerator default would contend for a chip its parent may hold. This module is the single source of truth for
that pin, shared by ``tests/conftest.py``, the tools and
``__graft_entry__.py``.

It must stay importable without jax side effects (conftest imports it before
jax) and with zero third-party imports.
"""

from __future__ import annotations

import os


def pin_cpu_env(env: dict, n_devices: int = 8) -> None:
    """Force the n-device virtual CPU platform in an env mapping.

    An already-present device-count flag is replaced (not kept), so the
    caller's requested n always wins."""
    import re

    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
    ).strip()
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env.setdefault("JAX_ENABLE_X64", "0")
    # The persistent CPU compile cache (tpu/jaxcache.py) makes XLA's AOT
    # loader log two C++ E-lines per reloaded executable (same-host feature
    # pseudo-mismatch, cosmetic). Only a pre-import env var reaches absl's
    # C++ logging init, so the pin sets it here; explicit settings win.
    # CAVEAT: level 3 mutes ALL C++ E-logs in the child. When debugging a
    # child failure, export ARKFLOW_XLA_VERBOSE=1 (or set
    # TF_CPP_MIN_LOG_LEVEL yourself) to see them (advisor r4, low).
    if env.get("ARKFLOW_XLA_VERBOSE") != "1":
        env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")


def cpu_child_env(n_devices: int = 8) -> dict:
    """A copy of os.environ pinned for a CPU-only jax child process."""
    env = dict(os.environ)
    pin_cpu_env(env, n_devices)
    return env
