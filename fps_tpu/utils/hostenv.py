"""Host-environment helpers: the CPU dry-run mesh and the compile cache.

JAX picks its platform when it is first imported, and a process that
has initialised a backend cannot widen itself to more devices. Anything
that needs an n-device CPU mesh on a machine with fewer real devices
(tests, the explicit CPU dry run in ``__graft_entry__``, the 8-device
tools) therefore sets up the environment BEFORE ``import jax`` — in this
process (tests/conftest.py) or in a child. This is the single home for
that recipe, and for where the persistent compilation cache lives.

Stdlib-only at import: tests/conftest.py and the jax-free tools load
this file by path.
"""

from __future__ import annotations

import os
import re

# Marker set in processes configured by cpu_mesh_env(); holds the device
# count they were configured with, so callers can tell "already running
# at this count — spawning again would loop" apart from "configured for a
# smaller mesh — spawning with a larger count is fine".
REEXEC_MARK = "_FPS_TPU_CPU_MESH_REEXEC"

_COUNT_FLAG = "--xla_force_host_platform_device_count"

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cpu_mesh_env(n_devices: int, env: dict | None = None) -> dict:
    """Return a copy of ``env`` set up for an ``n_devices`` CPU mesh.

    Forces JAX_PLATFORMS=cpu, force-sets (not merely appends) the
    host-platform device count — a pre-existing count of the wrong size
    must not win — and puts this checkout first on PYTHONPATH so a child
    imports the tree that spawned it.
    """
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p and p != _REPO_ROOT])
    flags = re.sub(rf"{_COUNT_FLAG}=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={n_devices}".strip()
    env[REEXEC_MARK] = str(n_devices)
    return env


def in_reexec() -> bool:
    return REEXEC_MARK in os.environ


def reexec_count() -> int:
    """Device count this process was configured with by cpu_mesh_env()
    (0 if it was not)."""
    try:
        return int(os.environ.get(REEXEC_MARK, "0"))
    except ValueError:
        return 0


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if the environment places it, else
    ``<checkout>/.jax_cache`` — or None on the CPU backend, where it
    stays off.

    Every entry point (``chip_smoke.py``, ``perfbench/run.py``,
    ``__graft_entry__``, the example CLIs) calls this before its first
    compile. When the environment sets the directory JAX reads it itself
    and no directory is set in code. Otherwise the default is one fixed
    directory inside the checkout — the path is part of JAX's cache key,
    so it is the same from every cwd and every process of a run, never a
    temp name — and every program is cached, whatever its compile time:
    under JAX's default one-second floor a program that compiles in about
    a second is written by whichever run happens to cross the floor, so a
    second run from the same checkout would still compile and still add
    entries. CPU runs are dry runs whose compiles are cheap, and XLA:CPU's
    loader logs a machine-feature error on every cache hit, so they are
    left uncached. Failures propagate: a cache that cannot be enabled is a
    set-up error, not a slower run.

    Wherever the cache is on, its key holds the programs' METADATA too
    (``jax_compilation_cache_include_metadata_in_key``). By default JAX
    keys an entry on the module with its debug info stripped, and the
    ``jax.named_scope`` paths a device trace is read by (``fps.ingest``,
    ``fps.ops/<route>``: docs/observability.md) live in that debug info:
    a tree whose scopes changed would load, from a cache an older tree
    filled, executables that carry the OLD scopes, and every reader of a
    new scope would find nothing (measured on the v5e, PR 24). The
    metadata holds source locations as well, so the checkout's root is
    cut from them (``jax_hlo_source_file_canonicalization_regex``): one
    tree keys the same wherever it is checked out. The price is that an
    edit compiles once more the programs traced through the lines it
    moved (3 of MF's 43 after an edit to driver.py: PERF.md, PR 24)."""
    import jax

    path = os.environ.get(_CACHE_ENV)
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(_REPO_ROOT + os.sep))
    return path
