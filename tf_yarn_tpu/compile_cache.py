"""Where compiled programs are kept between processes.

Every task the launcher starts compiles its own programs, and a machine
may be thrown away after one command, so a cold start is paid again and
again unless JAX's persistent compilation cache sits at a place that
does not move: the directory is part of every entry's key.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself; nothing
  here sets another directory.
* unset: the cache goes to :data:`CHECKOUT_CACHE_DIR`, one fixed
  directory next to the package — not under the working directory (the
  launcher moves tasks into per-run directories), a temp name, a pid or
  a clock. The variable is exported so that child processes agree.

A process that compiles calls `enable` when it starts
(`tasks/_bootstrap.init_runtime` for every task program; `bench.py`,
`benchmarks/run.py` and `chip_smoke.py`'s children for themselves). A
parent that only starts such processes calls `export` and stays off JAX.
"""

from __future__ import annotations

import os
from typing import Dict

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache",
)

# JAX's own monitoring events -> the counters `stats` reports.
_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile_cache/hits",
    "/jax/compilation_cache/cache_misses": "compile_cache/misses",
}
_listening = False


def export() -> str:
    """Fix the directory in the environment, for this process's own
    `import jax` and for every child. Touches no JAX."""
    return os.environ.setdefault(ENV_CACHE_DIR, CHECKOUT_CACHE_DIR)


def enable() -> str:
    """`export`, then make JAX use the directory and count what the
    cache answers. Imports JAX but starts no backend, so it takes no
    chip. Safe to call again."""
    global _listening
    directory = export()
    import jax

    if jax.config.jax_compilation_cache_dir != directory:
        # JAX was imported before the variable was set.
        jax.config.update("jax_compilation_cache_dir", directory)
    if not _listening:
        # JAX keeps listeners for the life of the process; so does this.
        _listening = True
        jax.monitoring.register_event_listener(_count)
    return directory


def _count(event: str, **_kwargs) -> None:
    name = _COUNTERS.get(event)
    if name is not None:
        from tf_yarn_tpu import telemetry

        telemetry.get_registry().counter(name).inc()


def entries() -> int:
    """Programs the directory holds now. Touches no JAX."""
    try:
        return sum(
            1 for entry in os.scandir(export())
            # A size-bounded cache keeps a last-access stamp beside each.
            if entry.is_file() and not entry.name.endswith("-atime")
        )
    except FileNotFoundError:
        return 0


def stats() -> Dict[str, object]:
    """The directory, what it holds, and how many of this process's
    compiles it answered (`hits`) or had to be compiled (`misses`)."""
    from tf_yarn_tpu import telemetry

    snapshot = telemetry.get_registry().snapshot()
    return {
        "dir": export(),
        "entries": entries(),
        "hits": int(snapshot.get("compile_cache/hits", 0)),
        "misses": int(snapshot.get("compile_cache/misses", 0)),
    }
