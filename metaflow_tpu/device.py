"""Which device this process computes on, and where it keeps its
compiled programs.

JAX falls back to the CPU with a warning when `JAX_PLATFORMS` is unset
and the TPU does not come up. A trainer or a server that took that
fallback would be quiet, slow and passing, so every path that picks a
kernel or starts a model asks `platform()` here: the answer is 'tpu',
or 'cpu' for a process that was started pinned to the CPU
(`JAX_PLATFORMS=cpu`, as the tests are). Anything else is an error.

A chip belongs to one process at a time. `refuse_chip_sharing` is what
the launchers of several local processes (a gang's ranks, a fleet's
replica workers) call before they start them.
"""

import os
import sys

from .exception import TpuFlowException

# one fixed place inside the checkout (`.tpuflow/` is git-ignored): the
# directory is part of the cache key, so a path that moves never hits
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".tpuflow", "jax_cache")


class NoAcceleratorError(TpuFlowException):
    headline = "No TPU"


def cpu_pinned():
    """True when this process was explicitly held to the CPU. Reads the
    JAX config once JAX is imported (tests pin through
    `jax.config.update` too), the environment before that — so a
    launcher can ask without importing JAX."""
    jax = sys.modules.get("jax")
    pinned = (jax.config.jax_platforms if jax is not None
              else os.environ.get("JAX_PLATFORMS"))
    return (pinned or "").strip().lower() == "cpu"


def platform():
    """'tpu', or 'cpu' when the process is CPU-pinned; raises otherwise.
    Initialises the backend — call it from the process that computes,
    never from one that is about to start such a process."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return "tpu"
    if backend == "cpu" and cpu_pinned():
        return "cpu"
    raise NoAcceleratorError(
        "JAX's default backend is %r, not 'tpu', and this process was "
        "not pinned to the CPU. Either the TPU failed to initialise "
        "(JAX then falls back to the CPU with only a warning) or "
        "another process holds the chip. Set JAX_PLATFORMS=cpu to run "
        "on the CPU on purpose." % backend)


def on_tpu():
    return platform() == "tpu"


def describe():
    """The device block every result carries, as JAX reports it."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peak_bytes_in_use():
    """Largest peak_bytes_in_use over the local devices, or None where
    the backend does not report memory (XLA:CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def refuse_chip_sharing(n_processes, what):
    """Several local processes cannot share the host's TPU: the first
    owns it and the others fail or hang on the device lock. Raises
    unless they will be CPU-pinned (they inherit this environment)."""
    if int(n_processes) > 1 and not cpu_pinned():
        raise TpuFlowException(
            "%s would start %d processes on this host, and a TPU chip "
            "belongs to one process at a time: every process after the "
            "first would fail or hang on the device lock. Run one "
            "process (it drives every chip of the host), or pin the "
            "processes to the CPU with JAX_PLATFORMS=cpu."
            % (what, int(n_processes)))


def setup_compile_cache():
    """Turn on JAX's persistent compilation cache; entry points call
    this once before they compile, nothing calls it at import. Where
    JAX_COMPILATION_CACHE_DIR is set JAX already uses it and no other
    directory is set here; otherwise the cache goes to `.tpuflow/
    jax_cache` in the checkout, through the same variable, so that JAX
    reads it when it is imported (a task that never imports JAX pays
    nothing) and child processes inherit it. A CPU-pinned process gets
    no cache of its own accord: XLA:CPU logs an error block for every
    executable it loads back. Returns the directory.

    The cache's key includes the programs' metadata (scope names, source
    lines). JAX leaves it out by default, and an executable loaded from
    the cache keeps the names it was compiled with: a profile would then
    show the scope names of whichever commit compiled the program first
    (seen on the chip, PERF.md PR 24: every program hit the parent's
    entries and the trace held none of this commit's scopes). The price
    is one recompilation after a change that moves lines."""
    _configure("jax_compilation_cache_include_metadata_in_key",
               "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY", "true", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if not cpu_pinned():
        _configure("jax_compilation_cache_dir",
                   "JAX_COMPILATION_CACHE_DIR", _CACHE_DIR, _CACHE_DIR)
    return _CACHE_DIR


def _configure(option, variable, text, value):
    """Set a JAX option through its environment variable, unless that is
    set already, so that JAX reads it when it is imported and child
    processes inherit it; a JAX imported before this call has read the
    environment and is told directly."""
    if os.environ.get(variable):
        return
    os.environ[variable] = text
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update(option, value)


def watch_compiles():
    """Count this process's XLA compilations from here on; returns the
    live counters (compile_s includes the time to load a cached one)."""
    from jax import monitoring

    counts = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
              "cache_misses": 0}

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1
            counts["compile_s"] += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return counts
