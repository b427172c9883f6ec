"""Compile-time hygiene: persistent compilation cache + trace audit
(DESIGN.md §2.10).

Every benchmark lane and the serve CLI re-trace the same handful of
programs on every process start; on CPU the XLA compile time dwarfs the
first-step run time.  ``enable_compile_cache`` turns on JAX's persistent
compilation cache so repeated invocations (CI re-runs, benchmark
sweeps, serve restarts) hit disk instead of recompiling:

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()            # benchmarks/results/.jax_cache
    enable_compile_cache("/tmp/cc")   # explicit directory

``JAX_COMPILATION_CACHE_DIR`` in the environment wins over both the
argument and the default, so operators can redirect the cache without
touching code.

``trace_audit`` is the measurement side of the same hygiene story: a
context manager that counts backend compiles and persistent-cache hits,
read from the program's one ``jax.monitoring`` listener
(``repro.tracing``), used by ``benchmarks/kernel_bench.py`` to record
trace counts next to wall times and by the O(1)-trace gates in
``tests/test_fused_matmul.py``.
"""
from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro import tracing

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    "benchmarks", "results", ".jax_cache")

# Curated XLA flags for reproducible CPU benchmarking.  Kept minimal on
# purpose: the only flag we add by default pins the intra-op threadpool
# so wall times are comparable across CI runners; everything else stays
# at XLA defaults (the fused kernels must win on merit, not flag tuning).
XLA_BENCH_FLAGS = ("--xla_cpu_multi_thread_eigen=false",)


def xla_flags_env(extra: tuple[str, ...] = ()) -> str:
    """Merged ``XLA_FLAGS`` value: existing env flags + curated bench
    flags + ``extra``, deduplicated, order-preserving."""
    flags: list[str] = []
    for chunk in (os.environ.get("XLA_FLAGS", "").split(),
                  XLA_BENCH_FLAGS, extra):
        for f in chunk:
            if f and f not in flags:
                flags.append(f)
    return " ".join(flags)


def enable_compile_cache(cache_dir: str | None = None) -> str:
    """Turn on the persistent compilation cache and return its path.

    Resolution order: ``JAX_COMPILATION_CACHE_DIR`` env var, then the
    ``cache_dir`` argument, then ``benchmarks/results/.jax_cache``.
    The min-compile-time / min-entry-size thresholds are zeroed so even
    the sub-second CPU test programs persist — without this the cache
    silently ignores everything the repro suite compiles.
    """
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir \
        or _DEFAULT_DIR
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax memoizes the cache-enabled decision at the FIRST compile of
    # the process (compilation_cache.is_cache_used); enabling the cache
    # after any jit call would otherwise be a silent no-op, so drop
    # that memo and let the next compile re-check the config.
    compilation_cache.reset_cache()
    return d


@dataclass
class TraceCounts:
    """Mutable tally filled in while a ``trace_audit`` block runs."""

    compiles: int = 0
    cache_hits: int = 0
    compile_secs: float = 0.0

    @property
    def traced_programs(self) -> int:
        """Distinct lowered computations: the backend-compile event
        fires once per program whether it compiled fresh or came out of
        the persistent cache (a hit additionally bumps ``cache_hits``),
        so this is just the compile-event count."""
        return self.compiles

    @property
    def fresh_compiles(self) -> int:
        """Programs actually compiled by XLA (not served from the
        persistent cache)."""
        return self.compiles - self.cache_hits


@contextlib.contextmanager
def trace_audit():
    """Count backend compiles (and persistent-cache hits) in a block.

    >>> with trace_audit() as counts:
    ...     jax.jit(fn)(x)
    >>> counts.compiles
    1

    The counts are the differences of ``repro.tracing.runtime_counts``
    across the block: every thread's compiles in that time count.
    """
    start = tracing.runtime_counts()
    counts = TraceCounts()
    try:
        yield counts
    finally:
        end = tracing.runtime_counts()
        counts.compiles = end.compiles - start.compiles
        counts.compile_secs = end.compile_secs - start.compile_secs
        counts.cache_hits = end.cache_hits - start.cache_hits
