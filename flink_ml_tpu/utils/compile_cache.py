"""Persistent XLA compilation cache — warm-process startup parity.

The reference rides the JVM: a Flink job's operators are bytecode that
starts in milliseconds, every run.  The TPU framework's equivalent
startup tax is XLA compilation: the first fit of a process pays the
HLO->LLO compile of the fused training program, seconds against a
sub-second steady fit.  JAX ships a persistent compilation cache that keys
compiled executables by (HLO, compile options, backend) and replays them
across processes; enabling it turns every warm process's compile into a
disk read, which is the closest a compiled-accelerator framework gets to
JVM startup.

Where the cache lives is decided ONCE, at package import, from the
environment alone (no backend is initialized):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads its own variable; this
  module never touches ``jax_compilation_cache_dir``.  A sealed machine
  whose home directory dies with it keeps its cache wherever the operator
  mounted one.
* unset — one fixed path inside the checkout, ``<repo root>/.jax_cache``
  (the path is part of JAX's cache key, so it must never move).  Skipped
  when ``JAX_PLATFORMS`` names only ``cpu``: XLA:CPU AOT replay logs a
  machine-feature mismatch error per loaded executable (jax 0.9.0's
  ``+prefer-no-scatter`` pseudo-features), and the compile the cache
  exists to skip is the TPU one.  A CPU run opts in by setting
  ``JAX_COMPILATION_CACHE_DIR``.
* ``FMT_COMPILE_CACHE=off`` — no persistent cache at all (tests, chaos
  workers).

Thresholds are set to cache everything (min entry size / min compile time
both disabled) — a pipeline of small stages benefits exactly as much as
one big program.  ``scripts/compile_cache_warmstart.py`` measures the
effect: the same fit in two fresh subprocesses against a fresh cache dir,
cold vs warm ``first_fit_s``.
"""

from __future__ import annotations

import os
import threading
import warnings

#: the directory this module pointed JAX at (None: JAX's own env var owns
#: the choice, or the cache is off)
_enabled_dir: str | None = None

#: the default location: ``<repo root>/.jax_cache`` (gitignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def _off() -> bool:
    from flink_ml_tpu.utils import knobs

    return knobs.knob_str("FMT_COMPILE_CACHE").strip().lower() == "off"


def _cpu_only() -> bool:
    """Does ``JAX_PLATFORMS`` (``jax_platforms``) name only ``cpu``?  Read
    from the config string — no backend is initialized."""
    import jax

    names = [p.strip() for p in (jax.config.jax_platforms or "").split(",")
             if p.strip()]
    return bool(names) and all(p == "cpu" for p in names)


def cache_dir() -> str | None:
    """The directory the persistent cache writes to (None when off) —
    what replica spawn propagates to children and the chip smoke counts
    entries in."""
    if _off():
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _enabled_dir


# -- batch-shape bucketing ----------------------------------------------------
#
# The persistent cache above replays compiled executables across PROCESSES;
# the ladder below bounds how many executables exist WITHIN a process when
# batch sizes vary.  Inference pads every batch's row count up to a bucket
# before dispatch, so the jit cache keys on a small fixed set of shapes
# instead of one shape per unique request size.  One ladder is shared by
# the staged mapper applies, the fused pipeline plans, and the serving
# runtime's coalesced micro-batches (``flink_ml_tpu/serving/``) — a row
# count the server has already warmed can never recompile when the same
# count arrives through a plain ``transform``.
#
# The rungs start at 1 (a single-row serving request pads to 1 row, not to
# a 256-row training-shaped bucket) and double past the top so arbitrarily
# large batches stay power-of-two bounded.  256 is a rung on purpose: the
# pre-ladder rule padded every <=256-row batch to 256, so keeping it makes
# the ladder exactly the old rule for n > 128 (no padded-compute
# regression on existing batch sizes) and strictly cheaper below.

#: the fixed bucket rungs; sizes beyond the top double from 512
BATCH_BUCKET_LADDER = (1, 8, 32, 128, 256, 512)

_BUCKETS_SEEN: set = set()
_BUCKETS_LOCK = threading.Lock()


def bucket_batch_rows(n: int, row_multiple: int = 1) -> int:
    """The padded row count for an ``n``-row batch: the smallest ladder
    bucket >= n (doubling past the top rung), rounded up to
    ``row_multiple`` (the data-axis size for mesh-sharded applies).

    First use of a (bucket, row_multiple) shape in the process bumps the
    ``compile_cache.bucket_new`` counter (the compile-bearing event —
    a fresh padded shape means a fresh XLA program for whatever function
    consumes it); repeats bump ``compile_cache.bucket_reuse``.  Across any
    mix of request sizes, ``bucket_new`` is bounded by the ladder length
    plus the doublings the largest batch needed — the recompile-flatness
    contract the serving bench asserts.
    """
    n = max(int(n), 1)
    b = 0
    for rung in BATCH_BUCKET_LADDER:
        if rung >= n:
            b = rung
            break
    if not b:
        b = BATCH_BUCKET_LADDER[-1]
        while b < n:
            b *= 2
    if row_multiple > 1:
        b = -(-b // row_multiple) * row_multiple
    with _BUCKETS_LOCK:
        new = (b, row_multiple) not in _BUCKETS_SEEN
        if new:
            _BUCKETS_SEEN.add((b, row_multiple))
    from flink_ml_tpu import obs

    obs.counter_add(
        "compile_cache.bucket_new" if new else "compile_cache.bucket_reuse"
    )
    return b


def reset_bucket_stats() -> None:
    """Forget which buckets this process has seen (tests)."""
    with _BUCKETS_LOCK:
        _BUCKETS_SEEN.clear()


def enable_compilation_cache() -> str | None:
    """Resolve the persistent compilation cache (idempotent; called at
    package import).  Returns the directory in use, or None when off.

    See the module docstring for the resolution order.  Runs before any
    backend exists, so every compile of the process — including a
    ``ModelServer`` load that jits before any mesh is built — finds the
    cache already configured."""
    global _enabled_dir
    try:
        import jax
    except ImportError:
        # pure-host tooling (the static analyzer's CLI) imports the
        # package in images without JAX; no backend means no cache
        return None

    if _off():
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    # cache every program regardless of size or compile time: the
    # pipeline API compiles many small per-stage programs whose compiles
    # add up; bound on-disk growth (JAX evicts LRU past the max size)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", 2 * 1024**3)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return cache_dir()
    if _enabled_dir is not None:
        return _enabled_dir
    if _cpu_only():
        return None
    try:
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    except OSError as e:
        # a read-only checkout must never make the package unimportable
        warnings.warn(
            f"persistent compilation cache disabled: cannot create "
            f"{DEFAULT_CACHE_DIR!r} ({e}); set JAX_COMPILATION_CACHE_DIR "
            "to a writable directory or FMT_COMPILE_CACHE=off to silence "
            "this",
            stacklevel=2,
        )
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    _enabled_dir = DEFAULT_CACHE_DIR
    return _enabled_dir
