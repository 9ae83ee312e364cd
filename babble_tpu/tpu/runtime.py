"""Process-level JAX set-up for the device backend: where compiled
programs are cached, and which platform the engines really run on.

Both are decided once, where a device backend is first chosen
(`node.Core` for consensus_backend="tpu", chip_smoke.py, the bench
mains), never at import: nothing here runs at module load, and
`enable_compile_cache` initializes no backend. The same place hands the
program's spans to the profiler (`annotate_spans`).

One process holds a chip. JAX registers its TPU client to fail quietly,
so a process that asks for the chip and loses it would otherwise carry
on on XLA:CPU under the name "tpu" — `require_tpu` turns that into an
error at start.
"""

from __future__ import annotations

import os
from typing import Dict

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# parent of the babble_tpu package directory: a fixed path, because the
# directory is part of what a later process must find again
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. An externally set JAX_COMPILATION_CACHE_DIR is left alone
    (JAX reads it itself); otherwise the cache lives at
    <repo root>/.jax_cache. The write thresholds are lowered either way
    so the sub-second `step` / `_pack_results` programs are kept too."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def annotate_spans() -> None:
    """Put the program's spans on the device trace's clock: from here on
    every `obs.span(...)` also opens a `jax.profiler.TraceAnnotation`
    named "babble.<span>", so that a `jax.profiler` trace shows them in
    its host plane beside the device's operations. With no profiler
    session an annotation is a flag test. Process-wide, like the
    profiler; the tracer itself (obs/trace.py) never imports jax."""
    import jax

    from ..obs.trace import SpanTracer

    SpanTracer.annotator = jax.profiler.TraceAnnotation


def device_info() -> Dict[str, object]:
    """The devices JAX serves this process from (initializes the
    backend): platform, device_kind and count, as /stats reports them."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def tpu_init_error() -> str:
    """Why this process has no TPU backend, in JAX's own words ("" when
    it has one)."""
    import jax

    try:
        jax.devices("tpu")
    except RuntimeError as e:
        return str(e)
    return ""


def cpu_pinned() -> bool:
    """True when the process was explicitly pinned to XLA:CPU
    (JAX_PLATFORMS=cpu, or the same through jax.config as
    tests/conftest.py does) — the test and CI mode."""
    import jax

    pinned = jax.config.jax_platforms or ""
    return "cpu" in [p.strip() for p in pinned.split(",")]


def require_tpu() -> Dict[str, object]:
    """device_info(), or RuntimeError when the platform is not a TPU and
    nobody pinned the CPU on purpose."""
    info = device_info()
    if info["platform"] != "tpu" and not cpu_pinned():
        raise RuntimeError(
            f"the tpu consensus backend found platform "
            f"{info['platform']!r}, not a TPU: {tpu_init_error()} "
            f"(a chip belongs to one process; set JAX_PLATFORMS=cpu to run "
            f"the device engines on XLA:CPU on purpose)"
        )
    return info
