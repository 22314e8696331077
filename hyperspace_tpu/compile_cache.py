"""Persistent XLA compilation cache wiring (ROADMAP item 5, pillar 1).

Compile time is the dominant unmeasured cost in this stack: one short
run logged ``jax/recompiles=1532`` with 22.5 s of ``jax/compile_s``,
every serve (bucket, k, scan_mode, precision, nprobe) combination is a
fresh executable compiled on first hit, and the historical rc=124
bench/multichip artifact losses were compile-dominated.  Every one of
those compiles is deterministic — the same HLO on the same backend
produces the same executable — so a process restart re-paying them is
pure waste.  This module points JAX's on-disk compilation cache
(``jax_compilation_cache_dir``) at a persistent directory so run #2 of
anything deserializes executables instead of invoking XLA.

**Where the cache lives** (:func:`resolve_dir`): where
``JAX_COMPILATION_CACHE_DIR`` says, if it is set — jax reads that
variable itself, and this module then sets no other directory (an
explicit path that disagrees with it is a :class:`ValueError`, never a
silent second cache).  With the variable unset: an explicit
``compile_cache_dir=`` flag, else the ``HYPERSPACE_COMPILE_CACHE`` env
var, else the fixed default ``<repo>/.cache/jax_compile`` beside the
graph-prep cache — fixed, because the path is part of what a caller
must repeat to hit the cache again.  The cache is **on by default**;
the value ``0`` (or ``false``/``no``/``off``) in the flag or in
``HYPERSPACE_COMPILE_CACHE`` disables it wherever it would have lived.
A directory that cannot be created or written is a loud
:class:`ValueError` (the CLIs turn it into a clean usage exit) — a
silently-dead cache would re-create exactly the cold-start cliff this
exists to kill.

**Cache-everything policy**: ``jax_persistent_cache_min_compile_time_
secs`` is set to 0 and the min-entry-size check is disabled, so even
the sub-second executables (the serve bucket ladder is made of them)
persist — disk is cheap next to a p99 cliff.

**Telemetry**: the shared ``jax.monitoring`` hook
(:func:`hyperspace_tpu.telemetry.registry.install_jax_monitoring_hook`,
armed by the package's import) counts ``jax/compile_cache_hit``
(executables deserialized from the cache — the backend compile never
ran) and ``jax/compile_cache_miss`` (backend compiles while the cache was
enabled — each writes a new entry).  Both ride into every JSONL record,
``telemetry_summary``, and bench artifact through the existing
registry, so cache hit rates are visible for free
(docs/observability.md).

Wired into ``__graft_entry__.py``, ``cli/train.py``, ``cli/serve.py``
and ``bench.py`` — the four process entry points whose restarts pay
cold compiles.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "HYPERSPACE_COMPILE_CACHE"
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_OFF_VALUES = ("0", "false", "no", "off")

# activation state: the directory the cache was pointed at (None = not
# activated / disabled) plus, once this module has written jax's cache
# config, the value it replaced (tests/conftest.py points the suite at
# its own cache — deactivate must restore it, not blank it).
_state: dict = {"dir": None, "prev": None, "changed": False}


def _set_jax_dir(jax, d: Optional[str]) -> None:
    """Write jax's cache-dir config, remembering the first value
    replaced, and drop jax's in-process file-cache singleton when one
    may exist: it is initialized once for the FIRST directory used, so
    re-pointing the config alone would keep writing to the old dir."""
    prev = jax.config.jax_compilation_cache_dir
    if not _state["changed"]:
        _state["prev"], _state["changed"] = prev, True
    jax.config.update("jax_compilation_cache_dir", d)
    if prev is not None:
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()


def default_dir() -> str:
    """``<repo>/.cache/jax_compile`` — beside the graph-prep cache
    (``data/prep_cache.py``), under the checkout the artifacts live in."""
    pkg = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(pkg), ".cache", "jax_compile")


def resolve_dir(flag: Optional[str] = None) -> Optional[str]:
    """The cache directory to use, or None when disabled (module
    docstring, "Where the cache lives").  ``flag`` is the CLI's
    ``compile_cache_dir=`` value (None = not given).  Raises
    :class:`ValueError` for an explicit path that disagrees with
    ``JAX_COMPILATION_CACHE_DIR``."""
    v = flag if flag not in (None, "") else os.environ.get(ENV_VAR, "")
    if v and v.strip().lower() in _OFF_VALUES:
        return None
    jax_dir = os.environ.get(JAX_ENV_VAR, "")
    if jax_dir:
        if v and os.path.abspath(v) != os.path.abspath(jax_dir):
            raise ValueError(
                f"compile_cache_dir={v!r} disagrees with "
                f"{JAX_ENV_VAR}={jax_dir!r} — the variable decides where "
                "the cache lives; drop one of the two")
        return jax_dir
    return v or default_dir()


def activate(flag: Optional[str] = None) -> Optional[str]:
    """Turn JAX's persistent compilation cache on at the resolved dir.

    Returns the directory in use, or None when disabled (the cache is
    then switched off even where ``JAX_COMPILATION_CACHE_DIR`` names a
    directory).  Raises :class:`ValueError` for a directory that cannot
    be created or written (callers map it to a clean usage error).
    Idempotent — re-activating with the same resolution is a no-op; a
    different explicit dir re-points the cache (jax re-reads the config
    value per compile)."""
    import jax

    d = resolve_dir(flag)
    if d is None:
        if jax.config.jax_compilation_cache_dir is not None:
            _set_jax_dir(jax, None)
        _state["dir"] = None
        return None
    d = os.path.abspath(d)
    try:
        os.makedirs(d, exist_ok=True)
    except OSError as e:
        raise ValueError(
            f"compile_cache_dir={d!r}: cannot create the cache "
            f"directory ({e}) — fix the path or disable with "
            "compile_cache_dir=0") from None
    if not os.access(d, os.W_OK):
        raise ValueError(
            f"compile_cache_dir={d!r}: directory is not writable — "
            "fix permissions or disable with compile_cache_dir=0")
    if jax.config.jax_compilation_cache_dir != d:
        # (with JAX_COMPILATION_CACHE_DIR set to an absolute path, jax
        # already holds this very directory and nothing is written)
        _set_jax_dir(jax, d)
    # cache-everything policy (module docstring): the serve ladder is
    # made of sub-second executables, and those ARE the cold-start cost
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _state["dir"] = d
    return d


def is_enabled() -> bool:
    """Whether :func:`activate` pointed the cache somewhere this
    process — the registry hook's miss-attribution gate."""
    return _state["dir"] is not None


def cache_dir() -> Optional[str]:
    return _state["dir"]


def deactivate() -> None:
    """Restore the pre-activation cache config (tests: jax config is
    process-global — a test that activated must not leak its dir into
    the next, nor blank a cache the harness had already pointed)."""
    if _state["changed"]:
        import jax

        _set_jax_dir(jax, _state["prev"])
    _state.update(dir=None, prev=None, changed=False)
