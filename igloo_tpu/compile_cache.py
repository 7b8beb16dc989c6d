"""Persistent XLA compile cache: policy, telemetry, and cluster transfer.

A cold TPC-H query spends far longer in the XLA compiler than on the
device (PERF.md has the chip's numbers) — for ad-hoc traffic, compilation
IS the latency. This module owns the three pieces that turn JAX's
persistent compilation cache into a *cluster-wide* one
(docs/compile_cache.md):

- **policy** (`configure`): resolve JAX_COMPILATION_CACHE_DIR and the
  IGLOO_TPU_COMPILE_CACHE setting into a cache directory and install it
  into jax.config. Imported-time entry point for `igloo_tpu/__init__.py`;
  also applied by workers when the coordinator propagates its setting at
  registration.
- **telemetry** (`install_metrics`): hook jax.monitoring's
  `/jax/compilation_cache/*` events into the MetricsRegistry as
  `compile_cache.hit` / `compile_cache.miss` counters and a
  `compile_cache.saved_s` histogram. Listeners run on the compiling thread,
  so per-query `counter_delta()` collectors (EXPLAIN ANALYZE, the bench
  sweep) see exactly their own query's cache traffic.
- **transfer** (`entry_names` / `read_entry` / `write_entry`): the
  filename-keyed entry store the cluster actions move around — workers pull
  missing entries from the coordinator at registration (pre-warm) and push
  entries they compile back (cluster/coordinator.py, cluster/worker.py), so
  a query shape compiles once per *cluster*, ever.

Env knobs:
    JAX_COMPILATION_CACHE_DIR    JAX's own variable. Where it is set, that
                                 directory IS the cache and this module
                                 installs no other — whoever launches the
                                 process places the cache.
    IGLOO_TPU_COMPILE_CACHE      0/false/off disables (whatever the JAX
                                 variable says); 1/true/on (or unset) uses
                                 `<checkout>/.xla_cache`; anything else is
                                 the directory to use. A directory here is
                                 honoured only when the JAX variable is
                                 unset.
    IGLOO_TPU_COMPILE_CACHE_MIN_SECS
                                 persist threshold override (default 1.0 —
                                 sub-second programs are cheaper to
                                 recompile than to ship; tests set 0).
"""
from __future__ import annotations

import base64
import os
import re
from typing import Optional

# entry filenames XLA writes (key-hash based) plus the sidecar files the
# cache keeps next to them; path separators and dotfiles are rejected so a
# malicious peer can never traverse out of the cache directory
_SAFE_NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]*$")

# the adaptive-hint store (exec/hints.py) lives beside the XLA entries but
# has merge semantics of its own — never ship it as a cache entry
_EXCLUDE = {"nhints.json"}

# refuse to read/accept pathological blobs (largest observed TPU entries are
# tens of MB; anything bigger is a bug or an attack, not a cache entry)
MAX_ENTRY_BYTES = 256 << 20

# cluster transfer only lists entries stable for this long (see entry_names)
TRANSFER_MIN_AGE_S = 5.0

_disabled_reason: Optional[str] = None


def default_dir() -> str:
    """`.xla_cache` at the root of the checkout (git-ignored): a fixed path,
    because the path is part of what keys a cache entry."""
    parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(parent, ".xla_cache")


def resolve_setting(raw: Optional[str] = None) -> Optional[str]:
    """IGLOO_TPU_COMPILE_CACHE value (+ JAX_COMPILATION_CACHE_DIR) -> cache
    directory (None = disabled). Off wins; then the JAX variable; then the
    igloo setting's own directory; then `default_dir()`."""
    if raw is None:
        raw = os.environ.get("IGLOO_TPU_COMPILE_CACHE", "1")
    flag = raw.strip().lower()
    if flag in ("0", "false", "off", "no", ""):
        return None
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if flag in ("1", "true", "on", "yes"):
        return default_dir()
    return raw


def configure(raw: Optional[str] = None) -> Optional[str]:
    """Install the persistent-cache setting into jax.config. Returns the
    active directory (None when disabled). A malformed
    IGLOO_TPU_COMPILE_CACHE_MIN_SECS downgrades to cold compiles only — but
    LOUDLY: one warning plus a `compile_cache.disabled` counter, so a
    silently-dead cache shows up in system.metrics instead of as a
    mysterious minute per query."""
    global _disabled_reason
    cache_dir = resolve_setting(raw)
    import jax
    if not cache_dir:
        # an explicit "off" must also UNDO a previously-installed directory:
        # workers adopting the coordinator's disabled setting at registration
        # would otherwise keep persisting to their import-time default
        jax.config.update("jax_compilation_cache_dir", None)
        return None
    try:
        # parse BEFORE touching jax.config so a failure can't leave the
        # cache half-enabled (dir installed, thresholds defaulted)
        min_secs = float(os.environ.get(
            "IGLOO_TPU_COMPILE_CACHE_MIN_SECS", "1.0"))
    except ValueError as ex:
        jax.config.update("jax_compilation_cache_dir", None)
        if _disabled_reason is None:
            _disabled_reason = f"{type(ex).__name__}: {ex}"
            import warnings
            warnings.warn(
                "igloo_tpu: persistent XLA compile cache could NOT be "
                f"enabled ({_disabled_reason}); every process will pay cold "
                "compiles. Set IGLOO_TPU_COMPILE_CACHE=0 to silence.",
                RuntimeWarning, stacklevel=2)
            from igloo_tpu.utils import tracing
            tracing.counter("compile_cache.disabled")
        return None
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def disabled_reason() -> Optional[str]:
    return _disabled_reason


def active_dir() -> Optional[str]:
    """The directory jax is currently configured to persist into."""
    import jax
    return jax.config.jax_compilation_cache_dir or None


# --- telemetry ---------------------------------------------------------------

_metrics_installed = False


def install_metrics() -> None:
    """Register jax.monitoring listeners mapping compilation-cache events to
    the engine's metrics registry. Idempotent; safe before any compile."""
    global _metrics_installed
    if _metrics_installed:
        return
    _metrics_installed = True

    from igloo_tpu.utils import tracing

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            tracing.counter("compile_cache.hit")
        elif event == "/jax/compilation_cache/cache_misses":
            tracing.counter("compile_cache.miss")

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == "/jax/compilation_cache/compile_time_saved_sec":
            # can be slightly negative on trivial programs (retrieval cost
            # exceeds the compile it replaced); record what was measured
            tracing.histogram("compile_cache.saved_s", duration)

    from jax import monitoring
    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


# --- filename-keyed entry transfer ------------------------------------------


def entry_names(cache_dir: Optional[str] = None,
                min_age_s: float = 0.0) -> list:
    """Sorted filenames of the persistent-cache entries in `cache_dir`
    (default: the active directory). Only plain, safely-named files count —
    the hint store and anything unshippable is excluded. `min_age_s` skips
    entries modified more recently than that: XLA writes its cache files
    NON-atomically, so the cluster transfer must only list entries that have
    been stable for a few seconds (a truncated blob shipped once would pin
    itself cluster-wide — write_entry never overwrites)."""
    d = cache_dir if cache_dir is not None else active_dir()
    if not d or not os.path.isdir(d):
        return []
    import time
    cutoff = time.time() - min_age_s
    out = []
    for name in os.listdir(d):
        if name in _EXCLUDE or not _SAFE_NAME.match(name):
            continue
        p = os.path.join(d, name)
        try:
            st = os.stat(p)
        except OSError:
            continue
        if not os.path.isfile(p):
            continue
        # zero-byte stubs and unshippable oversizes never make the listing:
        # read_entry would refuse them anyway, so advertising them only
        # makes every worker pull an empty body
        if not 0 < st.st_size <= MAX_ENTRY_BYTES:
            continue
        if min_age_s and st.st_mtime > cutoff:
            continue
        out.append(name)
    return sorted(out)


def _entry_path(name: str, cache_dir: Optional[str]) -> Optional[str]:
    d = cache_dir if cache_dir is not None else active_dir()
    if not d or name in _EXCLUDE or not _SAFE_NAME.match(name):
        return None
    return os.path.join(d, name)


def read_entry(name: str, cache_dir: Optional[str] = None) -> Optional[bytes]:
    """Entry bytes by filename, or None (unknown name, unsafe name, no
    cache). Oversized entries read as None rather than shipping gigabytes;
    so do empty files — a zero-byte entry is never a valid XLA cache blob,
    only the stub of an abandoned write."""
    p = _entry_path(name, cache_dir)
    if p is None or not os.path.isfile(p):
        return None
    if not 0 < os.path.getsize(p) <= MAX_ENTRY_BYTES:
        return None
    with open(p, "rb") as f:
        return f.read()


def write_entry(name: str, data: bytes,
                cache_dir: Optional[str] = None) -> bool:
    """Store an entry under `name` (atomic rename; concurrent writers of
    the same key write identical content, so last-wins is fine). Returns
    True when the entry is now present with this content. Unsafe names,
    empty payloads, and oversized payloads are rejected, never written.

    An existing file of the SAME size is kept (same key ⇒ same bytes); a
    SIZE MISMATCH is overwritten — it can only be an abandoned partial
    write from a killed process, and skipping it would pin the truncated
    blob cluster-wide with no repair path."""
    p = _entry_path(name, cache_dir)
    if p is None or not data or len(data) > MAX_ENTRY_BYTES:
        return False
    try:
        if os.path.getsize(p) == len(data):
            return True
    except OSError:
        pass
    import tempfile
    try:
        os.makedirs(os.path.dirname(p), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p))
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, p)
    except OSError:
        return False
    return True


def encode_entry(data: bytes) -> str:
    return base64.b64encode(data).decode("ascii")


def decode_entry(data: str) -> bytes:
    return base64.b64decode(data.encode("ascii"))
