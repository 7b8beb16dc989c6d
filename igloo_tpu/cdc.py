"""CDC: source-change detection driving cache invalidation.

The reference's cdc crate is an empty stub ("TODO: Implement CDC logic",
crates/cdc/src/lib.rs:9) whose declared purpose (README "Intelligent Caching")
is invalidating the cache when a source changes. This is that capability:

- every connector exposes a cheap `snapshot()` token (file mtimes/sizes for
  Parquet/CSV, metadata version for Iceberg — see connectors/*.py); the batch
  cache already validates tokens lazily on each hit (exec/cache.py), so even
  without a watcher stale data is never served;
- `SourceWatcher` adds EAGER invalidation + notification: poll() diffs the
  current tokens against the last seen ones, evicts changed tables from the
  engine's batch cache, and fires registered callbacks (the distributed tier
  uses this to broadcast invalidations to workers);
- `watch()` runs poll() on a background thread at a fixed interval.
"""
from __future__ import annotations

import logging
import threading
from typing import Callable, Optional

from igloo_tpu.exec.cache import provider_snapshot
from igloo_tpu.utils import tracing

log = logging.getLogger("igloo_tpu")

# lock discipline (igloo-lint lock-discipline): the registration path
# (on_change, any thread) and the poll sweep (watch thread) share both the
# seen-token map and the callback list
_GUARDED_BY = {"_lock": ("_seen", "_callbacks")}


class SourceWatcher:
    def __init__(self, engine, interval_s: float = 5.0):
        self.engine = engine
        self.interval_s = interval_s
        self._seen: dict[str, object] = {}
        self._callbacks: list[Callable[[str], None]] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def on_change(self, fn: Callable[[str], None]) -> None:
        """Register a callback fired with the table name on each change.
        Lock-guarded: registration may race the watch thread's poll()
        (list.append alone would also race a concurrent snapshot read)."""
        with self._lock:
            self._callbacks.append(fn)

    def poll(self) -> list[str]:
        """One sweep: returns the list of tables whose source changed, after
        evicting them from the engine's batch cache. Callbacks run OUTSIDE
        the lock (a slow subscriber must not stall registration) and a
        raising callback is counted (`cdc.callback_errors`) and logged
        instead of killing the watch thread — one bad subscriber cannot
        turn eager invalidation off for everyone else."""
        changed = []
        with self._lock:
            for name in self.engine.catalog.names():
                provider = self.engine.catalog.maybe_get(name)
                if provider is None:
                    continue
                tok = provider_snapshot(provider)
                prev = self._seen.get(name)
                if prev is not None and prev != tok:
                    self.engine.batch_cache.invalidate_table(name)
                    changed.append(name)
                self._seen[name] = tok
            callbacks = list(self._callbacks)
        for name in changed:
            for fn in callbacks:
                try:
                    fn(name)
                except Exception:
                    tracing.counter("cdc.callback_errors")
                    log.exception("cdc: on_change callback failed for "
                                  "table %r", name)
        return changed

    def watch(self) -> "SourceWatcher":
        """Start background polling; idempotent. Restartable after stop()."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.poll()
                except Exception:  # pragma: no cover - never kill the thread
                    import logging
                    logging.getLogger("igloo_tpu").exception("cdc poll failed")
        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="igloo-cdc")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval_s)
            self._thread = None
