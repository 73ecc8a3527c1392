"""Counter arithmetic and the compile counter.

`flatten_counters` / `delta_counters` turn the store's nested
`data_path_counters()` into flat window deltas; `Compiles` counts backend
compiles (persistent-cache hits included) through `jax.monitoring`, so a
run can show that nothing compiled inside its measured window.
"""
from __future__ import annotations

from typing import Dict

import jax


def flatten_counters(d: Dict, prefix: str = "") -> Dict:
    """Nested counter dict -> flat {"a.b.c": v}."""
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(flatten_counters(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out


def delta_counters(before: Dict, after: Dict) -> Dict:
    """Per-key numeric delta of two flat counter snapshots."""
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


class Compiles:
    """Backend compiles and persistent-cache hits seen by this process.
    jax.monitoring keeps listeners for the life of the process, so make
    one per process."""

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
