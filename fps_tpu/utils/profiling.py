"""Compat shim — the profiling helpers grew into :mod:`fps_tpu.obs`.

``trace`` now lives in :mod:`fps_tpu.obs.timing` alongside the phase
timers, recorder, and run journal; import it from ``fps_tpu.obs`` going
forward. This module re-exports it so existing call sites (and muscle
memory) keep working.
"""

from __future__ import annotations

from fps_tpu.obs.timing import trace

__all__ = ["trace"]
