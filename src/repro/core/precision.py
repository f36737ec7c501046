"""The decision plane's one x64 seam.

Scoring, the filter banks and the megatick carries compute in float64
(int64 counters) inside a *scoped* x64 block; the process-wide flag is
never touched, so the model path keeps its float32/bfloat16 defaults.
Every call site enters the scope through :func:`x64_scope`.
"""

from __future__ import annotations

import jax


def x64_scope(enabled: bool = True):
    """Context manager that turns 64-bit types on (or, with
    ``enabled=False``, off) for its block only."""
    return jax.enable_x64(enabled)
