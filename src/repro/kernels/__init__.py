"""Pallas kernels for the ALERT reproduction (docs/KERNELS.md).

Public entry points, re-exported here:

* :func:`alert_select` — the fused ``[S, K, L]`` decision kernel behind
  ``BatchedAlertEngine(backend="pallas")`` (plus its analytic roofline,
  :func:`alert_select_cost`);
* the serving-side kernels via their backend-resolving wrappers in
  :mod:`repro.kernels.ops` (interpret on the CPU, Mosaic elsewhere,
  ``backend="ref"`` for the pure-jnp oracles in :mod:`repro.kernels.ref`):
  :func:`nested_matmul`, :func:`flash_attention`,
  :func:`decode_attention`, :func:`rwkv_scan`.
"""

from repro.kernels.alert_select import alert_select, alert_select_cost
from repro.kernels.ops import (decode_attention, flash_attention,
                               nested_matmul, rwkv_scan)

__all__ = ["alert_select", "alert_select_cost", "decode_attention",
           "flash_attention", "nested_matmul", "rwkv_scan"]
