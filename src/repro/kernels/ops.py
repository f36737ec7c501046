"""jit'd public wrappers over the Pallas kernels.

``backend`` resolution: on the CPU platform the kernels run in interpret
mode (the kernel body executes as XLA ops via the Pallas interpreter,
with the TPU grid/BlockSpec semantics); on any other platform the same
calls compile through Mosaic.  ``backend="ref"`` uses the pure-jnp
oracle instead.
"""

from __future__ import annotations

import jax

from repro.core.nesting import StripeSpec
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import nested_matmul as _nm
from repro.kernels import ref
from repro.kernels import rwkv_scan as _rw


def use_interpret() -> bool:
    """Interpret mode on the CPU platform, Mosaic everywhere else."""
    return jax.default_backend() == "cpu"


def nested_matmul(x: jax.Array, w: jax.Array, in_spec: StripeSpec,
                  out_spec: StripeSpec, level: int | None = None,
                  backend: str | None = None, **kw) -> jax.Array:
    """Block-lower-triangular nested matmul at ``level`` (paper §4.2.1);
    ``backend="ref"`` uses the pure-jnp oracle, otherwise the Pallas
    kernel (interpret on CPU)."""
    if backend == "ref":
        return ref.nested_matmul_ref(x, w, in_spec, out_spec, level)
    return _nm.nested_matmul(x, w, in_spec, out_spec, level,
                             interpret=use_interpret(), **kw)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    backend: str | None = None, **kw):
    """Streaming-softmax prefill attention (GQA/MQA, causal/window/
    softcap); ``backend="ref"`` uses the pure-jnp oracle, otherwise the
    Pallas kernel (interpret on CPU)."""
    if backend == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window, softcap=softcap)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap,
                               interpret=use_interpret(), **kw)


def decode_attention(q, k, v, cache_len, *, window=None,
                     backend: str | None = None, **kw):
    """Single-position decode attention over a ragged KV cache;
    ``backend="ref"`` uses the pure-jnp oracle, otherwise the Pallas
    kernel (interpret on CPU)."""
    if backend == "ref":
        return ref.decode_attention_ref(q, k, v, cache_len, window=window)
    return _dec.decode_attention(q, k, v, cache_len, window=window,
                                 interpret=use_interpret(), **kw)


def rwkv_scan(r, k, v, w, u, s0, *, chunk: int = 128,
              backend: str | None = None, **kw):
    """Chunked RWKV6 state scan; ``backend="ref"`` uses the pure-jnp
    oracle, otherwise the Pallas kernel (interpret on CPU)."""
    if backend == "ref":
        return ref.rwkv_scan_ref(r, k, v, w, u, s0)
    return _rw.rwkv_scan(r, k, v, w, u, s0, chunk=chunk,
                         interpret=use_interpret(), **kw)
