"""Jit'd wrapper for the WKV6 kernel with jnp fallback."""
from __future__ import annotations

from typing import Optional

from repro.kernels.wkv6.ref import wkv6_ref
from repro.kernels.wkv6.wkv6 import wkv6_chunked


def wkv6(r, k, v, w, u, *, chunk: int = 64, use_pallas: bool = True,
         interpret: Optional[bool] = None):
    """r/k/w: (B, T, H, K); v: (B, T, H, V); u: (H, K) ->
    (y (B, T, H, V), final state (B, H, K, V))."""
    if use_pallas and r.shape[1] % min(chunk, r.shape[1]) == 0:
        return wkv6_chunked(r, k, v, w, u, chunk=chunk,
                            interpret=interpret)
    return wkv6_ref(r, k, v, w, u)
