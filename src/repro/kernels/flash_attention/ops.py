"""Jit'd wrapper: (B, S, n, hd) layout adapter + padding for the flash kernel."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention,
)


def flash_attention_bsnh(q, k, v, *, causal: bool = True,
                         window: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """Model-layout entry point. q: (B, Sq, Hq, hd); k/v: (B, Sk, Hkv, hd).

    Pads sequences to block multiples; padded K positions are masked by
    the causal predicate (they sit beyond the last real position), and
    padded Q rows are sliced off.
    """
    B, Sq, Hq, hd = q.shape
    Sk = k.shape[1]
    bq = min(DEFAULT_BLOCK_Q, max(16, Sq))
    bk = min(DEFAULT_BLOCK_K, max(16, Sk))
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q or pad_k:
        # padding shifts the q/k position offset unless the seqs match
        assert Sq == Sk and pad_q == pad_k, (Sq, Sk, pad_q, pad_k)
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    assert causal or pad_k == 0, "non-causal padding would attend to pad keys"
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=interpret)
    out = out[:, :, :Sq] if pad_q else out
    return jnp.moveaxis(out, 1, 2)
