"""Flash attention (GQA, causal, sliding-window) — Pallas TPU kernel.

Online-softmax over K blocks with VMEM accumulators. Grid is
(batch, q_heads, q_blocks, k_blocks); the K-block axis is innermost so
the (m, l, acc) scratch persists across its iterations (TPU grids run
sequentially per core). GQA is handled in the BlockSpec index maps
(query head h reads KV head h // group) — no KV replication in HBM.

Block shapes default to (128, head_dim): q/k tiles of 128 keep the MXU
systolic array fully utilized for head_dim >= 128 and the working set
(q, k, v, scores ~ 128x128 fp32) well inside VMEM.

Sliding-window + causal masking is applied with block-level iota; fully
masked K blocks are skipped via a cheap predicate on block indices.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, block_q, block_k, seq_k, seq_q):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this tile (q positions sit at the cache tail)
    off = seq_k - seq_q
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + off
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # block-level skip: is any element of this tile unmasked?
    q_last = iq * block_q + block_q - 1 + off
    q_first = iq * block_q + off
    k_first = ik * block_k
    k_last = ik * block_k + block_k - 1
    live = True
    if causal:
        live = k_first <= q_last
        if window is not None:
            live = jnp.logical_and(live, k_last > q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            mask = k_pos <= q_pos
            if window is not None:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq,)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """q: (B, Hq, Sq, d); k/v: (B, Hkv, Sk, d); Hq % Hkv == 0.

    Sq and Sk must be multiples of the block sizes (pad outside).
    interpret=None resolves backend-aware (compiled only on TPU).
    """
    interpret = resolve_interpret(interpret)
    B, Hq, Sq, d = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0 and Sq % block_q == 0 and Sk % block_k == 0, \
        (Hq, Hkv, Sq, Sk, block_q, block_k)
    g = Hq // Hkv
    scale = scale if scale is not None else d ** -0.5
    grid = (B, Hq, Sq // block_q, Sk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_k=Sk, seq_q=Sq)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m: running max
            pltpu.VMEM((block_q,), jnp.float32),      # l: running denom
            pltpu.VMEM((block_q, d), jnp.float32),    # acc: running numer
        ],
        interpret=interpret,
    )(q, k, v)
    return out
