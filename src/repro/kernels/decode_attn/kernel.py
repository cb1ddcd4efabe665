"""Pallas TPU kernel: KV-block-streaming GQA decode attention.

The SEM discipline of the paper applied to LM decoding (DESIGN.md §2):

  * ``O(1)`` state in fast memory — the query for the one new token plus the
    online-softmax running ``(m, l, acc)`` live in VMEM scratch for the
    whole stream (the "vertex state" tier).
  * ``O(seq)`` data streamed — the KV cache is walked block-by-block
    HBM->VMEM, each block used once per step (the "edge data" tier).
    Pallas double-buffers the next block's DMA behind the current block's
    compute, the analogue of SAFS asynchronous I/O.
  * **Block skipping** (paper P1, "limit superfluous reads"): a per-block
    "needed" bit (any slot holding a position inside the live window /
    below the current length) is scalar-prefetched.  Skipped blocks
    redirect the index map to block 0 — no DMA — and skip compute, exactly
    like FlashGraph eliding page reads for converged vertex ranges.
  * **Functional combining** (paper P5): the online-softmax update is an
    associative rescale-and-add, the same contention-free reduction shape
    as the engine's semiring combiners.

Grid: (batch, kv_heads, T/block_t), T-dimension innermost ("arbitrary"
semantics — accumulation order along the stream).
GQA: the G = H/KV query heads of one KV head ride together as the rows of
an (G, hd) VMEM tile, so each streamed KV block is reused G times — maximal
arithmetic intensity for the bytes fetched (MQA: G = H, the paper's "page
cache hit" best case).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attn_pallas"]

NEG_INF = -2.0e38


def _kernel(
    needed,  # scalar-prefetch: i32[B, nTb]
    cur,  # scalar-prefetch: i32[B] current absolute position
    q_ref,  # [1, 1, G, hd]
    k_ref,  # [1, Tb, 1, hd]
    v_ref,  # [1, Tb, 1, hd]
    pos_ref,  # [1, Tb] stored absolute positions (-1 = empty)
    o_ref,  # [1, 1, G, hd]
    m_ref,  # VMEM scratch [G, 1] running max
    l_ref,  # VMEM scratch [G, 1] running denominator
    acc_ref,  # VMEM scratch [G, hd] running numerator
    *,
    window: int,
    scale: float,
):
    b, h, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(needed[b, t] == 1)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, :, 0].astype(jnp.float32)  # (Tb, hd)
        v = v_ref[0, :, 0].astype(jnp.float32)  # (Tb, hd)
        pos = pos_ref[0]  # (Tb,)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, Tb)
        valid = (pos >= 0) & (pos <= cur[b])
        if window > 0:
            valid = valid & (pos > cur[b] - window)
        s = jnp.where(valid[None, :], s, NEG_INF)

        m_prev = m_ref[...]  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (G, Tb)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(t == nt - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attn_pallas(
    q: jnp.ndarray,  # [B, KV, G, hd] new-token queries, grouped per KV head
    k: jnp.ndarray,  # [B, T, KV, hd]
    v: jnp.ndarray,  # [B, T, KV, hd]
    pos: jnp.ndarray,  # [B, T] int32 stored absolute positions (-1 empty)
    cur: jnp.ndarray,  # [B] int32 current absolute position
    needed: jnp.ndarray,  # [B, nTb] int32 — block holds any live slot
    *,
    window: int = 0,
    block_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns attention output [B, KV, G, hd] (f32)."""
    B, KV, G, hd = q.shape
    T = k.shape[1]
    assert T % block_t == 0, (T, block_t)
    nTb = T // block_t
    scale = hd**-0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, nTb),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, t, needed, cur: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, block_t, 1, hd),
                # skip the DMA of un-needed blocks (index unchanged => no fetch)
                lambda b, h, t, needed, cur: (b, needed[b, t] * t, h, 0),
            ),
            pl.BlockSpec(
                (1, block_t, 1, hd),
                lambda b, h, t, needed, cur: (b, needed[b, t] * t, h, 0),
            ),
            pl.BlockSpec(
                (1, block_t), lambda b, h, t, needed, cur: (b, needed[b, t] * t)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, hd), lambda b, h, t, needed, cur: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )

    return pl.pallas_call(
        functools.partial(_kernel, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(needed, cur, q, k, v, pos)
