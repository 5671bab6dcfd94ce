"""Pallas TPU kernel: flash-decode attention over a facet(block)-layout KV cache.

CFA applied to serving (DESIGN.md §3): the KV cache is stored as sequence-
tiled blocks ``(B, nb, Hkv, bs, D)`` — the block index is the single-
assignment outer dimension, and each ``(bs, D)`` extent is contiguous in HBM.
Decode attention then streams the cache block-by-block:

* one DMA per (head, block) — a long "burst" in the paper's terms, versus the
  canonical ``(B, S, Hkv, D)`` layout whose per-head reads stride by
  ``Hkv*D`` every token;
* online-softmax state (m, l, acc) lives in VMEM scratch and persists across
  the sequential block grid — the read->execute pipeline overlap is Pallas
  grid double-buffering, exactly the DATAFLOW structure of paper Fig. 13.

Grid: ``(B, nb)`` with the block dimension minor (sequential per batch row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["decode_attention"]

_NEG_INF = float("-inf")


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_size: int, groups: int):
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[...].astype(jnp.float32)  # (Hkv, bs, D)
    v = v_ref[...].astype(jnp.float32)  # (Hkv, bs, D)
    q = q_ref[...].astype(jnp.float32)  # (Hq, D)
    hkv, bs, d = k.shape
    qg = q.reshape(hkv, groups, d)

    scores = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (0,)))
    ) / jnp.sqrt(jnp.float32(d))  # (Hkv, G, bs)

    length = len_ref[0]
    pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    scores = jnp.where(pos < length, scores, _NEG_INF)
    scores = scores.reshape(hkv * groups, bs)  # (Hq, bs)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    # guard: fully-masked block (all -inf) must not poison the accumulator
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(jnp.isfinite(m_new), alpha, 1.0)
    p = jnp.exp(scores - m_new)
    p = jnp.where(jnp.isfinite(m_new), p, 0.0)

    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(hkv, groups, bs), v, (((2,), (1,)), ((0,), (0,)))
    ).reshape(hkv * groups, d)  # (Hq, D)
    acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nb - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(
    q: jnp.ndarray,  # (B, Hq, D)
    k_blocks: jnp.ndarray,  # (B, nb, Hkv, bs, D) facet layout
    v_blocks: jnp.ndarray,  # (B, nb, Hkv, bs, D)
    lengths: jnp.ndarray,  # (B,) int32
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:  # (B, Hq, D)
    B, nb, Hkv, bs, D = k_blocks.shape
    Hq = q.shape[1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must be a multiple of Hkv={Hkv}")
    groups = Hq // Hkv
    kernel = functools.partial(_kernel, block_size=bs, groups=groups)
    return pl.pallas_call(
        kernel,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((None, 1), lambda b, j: (b, 0)),  # lengths (SMEM-class)
            pl.BlockSpec((None, Hq, D), lambda b, j: (b, 0, 0)),  # q
            pl.BlockSpec((None, None, Hkv, bs, D), lambda b, j: (b, j, 0, 0, 0)),
            pl.BlockSpec((None, None, Hkv, bs, D), lambda b, j: (b, j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, Hq, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),  # running max
            pltpu.VMEM((Hq, 1), jnp.float32),  # running denominator
            pltpu.VMEM((Hq, D), jnp.float32),  # running numerator
        ],
        interpret=resolve_interpret(interpret),
    )(lengths.reshape(B, 1).astype(jnp.int32), q, k_blocks, v_blocks)
