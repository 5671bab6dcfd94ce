"""Pallas TPU kernel: chunked Mamba2 SSD scan with facet state passing.

The SSD recurrence is a 1-D uniform-dependence tiled loop (chunks = tiles);
the inter-chunk state is exactly the chunk's CFA flow-out facet: dependence
depth 1 along the sequence-tile axis, so each chunk emits one (H, P, N)
state block, stored contiguously and consumed by the next chunk only —
write-one-burst / read-one-burst, the paper's stance, realised here as a VMEM
scratch carried across the sequential chunk grid.

Within a chunk of length L (the tile execute stage), with ``l`` the running
log-decay cumsum:

    y_intra[t] = sum_{s<=t} exp(l_t - l_s) (C_t . B_s) x_s      (masked GEMMs)
    y_inter[t] = exp(l_t) * C_t . S_prev
    S_next     = exp(l_L) S_prev + sum_s exp(l_L - l_s) x_s (x) B_s

All contractions map onto the MXU; chunk length and head dims are chosen as
multiples of (8, 128) by the caller for lane/sublane alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["ssd_scan"]


def _kernel(x_ref, loga_ref, b_ref, c_ref, y_ref, sfin_ref, state, *, nchunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[...].astype(jnp.float32)  # (L, H, P)
    loga = loga_ref[...].astype(jnp.float32)  # (L, H)
    Bm = b_ref[...].astype(jnp.float32)  # (L, N)
    C = c_ref[...].astype(jnp.float32)  # (L, N)
    L, H, P = x.shape

    lcum = jnp.cumsum(loga, axis=0)  # (L, H): l_t, inclusive of step t
    ltot = lcum[-1]  # (H,)

    # ---- inter-chunk: read the incoming facet (previous chunk's state) ----
    S_prev = state[...]  # (H, P, N)
    # y_inter[t,h,p] = exp(l[t,h] - loga[t,h]) * sum_n C[t,n] S_prev[h,p,n]
    # (the state seen by step t excludes step t's own decay-then-update; the
    #  reference applies a_t to S_{t-1} *before* the update, so the factor is
    #  exp(l_t) which already includes a_t.)
    cs = jax.lax.dot_general(S_prev, C, (((2,), (1,)), ((), ())))  # (H, P, L)
    y_inter = jnp.exp(lcum).transpose(1, 0)[:, None, :] * cs  # (H, P, L)

    # ---- intra-chunk: masked decay attention ----
    G = jax.lax.dot_general(C, Bm, (((1,), (1,)), ((), ())))  # (L, L): C_t . B_s
    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    mask = ti >= si
    # decay[h,t,s] = exp(l_t[h] - l_s[h]) for s <= t
    ldiff = lcum.transpose(1, 0)[:, :, None] - lcum.transpose(1, 0)[:, None, :]
    W = jnp.where(mask[None], jnp.exp(ldiff) * G[None], 0.0)  # (H, L, L)
    y_intra = jax.lax.dot_general(
        W, x.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,)))
    )  # (H, L, P)

    y = y_intra.transpose(1, 0, 2) + y_inter.transpose(2, 0, 1)  # (L, H, P)
    y_ref[...] = y.astype(y_ref.dtype)

    # ---- flow-out facet: next chunk state ----
    # S_next[h,p,n] = exp(ltot[h]) S_prev + sum_s exp(ltot[h]-l_s[h]) x_s B_s
    wout = jnp.exp(ltot[None, :] - lcum)  # (L, H)
    xw = x * wout[:, :, None]  # (L, H, P)
    dS = jax.lax.dot_general(
        xw.transpose(1, 2, 0), Bm, (((2,), (0,)), ((), ()))
    )  # (H, P, N)
    state[...] = jnp.exp(ltot)[:, None, None] * S_prev + dS

    @pl.when(c_idx == nchunks - 1)
    def _emit():
        sfin_ref[...] = state[...].astype(sfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jnp.ndarray,  # (B, T, H, P)
    loga: jnp.ndarray,  # (B, T, H)
    Bmat: jnp.ndarray,  # (B, T, N)
    C: jnp.ndarray,  # (B, T, N)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan; returns (y (B,T,H,P), final state (B,H,P,N))."""
    Bb, T, H, P = x.shape
    N = Bmat.shape[-1]
    if T % chunk:
        raise ValueError(f"T={T} must divide by chunk={chunk}")
    nc = T // chunk
    kernel = functools.partial(_kernel, nchunks=nc)
    y, sfin = pl.pallas_call(
        kernel,
        grid=(Bb, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((None, chunk, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((None, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, T, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, loga, Bmat, C)
    return y, sfin
