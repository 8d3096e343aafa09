"""Jit'd model-facing wrappers around the Pallas kernels.

These adapt model-layout tensors (GQA head grouping, [B, S, H, D] layouts)
to the kernels' flat [BH, S, D] layout and pad sequences to block
multiples.  The kernels compile for the TPU; a caller without one passes
``interpret=True`` (the tests do).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.swiglu_matmul import swiglu_matmul

__all__ = ["gqa_flash_attention", "ssd_mixer", "fused_swiglu"]


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def gqa_flash_attention(
    q: jax.Array,   # [B, S, H, D]
    k: jax.Array,   # [B, S, KV, D]
    v: jax.Array,   # [B, S, KV, D]
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """GQA wrapper: repeats KV per query group, flattens heads into batch."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if G != 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, S))
    qf = _pad_to(jnp.moveaxis(q, 2, 1).reshape(B * H, S, D), 1, bq)
    kf = _pad_to(jnp.moveaxis(k, 2, 1).reshape(B * H, S, D), 1, bk)
    vf = _pad_to(jnp.moveaxis(v, 2, 1).reshape(B * H, S, D), 1, bk)
    # padded KV rows are masked out by causality (they sit beyond every q row)
    o = flash_attention(qf, kf, vf, causal=True if not causal else causal,
                        block_q=bq, block_k=bk, interpret=interpret)
    o = o[:, :S].reshape(B, H, S, D)
    return jnp.moveaxis(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def ssd_mixer(
    x: jax.Array,    # [B, S, H, P]
    dt: jax.Array,   # [B, S, H]
    A: jax.Array,    # [H]
    Bm: jax.Array,   # [B, S, G, N]
    Cm: jax.Array,   # [B, S, G, N]
    block_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Model-layout wrapper: broadcast groups to heads, flatten [B*H]."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    if rep != 1:
        Bm = jnp.repeat(Bm, rep, axis=2)
        Cm = jnp.repeat(Cm, rep, axis=2)
    bs = min(block_s, S)
    pad = (-S) % bs
    xf = _pad_to(jnp.moveaxis(x, 2, 1).reshape(B * H, S, P), 1, bs)
    dtf = _pad_to(jnp.moveaxis(dt, 2, 1).reshape(B * H, S), 1, bs)
    Bf = _pad_to(jnp.moveaxis(Bm, 2, 1).reshape(B * H, S, N), 1, bs)
    Cf = _pad_to(jnp.moveaxis(Cm, 2, 1).reshape(B * H, S, N), 1, bs)
    Af = jnp.tile(A.astype(jnp.float32), B)
    o = ssd_scan(xf, dtf.astype(jnp.float32), Af, Bf, Cf,
                 block_s=bs, interpret=interpret)
    o = o[:, :S].reshape(B, H, S, P)
    return jnp.moveaxis(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f", "block_k", "interpret"))
def fused_swiglu(
    x: jax.Array,    # [..., D]
    wg: jax.Array,   # [D, F]
    wu: jax.Array,   # [D, F]
    block_m: int = 256,
    block_f: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    lead = x.shape[:-1]
    D = x.shape[-1]
    F = wg.shape[1]
    xf = x.reshape(-1, D)
    M = xf.shape[0]
    bm = min(block_m, M)
    xf = _pad_to(xf, 0, bm)
    o = swiglu_matmul(xf, wg, wu, block_m=bm,
                      block_f=min(block_f, F), block_k=min(block_k, D),
                      interpret=interpret)
    return o[:M].reshape(*lead, F)
