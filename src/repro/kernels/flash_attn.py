"""Pallas TPU kernel: causal flash attention (forward).

The §Perf cell-A analysis (EXPERIMENTS.md) showed the XLA-level online-softmax
attention still round-trips (qb, kvb) score tiles through HBM (~1.9 TiB/device
loop-weighted at 32 K prefill); this kernel keeps the tiles in VMEM — per
layer the HBM traffic drops to the q/k/v/out streams, which is the estimated
memory-term floor (13.5 s -> ~3.5 s for phi3 prefill_32k).

Grid: (B*H, n_q_blocks, n_kv_blocks), kv innermost.  The (m, l, acc) running
state lives in VMEM scratch that persists across the kv iterations of one
(bh, qi) cell and is re-initialized at kv==0; the output block is written at
the last kv step (the standard Pallas flash structure).  Causality is an
additive bias from block position iotas; fully-masked tiles (kv block
entirely after the q block) are skipped with ``pl.when``.

Validated bit-close against the pure-jnp oracle in interpret mode across a
shape sweep (tests/test_kernels.py::test_flash_kernel).  GQA: callers repeat
K/V to H (the framework's repeat-KV layout) before the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, qb: int, kvb: int, nkv: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile():
        q = q_ref[0].astype(jnp.float32)  # (qb, hd)
        k = k_ref[0].astype(jnp.float32)  # (kvb, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T) * scale  # (qb, kvb)
        if causal:
            qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kvb), 0)
            kpos = kj * kvb + jax.lax.broadcasted_iota(jnp.int32, (qb, kvb), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(p, v)

    if causal:
        # skip tiles entirely above the diagonal
        pl.when(kj * kvb <= qi * qb + (qb - 1))(_tile)
    else:
        _tile()

    @pl.when(kj == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "q_block", "kv_block", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float | None = None,
    causal: bool = True,
    q_block: int = 256,
    kv_block: int = 256,
    interpret: bool,
) -> jax.Array:
    """Causal flash attention.  q/k/v: (B, S, H, hd) with equal H (repeat-KV
    upstream for GQA).  Returns (B, S, H, hd)."""
    B, S, H, hd = q.shape
    assert k.shape == v.shape == (B, S, H, hd), (q.shape, k.shape)
    if scale is None:
        scale = 1.0 / (hd**0.5)
    qb = min(q_block, S)
    kvb = min(kv_block, S)
    assert S % qb == 0 and S % kvb == 0, (S, qb, kvb)
    nq, nkv = S // qb, S // kvb

    # (B, S, H, hd) -> (B*H, S, hd): one grid row per (batch, head)
    def _bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    qf, kf, vf = _bh(q), _bh(k), _bh(v)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, qb=qb, kvb=kvb, nkv=nkv, scale=scale, causal=causal
        ),
        grid=(B * H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, qb, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, kvb, hd), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, kvb, hd), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            _scratch((qb,), jnp.float32),  # running max m
            _scratch((qb,), jnp.float32),  # running denominator l
            _scratch((qb, hd), jnp.float32),  # running numerator acc
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def _scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
