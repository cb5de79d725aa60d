"""Causal flash attention (Pallas TPU) — online-softmax, O(S) memory.

Beyond-paper kernel for the LM stack's prefill/train hot spot: the chunked
jnp attention in `models/attention.py` bounds live memory but still writes
(B,H,QC,S) logits to HBM per chunk; this kernel keeps the running max /
denominator / accumulator in VMEM scratch across KV blocks (FlashAttention
reformulated for the TPU grid: KV is the innermost sequential grid dim).

Layout: grid (batch*heads, q_blocks, kv_blocks); blocks (BQ, D) / (BK, D).
Causality at block granularity: kv blocks strictly above the diagonal are
skipped via pl.when; the diagonal block applies the elementwise mask.
Validated in interpret mode against ref_attention (tests sweep shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    run = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)                  # (BQ, D)
        k = k_ref[0].astype(jnp.float32)                  # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        s = (q @ k.T) * scale                             # (BQ, BK)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                               # (BQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                            # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)                   # (BQ, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "causal", "interpret"))
def flash_attention(q, k, v, *, bq: int = 128, bk: int = 128,
                    causal: bool = True, interpret: bool | None = None):
    """q/k/v: (B, H, S, D) -> (B, H, S, D). S divisible by bq and bk.
    `interpret=None` runs the Pallas interpreter on the CPU backend only."""
    b, h, s, d = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    scale = 1.0 / (d ** 0.5)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)

    grid = (b * h, s // bq, s // bk)
    kernel = functools.partial(_kernel, bq=bq, bk=bk, scale=scale,
                               causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)


def ref_attention(q, k, v, *, causal: bool = True):
    """Pure-jnp oracle."""
    b, h, s, d = q.shape
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
