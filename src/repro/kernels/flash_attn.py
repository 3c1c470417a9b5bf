"""Flash attention (forward) — Pallas TPU kernel, online softmax.

The §Perf logs (EXPERIMENTS.md) show every train/prefill cell memory-bound
with the score-tensor HBM round trips as the largest removable term: the
XLA path materializes [S, T] scores + softmax intermediates per head.
This kernel streams K/V blocks past a VMEM-resident Q block with running
(m, l) statistics — scores never leave VMEM, exactly the paper's VSR
principle (intermediates stay on-chip; only true inputs/outputs touch
HBM) applied to attention.

Layout: head-major [BH, S, D] (matches the decode cache layout).  Causal
and sliding-window masks are positional; fully-masked K blocks are
skipped via ``pl.when`` on the block index (the causal half and the
out-of-window band cost no MXU work).

Validated under ``interpret=True`` vs :func:`repro.kernels.ref.mha_ref`
(tests/test_flash_attn.py); block sizes default to MXU/VMEM-aligned
(128, 512) for D ≤ 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, bq, bk, n_kblocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk

    # skip K blocks that the causal/window mask fully excludes
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale       # [bq, bk]
        iq = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        jk = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= jk <= iq
        if window is not None:
            mask &= jk > iq - window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                    # [bq, bk]
        corr = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == n_kblocks - 1)
    def _final():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30))[None].astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q [BH, S, D], k/v [BH, T, D] -> [BH, S, D].

    Scores and softmax statistics never leave VMEM; HBM traffic is the
    q/k/v reads + output write.  ``window``: sliding-window width.
    """
    bh, s, d = q.shape
    t = k.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, t)
    assert s % bq == 0 and t % bk == 0, "pad seq to block multiples"
    n_kb = t // bk
    scale = d ** -0.5

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, bq=bq, bk=bk, n_kblocks=n_kb)
    return pl.pallas_call(
        kern,
        grid=(bh, s // bq, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
