"""Flash attention forward kernel (Pallas TPU).

TPU-native tiling of the online-softmax attention in
``repro.models.attention.online_attention``, on head-major operands
(``kernels.ops.flash_attention`` transposes to and from the models'
``[B, T, H, d]`` contract):

* grid ``(B, H, nq, nk)`` — the last (innermost) dimension is *sequential*
  ("arbitrary" semantics on TPU): the kernel revisits the same output block
  for each KV block, accumulating running (max, sum, acc) in fp32 VMEM
  scratch and finalising on the last KV step;
* BlockSpecs stage ``[qb, d]`` query tiles and ``[kb, d]`` KV tiles into VMEM
  as the last two block dims, which is what Mosaic tiles (a head dim of 1
  in second-to-last position is refused); qb/kb default 512/1024 → the
  dominant working set is qb·d + kb·d + qb·kb ≈ 0.8 MB at d=128 in bf16 —
  comfortably inside the ~16 MB v5e VMEM, leaving room for double buffering;
* matmul tiles are MXU-aligned (qb, kb, d multiples of 128; d=64 heads still
  map acceptably);
* GQA is handled by indexing the KV head as ``h // (H // K)`` in the
  BlockSpec index maps — no repeated KV materialisation in HBM;
* masks (causal / sliding window / tail padding) are applied with 2-D iota
  position tiles, so padded cells never contribute.

Validated in ``interpret=True`` mode against the pure-jnp oracle
(``kernels/ref.py``) across shape/dtype sweeps (tests/test_kernels.py), and
compiled by Mosaic for a described v5e chip in tests/test_chip_compile.py.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, causal: bool, window: int, scale: float,
    qb: int, kb: int, nk: int, tq: int, tk: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                             # [qb, dk]
    k = k_ref[0, 0]                             # [kb, dk]
    v = v_ref[0, 0]                             # [kb, dv]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                   # [qb, kb]

    q_pos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    k_pos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    mask = (k_pos < tk) & (q_pos < tq)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                         # [qb, 1]
    m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))[:, None]
    p = jnp.exp(s - m_new)                      # [qb, kb]
    corr = jnp.exp(m_prev - m_new)              # [qb, 1]
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)[:, None]
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,   # [B, H, Tq, dk]
    k: jnp.ndarray,   # [B, K, Tk, dk]
    v: jnp.ndarray,   # [B, K, Tk, dv]
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    k_block: int = 1024,
    scale: Optional[float] = None,
    interpret: bool,
) -> jnp.ndarray:
    """Head-major flash attention forward → ``[B, H, Tq, dv]``."""
    B, H, Tq, dk = q.shape
    _, K, Tk, dv = v.shape
    assert H % K == 0, (H, K)
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)

    qb = min(q_block, Tq)
    kb = min(k_block, Tk)
    pq = (-Tq) % qb
    pk = (-Tk) % kb
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = q.shape[2] // qb
    nk = k.shape[2] // kb

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, scale=scale,
        qb=qb, kb=kb, nk=nk, tq=Tq, tk=Tk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, dk), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kb, dk), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, kb, dv), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * qb, dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Tq]
