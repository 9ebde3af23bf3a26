"""Pallas TPU flash attention (GQA-grouped, causal/sliding-window).

The jnp flash path materializes [q_chunk, kv_chunk] score/weight tensors in
HBM every block — the §Roofline tables show attention intermediates
dominating the memory term of the dense train/prefill cells.  This kernel
keeps the online-softmax state (m, l, acc) and the score tile entirely in
VMEM: HBM traffic is exactly q + k + v + o.

Layout: q [G, P, Sq, hd] (G = kv groups, P = q-heads-per-group), k/v
[G, Sk, hd].  Grid (G, nq, nk) with the kv dim innermost (sequential on
TPU); scratch VMEM carries the accumulator across kv steps.

VMEM budget per step (bq=256, bk=512, P<=8, hd<=256, f32):
  q tile P*256*256*4 = 2 MiB; k/v 2*512*256*4 = 1 MiB;
  scores P*256*512*4 = 2 MiB; acc 2 MiB  => ~7 MiB < 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, window: int, bq: int, bk: int, nk: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # [P, bq, hd]
    k = k_ref[0].astype(jnp.float32)                # [bk, hd]
    v = v_ref[0].astype(jnp.float32)                # [bk, hd]
    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [P, bq, bk]

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), bool)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    s = jnp.where(ok[None], s, NEG_INF)

    m_prev = m_ref[...]                             # [P, bq]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [P, bq, hd]
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention_tpu(
    q: jax.Array,  # [G, P, Sq, hd]
    k: jax.Array,  # [G, Sk, hd]
    v: jax.Array,  # [G, Sk, hd]
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 256,
    bk: int = 512,
    interpret: bool = True,
) -> jax.Array:
    g, p, sq, hd = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, "pad sequences to block multiples"
    nq, nk = sq // bq, sk // bk
    scale = hd**-0.5

    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window, bq=bq, bk=bk,
                          nk=nk, scale=scale),
        grid=(g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, p, bq, hd), lambda gg, qq, kk: (gg, 0, qq, 0)),
            pl.BlockSpec((1, bk, hd), lambda gg, qq, kk: (gg, kk, 0)),
            pl.BlockSpec((1, bk, hd), lambda gg, qq, kk: (gg, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, p, bq, hd), lambda gg, qq, kk: (gg, 0, qq, 0)),
        out_shape=jax.ShapeDtypeStruct((g, p, sq, hd), q.dtype),
        scratch_shapes=[
            # online-softmax state lives in VMEM across the sequential kv dim
            pltpu.VMEM((p, bq, hd), jnp.float32),
            pltpu.VMEM((p, bq), jnp.float32),
            pltpu.VMEM((p, bq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_tpu",  # the op's name in a device trace
    )(q, k, v)
