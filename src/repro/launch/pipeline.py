"""GPipe-style pipeline parallelism over the ``pod`` axis (selectable).

Multi-pod strategy: instead of treating the second pod as extra data
parallelism, the layer stack is split into ``n_pod`` contiguous stages;
microbatches stream through the stages with activations handed across pods
by ``ppermute`` (cross-pod ICI is the scarce link — PP sends one activation
tensor per microbatch instead of gradient all-reduces over the full model).

Implementation: ``shard_map`` manual over ``pod`` only (data/model stay
GSPMD-auto inside the body), the classic M+S-1 tick loop, stage params
sliced from a [n_pod, ...] stack.  Supports uniform-pattern decoder archs
(pattern length 1, no tail).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.models.attention import head_layout
from repro.models.modules import Policy, chunked_softmax_xent, embed, pad_vocab, unembed_logits


def stack_stage_params(cfg: ArchConfig, params: dict, n_stages: int) -> dict:
    """Re-stack blocks [periods, ...] -> [n_stages, periods/n_stages, ...]."""
    assert len(cfg.pattern) == 1 and not cfg.tail, "PP supports uniform-pattern archs"
    per = cfg.num_periods
    assert per % n_stages == 0
    blocks = jax.tree.map(
        lambda a: a.reshape((n_stages, per // n_stages) + a.shape[1:]),
        params["blocks"],
    )
    return {**params, "blocks": blocks}


def make_pp_loss(cfg: ArchConfig, pol: Policy, mesh: Mesh, *, microbatches: int):
    """Pipelined loss over the pod axis.  batch [B, S] split into M
    microbatches; returns mean loss (identical math to the unpiped model)."""
    n_stages = mesh.shape["pod"]
    lay = head_layout(cfg.num_heads, cfg.num_kv_heads, pol.tp)

    def stage_blocks(blocks_stage, x, pos):
        def body(carry, per_params):
            y, _, _ = transformer._apply_block(
                cfg.pattern[0], per_params["b0"], carry, cfg, lay, pol, pos=pos)
            return y, None
        if pol.remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body, x, blocks_stage)
        return x

    def pp_body(stage_params, embed_tok, lm_head, final_norm, tokens, labels, mask):
        # manual over "pod": P("pod") args arrive as [1, ...] — drop the
        # stage axis to get this stage's own parameter stack
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        sid = jax.lax.axis_index("pod")
        b, s = tokens.shape
        m = microbatches
        mb = b // m
        d = cfg.d_model
        pos = transformer._positions(cfg, mb, s, 0)
        ticks = m + n_stages - 1
        buf_in = jnp.zeros((mb, s, d), pol.compute_dtype)
        # rank-1 carries: old shard_map's transpose rank-check rejects
        # rank-0 residuals
        losses = jnp.zeros((1,), jnp.float32)
        denom = jnp.zeros((1,), jnp.float32)

        def tick(t, carry):
            buf_in, losses, denom = carry
            mb_idx = jnp.clip(t - sid, 0, m - 1)
            tok_mb = jax.lax.dynamic_slice_in_dim(tokens, mb_idx * mb, mb, 0)
            lab_mb = jax.lax.dynamic_slice_in_dim(labels, mb_idx * mb, mb, 0)
            msk_mb = jax.lax.dynamic_slice_in_dim(mask, mb_idx * mb, mb, 0)
            # stage 0 embeds its microbatch; later stages consume the buffer
            x0 = embed({"tok": embed_tok}, tok_mb, scale=cfg.embed_scale, d=d, pol=pol)
            x = jnp.where(sid == 0, x0, buf_in)
            active = (t >= sid) & (t - sid < m)
            y = stage_blocks(stage_params["blocks"], x, pos)
            y = jnp.where(active, y, 0.0)
            # last stage: norm + loss for its finished microbatch
            from repro.models.modules import apply_norm

            h = apply_norm(final_norm, y, cfg.norm_kind)
            mb_loss = chunked_softmax_xent(
                h, lm_head, lab_mb, msk_mb, pol, cfg.vocab_size,
                chunk=min(512, s))
            is_last = sid == n_stages - 1
            losses = losses + jnp.where(is_last & active, mb_loss, 0.0)
            denom = denom + jnp.where(is_last & active, 1.0, 0.0)
            # hand activations to the next stage
            nxt = jax.lax.ppermute(y, "pod",
                                   [(i, i + 1) for i in range(n_stages - 1)])
            return (nxt, losses, denom)

        buf_in, losses, denom = jax.lax.fori_loop(
            0, ticks, tick, (buf_in, losses, denom))
        total = jax.lax.psum(losses, "pod")  # only last stage contributed
        cnt = jax.lax.psum(denom, "pod")
        # emit the (replicated) loss as a pod-mapped [1] output: transposing
        # an unmapped P() output through jax.grad is unsupported on older
        # shard_map, and the mean outside is identical math
        return total / jnp.maximum(cnt, 1.0)

    mapped = shard_map(
        pp_body,
        mesh=mesh,
        in_specs=(P("pod"), P(), P(), P(), P(), P(), P()),
        out_specs=P("pod"),
        axis_names=frozenset({"pod"}),
        check_vma=False,
    )

    def loss_fn(stacked_params, batch):
        per_stage = mapped(
            {"blocks": stacked_params["blocks"]},
            stacked_params["embed"]["tok"],
            stacked_params.get("lm_head", stacked_params["embed"]["tok"]),
            stacked_params["final_norm"],
            batch["tokens"], batch["labels"], batch["mask"],
        )
        return jnp.mean(per_stage)

    return loss_fn
