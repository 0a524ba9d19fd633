"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The layer-rep stack is split into contiguous stages along a 'stage' mesh
axis; microbatches stream through with ppermute handoffs. The schedule
is the classic fill-drain pipeline (M microbatches, S stages, M+S-1
slots); bubble slots compute on garbage and are masked out of the loss.
jax.grad differentiates straight through the ppermutes, giving the
backward pipeline for free.

Scope: decoder-only models with a homogeneous pattern (len == 1); embed
and LM head are replicated on all stages (their compute is masked to
stage 0 / last stage respectively). This is the production pattern for
the dense assigned archs; tests assert exact loss parity vs. the
unpipelined model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import lm_specs
from repro.models.blocks import block_apply_full
from repro.models.common import rmsnorm
from repro.models.lm import embed_tokens, logits_fn


def make_pp_loss(cfg, mesh, num_microbatches: int, axis: str = "stage"):
    """Returns loss_fn(params, batch) computing pipelined CE loss.

    params: the standard lm param tree (blocks stacked over reps).
    batch: tokens/labels (B, S) with B % num_microbatches == 0.
    """
    assert len(cfg.block_pattern) == 1, "PP supports homogeneous patterns"
    kind = cfg.block_pattern[0]
    nstages = mesh.shape[axis]
    M = num_microbatches
    assert cfg.pattern_repeats % nstages == 0

    def pp_fn(blocks_local, embed_p, final_norm_p, head_p, tokens_mb, labels_mb):
        """Runs inside shard_map; blocks_local: stage's slice of the stack.
        tokens_mb/labels_mb: (M, mb, S) replicated on all stages."""
        s_idx = jax.lax.axis_index(axis)
        mb, S = tokens_mb.shape[1], tokens_mb.shape[2]
        positions = jnp.arange(S, dtype=jnp.int32)
        params_head = {"embed": embed_p, "final_norm": final_norm_p}
        if head_p is not None:
            params_head["lm_head"] = head_p

        def run_blocks(x):
            def body(x, prm):
                x, _, _ = block_apply_full(cfg, kind, prm, x, positions)
                return x, None
            body = jax.checkpoint(body)
            x, _ = jax.lax.scan(body, x, blocks_local)
            return x

        h = jnp.zeros((mb, S, cfg.d_model), jnp.dtype(cfg.dtype))
        collected = jnp.zeros((M, mb, S, cfg.d_model), jnp.dtype(cfg.dtype))
        nslots = M + nstages - 1
        for t in range(nslots):
            m = t - s_idx                                  # microbatch index
            valid = (m >= 0) & (m < M)
            m_c = jnp.clip(m, 0, M - 1)
            toks = jax.lax.dynamic_index_in_dim(tokens_mb, m_c, 0, False)
            x0 = embed_tokens(cfg, params_head, toks, positions)
            x_in = jnp.where(s_idx == 0, x0, h)
            h_out = run_blocks(x_in)
            # last stage: stash the finished microbatch
            stash = (s_idx == nstages - 1) & valid
            upd = jnp.where(stash, h_out, jax.lax.dynamic_index_in_dim(
                collected, m_c, 0, False))
            collected = jax.lax.dynamic_update_index_in_dim(collected, upd, m_c, 0)
            # hand off to the next stage
            perm = [(i, i + 1) for i in range(nstages - 1)]
            h = jax.lax.ppermute(h_out, axis, perm)

        # loss only meaningful on the last stage
        xs = collected.reshape(M * mb, S, cfg.d_model)
        xs = rmsnorm(xs, final_norm_p, cfg.norm_eps)
        logits = logits_fn(cfg, params_head, xs)
        labels = labels_mb.reshape(M * mb, S)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
        ce = logz - jnp.sum(logits * onehot, axis=-1).astype(jnp.float32)
        loss_local = jnp.mean(ce)
        loss = jax.lax.psum(
            jnp.where(s_idx == nstages - 1, loss_local, 0.0), axis)
        return loss

    def loss_fn(params, batch):
        B, S = batch["tokens"].shape
        assert B % M == 0
        mb = B // M
        toks = batch["tokens"].reshape(M, mb, S)
        labs = batch["labels"].reshape(M, mb, S)
        blocks = params["blocks"][0]
        head_p = params.get("lm_head")
        in_specs = (
            jax.tree_util.tree_map(lambda _: P(axis), blocks),
            P(), P(), (P() if head_p is not None else None),
            P(), P())
        fn = jax.shard_map(pp_fn, mesh=mesh,
                           in_specs=in_specs, out_specs=P(),
                           check_vma=False)
        return fn(blocks, params["embed"], params["final_norm"], head_p,
                  toks, labs)

    return loss_fn
