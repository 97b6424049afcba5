"""Pipeline parallelism over the pod axis (multi-pod option).

On the 2x16x16 mesh the default data-parallel-over-pod schedule all-reduces
the full gradient across the inter-pod links every step.  This module offers
the alternative: split the layer stack into one *stage per pod* and stream
microbatches GPipe-style — cross-pod traffic becomes per-microbatch
activations (B_micro x S x d), orders of magnitude smaller than gradients
for large models.

Implementation: ``shard_map`` over the ``pod`` axis; each stage runs its
slice of periods; activations hop stages with ``jax.lax.ppermute``.  The
bubble fraction is (P-1)/(P-1+M) for M microbatches; with P=2 pods and M=8
it is 11%.  This is a framework feature exercised by tests on a small forced
mesh and selectable via ``launch/train.py --pipeline``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import lm
from repro.models.config import ModelConfig


def split_periods(params, n_stages: int):
    """Slice the stacked `blocks` pytree into per-stage stacks (axis 0)."""

    def sl(leaf, s):
        per = leaf.shape[0] // n_stages
        return leaf[s * per:(s + 1) * per]

    return [jax.tree.map(functools.partial(sl, s=s), params["blocks"])
            for s in range(n_stages)]


def pipelined_forward(cfg: ModelConfig, params, tokens, *, mesh: Mesh,
                      n_micro: int):
    """GPipe forward over the pod axis.  Returns final hidden states.

    Stage s owns periods [s*per, (s+1)*per).  Microbatches rotate through
    stages via ppermute; stage boundaries carry (B_micro, S, d).
    """
    n_stages = mesh.shape["pod"]
    assert cfg.n_periods % n_stages == 0
    B = tokens.shape[0]
    assert B % n_micro == 0

    # stage-local parameter stacks, stacked over pod for shard_map
    stages = split_periods(params, n_stages)
    stage_params = jax.tree.map(
        lambda *ls: jnp.stack(ls), *stages)  # (pod, per, ...)

    dt = jnp.dtype(cfg.dtype)
    x_emb = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    S = x_emb.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    def stage_fn(pp, xin):
        # run this stage's periods over one microbatch
        def body(h, xs):
            h, _ = lm._apply_period(cfg, xs, h, positions,
                                    {f"l{i}": {} for i in
                                     range(len(cfg.period()))}, "train")
            return h, None

        out, _ = jax.lax.scan(body, xin, pp)
        return out

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pod"), P(None, "data", None, None)),
        out_specs=P(None, "data", None, None), check_vma=False)
    def run(pp, micro):
        # pp: (1, per, ...) this pod's stage params; micro: (M, b, S, d)
        pp = jax.tree.map(lambda l: l[0], pp)
        stage = jax.lax.axis_index("pod")
        M = micro.shape[0]
        n_ticks = M + n_stages - 1

        def tick(carry, t):
            buf, outs = carry   # buf: activation arriving at this stage
            mb_idx = t - stage
            take = jnp.logical_and(mb_idx >= 0, mb_idx < M)
            xin = jnp.where(stage == 0,
                            micro[jnp.clip(t, 0, M - 1)], buf)
            y = stage_fn(pp, xin)
            # pass activation to the next stage
            buf_next = jax.lax.ppermute(
                y, "pod", [(i, i + 1) for i in range(n_stages - 1)])
            # last stage records finished microbatches
            outs = jnp.where(
                jnp.logical_and(stage == n_stages - 1, take),
                jax.lax.dynamic_update_slice_in_dim(
                    outs, y[None], jnp.clip(mb_idx, 0, M - 1), axis=0),
                outs)
            return (buf_next, outs), None

        b = micro.shape[1]
        buf0 = jnp.zeros((b, S, cfg.d_model), dt)
        outs0 = jnp.zeros((M, b, S, cfg.d_model), dt)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all pods
        # (masked psum — ppermute pairs must be unique src/dst)
        outs = jnp.where(stage == n_stages - 1, outs, 0.0)
        outs = jax.lax.psum(outs, "pod")
        return outs

    micro = x_emb.reshape(n_micro, B // n_micro, S, cfg.d_model)
    outs = run(stage_params, micro)
    x = outs.reshape(B, S, cfg.d_model)
    return lm.rms_norm(x, params["final_ln"], cfg.norm_eps)
