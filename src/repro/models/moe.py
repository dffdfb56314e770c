"""Mixture-of-Experts: top-k routing with sort-based capacity dispatch.

Sharding strategy (selected implicitly by the Multi-Dimension rules +
divisibility pruning, no model-code branches):

- *Expert parallelism* (EP): when n_experts divides the model axis
  (deepseek-moe 64/16, jamba 16/16) the `experts` dim of both the dispatch
  buffers and the expert weights shards over `model`; dispatch is comm-free
  because activations are model-replicated under the hybrid strategy, and the
  combine lowers to one (B, S, D) all-reduce — the same bytes as a Megatron
  TP MLP.
- *Expert tensor parallelism*: when it doesn't (grok-1: 8 experts on a 16-way
  axis) the `experts` dim prunes and the `expert_mlp` (d_ff) dim takes the
  model axis instead — every shard holds a 1/16 slice of every expert and the
  combine is the standard row-parallel partial-sum all-reduce.

Dispatch is sort-based (argsort over token→expert assignments, rank-in-expert
capacity cutoff) rather than one-hot-einsum based, so no (B, S, E, C) tensor
is ever materialised — the buffers are O(B · E · C · D).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.sharding import constrain
from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MoECfg:
    d_model: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0            # shared (always-on) experts, deepseek-style
    capacity_factor: float = 1.25
    act: str = "silu"
    router_z_coef: float = 1e-3
    lb_coef: float = 1e-2

    def capacity(self, seq_len: int) -> int:
        c = int(seq_len * self.top_k * self.capacity_factor / self.n_experts) + 1
        return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


def init_moe(key, cfg: MoECfg, dtype) -> dict:
    kr, k1, kg, k2, ks = jax.random.split(key, 5)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": {"w": layers.dense_init(kr, D, (D, E), jnp.float32)},
        "w_in": layers.dense_init(k1, D, (E, D, F), dtype),
        "w_gate": layers.dense_init(kg, D, (E, D, F), dtype),
        "w_out": layers.dense_init(k2, F, (E, F, D), dtype),
    }
    if cfg.n_shared:
        p["shared"] = layers.init_mlp(ks, D, F * cfg.n_shared, dtype, gated=True)
    return p


def axes_moe(cfg: MoECfg) -> dict:
    a = {
        "router": {"w": ("embed", None)},           # router stays replicated
        "w_in": ("experts", "embed", "expert_mlp"),
        "w_gate": ("experts", "embed", "expert_mlp"),
        "w_out": ("experts", "expert_mlp", "embed"),
    }
    if cfg.n_shared:
        a["shared"] = layers.axes_mlp(gated=True)
    return a


def _dispatch_indices(expert_idx: jax.Array, weights: jax.Array, E: int, C: int,
                      seq_len: int):
    """expert_idx/weights: (B, S, k) → per-slot token indices + weights.

    Returns tok (B, E, C) int32 in [0, S] (S = dropped) and w (B, E, C) f32.
    """
    B, S, k = expert_idx.shape
    T = S * k
    flat_e = expert_idx.reshape(B, T)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    # rank of each assignment within its expert = i - first index of expert
    start = jax.vmap(lambda s: jnp.searchsorted(s, s, side="left"))(sorted_e)
    rank = jnp.arange(T)[None, :] - start
    valid = rank < C
    slot = jnp.where(valid, sorted_e * C + rank, E * C)   # E*C = dropped sentinel
    tok_sorted = order // k
    w_sorted = jnp.take_along_axis(weights.reshape(B, T), order, axis=-1)

    tok = jnp.full((B, E * C), seq_len, jnp.int32)
    tok = jax.vmap(lambda t, s, v: t.at[s].set(v, mode="drop"))(tok, slot, tok_sorted)
    wbuf = jnp.zeros((B, E * C), jnp.float32)
    wbuf = jax.vmap(lambda t, s, v: t.at[s].set(v, mode="drop"))(wbuf, slot, w_sorted)
    return tok.reshape(B, E, C), wbuf.reshape(B, E, C)


def _route(params: dict, x: jax.Array, cfg: MoECfg):
    """Per-token routing (f32): (logits, normalized top-k weights, expert
    ids, per-batch mean prob `me`, per-batch assignment fraction `ce`).
    The means are over the *local* batch — callers running batch-sharded
    (moe_block_ep) pmean them to the global mean."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    w_topk, e_idx = jax.lax.top_k(probs, cfg.top_k)                # (B, S, k)
    w_topk = w_topk / jnp.maximum(w_topk.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=(0, 1))                                   # (E,)
    ce = jnp.mean(jax.nn.one_hot(e_idx, cfg.n_experts, dtype=jnp.float32),
                  axis=(0, 1, 2))
    return logits, w_topk, e_idx, me, ce


def _aux_losses(cfg: MoECfg, me, ce, mean_sq_lse):
    """Load balance (GShard-style) + router z-loss from routing stats."""
    lb_loss = cfg.lb_coef * cfg.n_experts * jnp.sum(me * ce)
    z_loss = cfg.router_z_coef * mean_sq_lse
    return lb_loss, z_loss


def _expert_ffn(params: dict, xin: jax.Array, cfg: MoECfg, dtype):
    """SwiGLU over per-expert capacity buffers: (..., E', C, D) →
    (..., E', C, D) with the experts dim of the weights matching E'."""
    h = jnp.einsum("becd,edf->becf", xin, params["w_in"].astype(dtype))
    g = jnp.einsum("becd,edf->becf", xin, params["w_gate"].astype(dtype))
    h = layers._ACTS[cfg.act](g) * h
    h = constrain(h, ("batch", "experts", None, "expert_mlp"))
    return jnp.einsum("becf,efd->becd", h, params["w_out"].astype(dtype))


def _combine(tok: jax.Array, out: jax.Array, seq_len: int) -> jax.Array:
    """Weighted capacity buffers (B, E, C, D) → (B, S, D) scatter-add."""
    B = out.shape[0]
    D = out.shape[-1]
    y = jnp.zeros((B, seq_len, D), out.dtype)
    return jax.vmap(
        lambda yb, tb, ub: yb.at[tb.reshape(-1)].add(
            ub.reshape(-1, D), mode="drop")
    )(y, tok, out)


def moe_block(params: dict, x: jax.Array, cfg: MoECfg):
    """x: (B, S, D) → (B, S, D), aux-loss dict."""
    B, S, D = x.shape
    E = cfg.n_experts
    C = cfg.capacity(S)

    # --- routing (f32; replicated over the model axis) ---
    logits, w_topk, e_idx, me, ce = _route(params, x, cfg)
    lb_loss, z_loss = _aux_losses(cfg, me, ce, jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1))))

    tok, w = _dispatch_indices(e_idx, w_topk, E, C, S)             # (B, E, C)
    tok = constrain(tok, ("batch", "experts", None))
    w = constrain(w, ("batch", "experts", None))
    tok_safe = jnp.minimum(tok, S - 1)

    # --- dispatch gather: (B, E, C, D); sharded batch × experts ---
    xin = jax.vmap(lambda xb, tb: xb[tb])(x, tok_safe)
    xin = constrain(xin, ("batch", "experts", None, None))

    # --- expert FFN (SwiGLU) ---
    out = _expert_ffn(params, xin, cfg, x.dtype)
    out = out * w[..., None].astype(out.dtype)
    out = constrain(out, ("batch", "experts", None, None))

    # --- combine scatter-add back to (B, S, D) (partial sums → all-reduce) ---
    y = _combine(tok, out, S)
    y = constrain(y, ("batch", None, None))

    if cfg.n_shared:
        y = y + layers.mlp(params["shared"], x, act=cfg.act)
    aux = {"lb_loss": lb_loss, "z_loss": z_loss,
           "expert_load": jax.lax.stop_gradient(ce)}
    return y, aux


# ---------------------------------------------------------------------------
# explicit expert parallelism: the nested replica{split[experts]} executor
# ---------------------------------------------------------------------------

def moe_block_ep(params: dict, x: jax.Array, cfg: MoECfg, mesh, *,
                 axis: str = "expert"):
    """Expert-parallel `moe_block` via an explicit ``shard_map``.

    The graph optimizer's ``replica{split[experts]}`` lowering made
    concrete (graph_opt.plan_bridge's ``all_to_all`` bridges as real
    collectives): the batch shards over the ``axis`` mesh axis, expert
    weights shard their leading ``experts`` dim over the same axis, and
    dispatch/combine are ``jax.lax.all_to_all`` exchanges —

    - *dispatch*: each shard routes its local tokens into per-expert
      capacity buffers, then all-to-all regroups them so shard ``e`` holds
      **every** batch shard's tokens for **its** experts
      ((B/ep, E, C, D) → (B, E/ep, C, D));
    - *combine*: the reverse all-to-all returns expert outputs to their
      home batch shard, where the weighted scatter-add rebuilds (B/ep, S, D).

    Routing (and its aux losses, ``pmean``-ed to the global batch mean) is
    per-token, and the reference's capacity cutoff is per (batch-row,
    expert) — batch sharding therefore commutes with dispatch and the
    result equals single-device :func:`moe_block` to fp32 tolerance
    (asserted by tests/test_distributed.py), forward *and* backward: the
    all-to-all is its own autodiff transpose, and replicated-in params
    (the router) get their gradient ``psum`` from the shard_map transpose.
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape[axis]
    B = x.shape[0]
    E = cfg.n_experts
    if E % ep:
        raise ValueError(
            f"expert parallelism needs n_experts % ep == 0; "
            f"got E={E} over {ep}-way axis {axis!r}")
    if B % ep:
        raise ValueError(
            f"expert parallelism shards the batch over {axis!r}: "
            f"batch {B} % ep {ep} != 0")

    def body(p, xl):
        S = xl.shape[1]
        C = cfg.capacity(S)

        # routing on the local batch shard; aux stats pmean to the global
        # batch mean (routing is per-token, so sharding commutes)
        logits, w_topk, e_idx, me, ce = _route(p, xl, cfg)
        me = jax.lax.pmean(me, axis)
        ce = jax.lax.pmean(ce, axis)
        lb_loss, z_loss = _aux_losses(cfg, me, ce, jax.lax.pmean(jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))), axis))

        tok, w = _dispatch_indices(e_idx, w_topk, E, C, S)
        tok_safe = jnp.minimum(tok, S - 1)
        xin = jax.vmap(lambda xb, tb: xb[tb])(xl, tok_safe)   # (Bl, E, C, D)

        # dispatch bridge: shard e receives every batch shard's tokens for
        # its own E/ep experts
        xg = jax.lax.all_to_all(xin, axis, split_axis=1, concat_axis=0,
                                tiled=True)                   # (B, E/ep, C, D)
        out = _expert_ffn(p, xg, cfg, xl.dtype)

        # combine bridge: expert outputs return to their home batch shard
        out = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=1,
                                 tiled=True)                  # (Bl, E, C, D)
        out = out * w[..., None].astype(out.dtype)
        y = _combine(tok, out, S)

        if cfg.n_shared:
            y = y + layers.mlp(p["shared"], xl, act=cfg.act)
        aux = {"lb_loss": lb_loss, "z_loss": z_loss,
               "expert_load": jax.lax.stop_gradient(ce)}
        return y, aux

    pspec = {
        "router": {"w": P()},
        "w_in": P(axis),            # experts is the leading weight dim
        "w_gate": P(axis),
        "w_out": P(axis),
    }
    if "shared" in params:
        pspec["shared"] = jax.tree.map(lambda _: P(), params["shared"])
    aux_spec = {"lb_loss": P(), "z_loss": P(), "expert_load": P()}
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(pspec, P(axis)),
                       out_specs=(P(axis), aux_spec), check_vma=False)
    return fn(params, x)
