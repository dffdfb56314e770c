"""Unified layer-stack for every assigned architecture.

A model backbone is a *pattern* of block configs repeated ``n_rep`` times and
executed with ``jax.lax.scan`` over the repeats (params stacked on a leading
``layers`` dim).  This covers:

- dense transformers          pattern = [attn+dense]           × L
- MoE transformers            pattern = [attn+moe]             × L
- mamba2                      pattern = [ssd+none]             × L
- jamba hybrid                pattern = 8 blocks (1 attn + 7 ssd, MoE on odd
                              positions)                        × L/8

Scanning over repeats is what keeps the lowered HLO (and 512-way SPMD
partitioning time) small and is also Whale's "cluster repeated substructures"
idea applied to compilation: one pattern body is partitioned once, × n_rep.

Each block: pre-norm mixer (attention | SSD) + pre-norm MLP (dense | MoE),
residual connections, optional remat (checkpoint) around the whole repeat.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.sharding import constrain
from repro.models import attention as attn_mod
from repro.models import layers, mamba2, moe as moe_mod
from repro.models.attention import AttnCfg
from repro.models.mamba2 import SSDCfg
from repro.models.moe import MoECfg


@dataclasses.dataclass(frozen=True)
class BlockCfg:
    d_model: int
    mixer: str = "attn"                  # "attn" | "ssd"
    mlp: str = "dense"                   # "dense" | "moe" | "none"
    attn: AttnCfg | None = None
    ssd: SSDCfg | None = None
    moe: MoECfg | None = None
    d_ff: int = 0
    norm: str = "rms"
    act: str = "silu"
    gated_mlp: bool = True


@dataclasses.dataclass(frozen=True)
class StackCfg:
    pattern: tuple                        # tuple[BlockCfg, ...]
    n_rep: int
    remat: str = "full"                   # "none" | "full" | "dots"
    scan: bool = True
    attn_block_q: int = 512
    attn_block_k: int = 512
    attn_wedge: bool = False              # causal block skipping (perf opt)
    attn_impl: str = "ref"                # "ref" | "pallas" (fwd+bwd fused)
    ssd_impl: str = "ref"                 # "ref" | "pallas"
    attn_bwd_remat: bool = False          # flash-style backward (perf opt)
    kv_cache_dtype: str = "bfloat16"      # "bfloat16" | "int8" (serving opt)

    @property
    def n_layers(self) -> int:
        return len(self.pattern) * self.n_rep


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, cfg: BlockCfg, dtype) -> dict:
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    norm_init, _, _ = layers.make_norm(cfg.norm)
    p: dict[str, Any] = {"norm1": norm_init(cfg.d_model, dtype)}
    if cfg.mixer == "attn":
        p["attn"] = attn_mod.init_attention(km, cfg.attn, dtype)
    else:
        p["ssd"] = mamba2.init_ssd(km, cfg.ssd, dtype)
    if cfg.mlp != "none":
        p["norm2"] = norm_init(cfg.d_model, dtype)
        if cfg.mlp == "moe":
            p["moe"] = moe_mod.init_moe(kf, cfg.moe, dtype)
        else:
            p["mlp"] = layers.init_mlp(kf, cfg.d_model, cfg.d_ff, dtype,
                                       gated=cfg.gated_mlp)
    return p


def axes_block(cfg: BlockCfg) -> dict:
    _, norm_axes, _ = layers.make_norm(cfg.norm)
    a: dict[str, Any] = {"norm1": norm_axes()}
    if cfg.mixer == "attn":
        a["attn"] = attn_mod.axes_attention(cfg.attn)
    else:
        a["ssd"] = mamba2.axes_ssd(cfg.ssd)
    if cfg.mlp != "none":
        a["norm2"] = norm_axes()
        if cfg.mlp == "moe":
            a["moe"] = moe_mod.axes_moe(cfg.moe)
        else:
            a["mlp"] = layers.axes_mlp(gated=cfg.gated_mlp)
    return a


def mixer_scope(cfg: BlockCfg) -> str:
    """The ``jax.named_scope`` of a block's mixer sub-layer (DESIGN.md §13)."""
    return "attention" if cfg.mixer == "attn" else "ssd"


def mlp_scope(cfg: BlockCfg) -> str:
    """The ``jax.named_scope`` of a block's MLP sub-layer (DESIGN.md §13)."""
    return "moe" if cfg.mlp == "moe" else "mlp"


def _zero_aux() -> dict:
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32)}


def apply_block(params: dict, x: jax.Array, positions: jax.Array,
                cfg: BlockCfg, stack: StackCfg, *, return_kv: bool = False):
    """x: (B, S, E) → (x', aux, kv-or-None)."""
    _, _, norm = layers.make_norm(cfg.norm)
    aux = _zero_aux()
    kv = None
    with jax.named_scope(mixer_scope(cfg)):
        h = norm(params["norm1"], x)
        if cfg.mixer == "attn":
            out = attn_mod.attention(
                params["attn"], h, positions, cfg.attn,
                block_q=stack.attn_block_q, block_k=stack.attn_block_k,
                wedge=stack.attn_wedge, return_kv=return_kv,
                impl=stack.attn_impl, bwd_remat=stack.attn_bwd_remat)
            if return_kv:
                out, kv = out
        else:
            out = mamba2.ssd_block(params["ssd"], h, cfg.ssd,
                                   impl=stack.ssd_impl)
        x = x + out
    if cfg.mlp != "none":
        with jax.named_scope(mlp_scope(cfg)):
            h = norm(params["norm2"], x)
            if cfg.mlp == "moe":
                out, moe_aux = moe_mod.moe_block(params["moe"], h, cfg.moe)
                aux = {"lb_loss": moe_aux["lb_loss"],
                       "z_loss": moe_aux["z_loss"]}
            else:
                out = layers.mlp(params["mlp"], h, act=cfg.act)
            x = x + out
    x = constrain(x, ("batch", "seq", None))
    return x, aux, kv


def decode_block(params: dict, x: jax.Array, state: dict, pos: jax.Array,
                 cfg: BlockCfg):
    """x: (B, E) one token; state: kv cache or ssd state for this block."""
    _, _, norm = layers.make_norm(cfg.norm)
    with jax.named_scope(mixer_scope(cfg)):
        h = norm(params["norm1"], x[:, None, :])[:, 0]
        if cfg.mixer == "attn":
            if "k_sc" in state:              # int8 KV cache
                out, k_new, v_new, ks, vs = attn_mod.decode_attention(
                    params["attn"], h, state["k"], state["v"], pos, cfg.attn,
                    k_sc=state["k_sc"], v_sc=state["v_sc"])
                state = {"k": k_new, "v": v_new, "k_sc": ks, "v_sc": vs}
            else:
                out, k_new, v_new = attn_mod.decode_attention(
                    params["attn"], h, state["k"], state["v"], pos, cfg.attn)
                state = {"k": k_new, "v": v_new}
        else:
            out, state = mamba2.ssd_decode_step(params["ssd"], h, state,
                                                cfg.ssd)
        x = x + out
    if cfg.mlp != "none":
        x = _decode_mlp(params, x, cfg, norm)
    return x, state


def _decode_mlp(params: dict, x: jax.Array, cfg: BlockCfg, norm):
    """The MLP sub-layer of one decoded token: pre-norm, body, residual."""
    with jax.named_scope(mlp_scope(cfg)):
        h = norm(params["norm2"], x[:, None, :])
        if cfg.mlp == "moe":
            out, _ = moe_mod.moe_block(params["moe"], h, cfg.moe)
        else:
            out = layers.mlp(params["mlp"], h, act=cfg.act)
        return x + out[:, 0]


def init_block_state(cfg: BlockCfg, batch: int, max_len: int, dtype,
                     kv_dtype: str = "bfloat16") -> dict:
    if cfg.mixer == "attn":
        a = cfg.attn
        shape = (batch, max_len, a.n_kv_heads, a.head_dim)
        if kv_dtype == "int8":
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_sc": jnp.zeros(shape[:3], jnp.float32),
                    "v_sc": jnp.zeros(shape[:3], jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return mamba2.init_ssd_state(batch, cfg.ssd, dtype)


def axes_block_state(cfg: BlockCfg, kv_dtype: str = "bfloat16") -> dict:
    if cfg.mixer == "attn":
        n = ("batch", "kv_seq", "kv_heads", None)
        a = {"k": n, "v": n}
        if kv_dtype == "int8":
            a["k_sc"] = ("batch", "kv_seq", "kv_heads")
            a["v_sc"] = ("batch", "kv_seq", "kv_heads")
        return a
    return mamba2.axes_ssd_state()


# ---------------------------------------------------------------------------
# stack (scan over pattern repeats)
# ---------------------------------------------------------------------------

def init_stack(key, stack: StackCfg, dtype) -> dict:
    params = {}
    for i, bcfg in enumerate(stack.pattern):
        keys = jax.random.split(jax.random.fold_in(key, i), stack.n_rep)
        params[f"p{i}"] = jax.vmap(lambda k: init_block(k, bcfg, dtype))(keys)
    return params


def axes_stack(stack: StackCfg) -> dict:
    axes = {}
    for i, bcfg in enumerate(stack.pattern):
        ax = axes_block(bcfg)
        axes[f"p{i}"] = jax.tree.map(lambda t: ("layers",) + t, ax,
                                     is_leaf=lambda t: isinstance(t, tuple))
    return axes


def _remat_wrap(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)  # "full": save nothing


def apply_stack(params: dict, x: jax.Array, positions: jax.Array,
                stack: StackCfg):
    """x: (B, S, E) → (x', summed aux)."""

    def rep_body(x, rep_params):
        aux = _zero_aux()
        for i, bcfg in enumerate(stack.pattern):
            x, a, _ = apply_block(rep_params[f"p{i}"], x, positions, bcfg, stack)
            aux = jax.tree.map(jnp.add, aux, a)
        return x, aux

    body = _remat_wrap(rep_body, stack.remat)
    if stack.scan and stack.n_rep > 1:
        x, auxs = jax.lax.scan(lambda c, p: body(c, p), x, params)
        aux = jax.tree.map(lambda a: a.sum(0), auxs)
    else:
        aux = _zero_aux()
        for r in range(stack.n_rep):
            rep_params = jax.tree.map(lambda p: p[r], params)
            x, a = body(x, rep_params)
            aux = jax.tree.map(jnp.add, aux, a)
    return x, aux


def prefill_stack(params: dict, x: jax.Array, positions: jax.Array,
                  stack: StackCfg):
    """Forward returning per-block KV caches (attn) for subsequent decode."""

    def rep_body(x, rep_params):
        kvs = {}
        for i, bcfg in enumerate(stack.pattern):
            x, _, kv = apply_block(rep_params[f"p{i}"], x, positions, bcfg,
                                   stack, return_kv=(bcfg.mixer == "attn"))
            if bcfg.mixer == "attn":
                kvs[f"p{i}"] = {"k": kv[0], "v": kv[1]}
        return x, kvs

    if stack.scan and stack.n_rep > 1:
        x, caches = jax.lax.scan(rep_body, x, params)
    else:
        caches_list = []
        for r in range(stack.n_rep):
            rep_params = jax.tree.map(lambda p: p[r], params)
            x, kvs = rep_body(x, rep_params)
            caches_list.append(kvs)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches_list)
    return x, caches


def init_stack_state(stack: StackCfg, batch: int, max_len: int, dtype) -> dict:
    state = {}
    for i, bcfg in enumerate(stack.pattern):
        s = init_block_state(bcfg, batch, max_len, dtype,
                             kv_dtype=stack.kv_cache_dtype)
        state[f"p{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (stack.n_rep,) + a.shape), s)
    return state


def axes_stack_state(stack: StackCfg) -> dict:
    axes = {}
    for i, bcfg in enumerate(stack.pattern):
        ax = axes_block_state(bcfg, kv_dtype=stack.kv_cache_dtype)
        axes[f"p{i}"] = jax.tree.map(lambda t: ("layers",) + t, ax,
                                     is_leaf=lambda t: isinstance(t, tuple))
    return axes


def decode_stack(params: dict, x: jax.Array, state: dict, pos: jax.Array,
                 stack: StackCfg):
    """x: (B, E) → (x', state').  Scans blocks, threading per-layer state."""

    def rep_body(x, inp):
        rep_params, rep_state = inp
        new_state = {}
        for i, bcfg in enumerate(stack.pattern):
            x, s = decode_block(rep_params[f"p{i}"], x, rep_state[f"p{i}"],
                                pos, bcfg)
            new_state[f"p{i}"] = s
        return x, new_state

    if stack.scan and stack.n_rep > 1:
        x, new_state = jax.lax.scan(rep_body, x, (params, state))
    else:
        outs = []
        for r in range(stack.n_rep):
            rp = jax.tree.map(lambda p: p[r], params)
            rs = jax.tree.map(lambda s: s[r], state)
            x, s = rep_body(x, (rp, rs))
            outs.append(s)
        new_state = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, new_state


# ---------------------------------------------------------------------------
# paged decode (block/paged KV cache — serving tier, DESIGN.md §9)
# ---------------------------------------------------------------------------

def _check_paged(stack: StackCfg):
    if any(b.mixer != "attn" for b in stack.pattern):
        raise ValueError(
            "paged KV decode requires an all-attention pattern (SSD state "
            "is O(1) per slot and gains nothing from paging); pattern has "
            f"mixers {[b.mixer for b in stack.pattern]}")
    if stack.kv_cache_dtype == "int8":
        raise ValueError(
            "paged KV decode does not support the int8 KV cache yet — "
            "page pools are kept in the activation dtype")


def init_paged_stack_state(stack: StackCfg, n_pages: int, page_size: int,
                           dtype) -> dict:
    """Per-pattern-position page pools ``(n_rep, n_pages, K, page_size, D)``
    (head-major, so a page of one kv head is a ``(page_size, D)`` tile).

    Pools are *slot-free*: every decode slot shares them through its block
    table row, which is what lets short sequences stop reserving
    ``max_len`` KV rows each.
    """
    _check_paged(stack)
    pools = {}
    for i, bcfg in enumerate(stack.pattern):
        a = bcfg.attn
        shape = (n_pages, a.n_kv_heads, page_size, a.head_dim)
        s = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        pools[f"p{i}"] = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (stack.n_rep,) + t.shape), s)
    return pools


def axes_paged_stack_state(stack: StackCfg) -> dict:
    """Pools shard like the dense cache minus the batch dim: pages and
    rows replicated, kv heads on the model axis."""
    _check_paged(stack)
    n = ("layers", None, "kv_heads", None, None)
    return {f"p{i}": {"k": n, "v": n} for i in range(len(stack.pattern))}


def paged_decode_block(params: dict, x: jax.Array, pools: dict,
                       block_table: jax.Array, pos: jax.Array,
                       cfg: BlockCfg, stack: StackCfg):
    """Paged twin of :func:`decode_block` for one attention block."""
    _, _, norm = layers.make_norm(cfg.norm)
    with jax.named_scope(mixer_scope(cfg)):
        h = norm(params["norm1"], x[:, None, :])[:, 0]
        out, k_pool, v_pool = attn_mod.paged_decode_attention(
            params["attn"], h, pools["k"], pools["v"], block_table, pos,
            cfg.attn, impl=stack.attn_impl)
        pools = {"k": k_pool, "v": v_pool}
        x = x + out
    if cfg.mlp != "none":
        x = _decode_mlp(params, x, cfg, norm)
    return x, pools


def decode_stack_paged(params: dict, x: jax.Array, pools: dict,
                       block_table: jax.Array, pos: jax.Array,
                       stack: StackCfg):
    """x: (B, E) → (x', pools').  :func:`decode_stack` against page pools;
    the block table and positions are shared by every layer."""
    _check_paged(stack)

    def rep_body(x, inp):
        rep_params, rep_pools = inp
        new_pools = {}
        for i, bcfg in enumerate(stack.pattern):
            x, p = paged_decode_block(rep_params[f"p{i}"], x,
                                      rep_pools[f"p{i}"], block_table, pos,
                                      bcfg, stack)
            new_pools[f"p{i}"] = p
        return x, new_pools

    if stack.scan and stack.n_rep > 1:
        x, new_pools = jax.lax.scan(rep_body, x, (params, pools))
    else:
        outs = []
        for r in range(stack.n_rep):
            rp = jax.tree.map(lambda p: p[r], params)
            rs = jax.tree.map(lambda s: s[r], pools)
            x, s = rep_body(x, (rp, rs))
            outs.append(s)
        new_pools = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, new_pools
