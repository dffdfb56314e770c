"""Attention for the LM family: blocked (flash-style) training/prefill paths
and a flash-decode serving path, all strategy-agnostic via logical axes.

Layouts
-------
Grouped-query attention is computed in the *grouped* layout
``q: (B, S, K, G, D)`` vs ``k/v: (B, S, K, D)`` (K = kv heads, G = query group
size) so the KV tensors are never materialised per query head.  When the
planner wants query-head tensor parallelism but K does not divide the model
axis (e.g. grok-1: K=8 on a 16-way axis), KV is physically repeated to the
48 query heads ("repeat" layout, K←Hq, G←1) — the repeat is cheap relative to
scores and lets GSPMD shard the head dim.  When neither head count divides
(gemma-2b: 8 heads, qwen2-vl: 12 heads), the query *sequence* is sharded
instead ("seq" layout) with KV replicated — MQA-style context parallelism.

The training path is a blocked online-softmax (flash) computation expressed
with `lax.scan` over KV blocks so the lowered HLO never materialises the
(S, S) score matrix — this is what makes the 32k prefill dry-run fit HBM.
``wedge=True`` additionally skips fully-masked KV blocks (python-unrolled
per-q-block prefix lengths → ~2× fewer attention FLOPs for causal), used by
the perf hillclimb.

The decode path writes the partial-softmax combine explicitly (local max /
sumexp / weighted values, then tiny cross-shard reductions) so that a KV
cache sharded along the sequence dim lowers to flash-decode-style collectives
instead of an all-gather of the cache.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.sharding import constrain, current_rules
from repro.models import layers

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 10000.0
    mrope_sections: tuple | None = None
    causal: bool = True
    use_rope: bool = True

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: AttnCfg, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    E, H, K, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": layers.dense_init(kq, E, (E, H, D), dtype),
        "wk": layers.dense_init(kk, E, (E, K, D), dtype),
        "wv": layers.dense_init(kv, E, (E, K, D), dtype),
        "wo": layers.dense_init(ko, H * D, (H, D, E), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rmsnorm(D, dtype)
        p["k_norm"] = layers.init_rmsnorm(D, dtype)
    return p


def axes_attention(cfg: AttnCfg) -> dict:
    a = {
        "wq": ("embed", "q_heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("q_heads", "head_dim", "embed"),
    }
    if cfg.qk_norm:
        a["q_norm"] = {"scale": ("head_dim",)}
        a["k_norm"] = {"scale": ("head_dim",)}
    return a


def choose_layout(cfg: AttnCfg) -> str:
    """Pick grouped / repeat / seq per the active sharding rules (see module doc)."""
    rules = current_rules()
    if rules is None:
        return "grouped"
    tp = rules.axis_size(rules.rules.get("kv_heads"))
    if cfg.n_kv_heads % tp == 0:
        return "grouped"
    if cfg.n_heads % tp == 0:
        return "repeat"
    return "seq"


# ---------------------------------------------------------------------------
# blocked (flash-style) attention core — grouped layout
# ---------------------------------------------------------------------------

def _blocked_gqa(q, k, v, *, causal: bool, block_q: int, block_k: int,
                 wedge: bool = False, kv_offset: int = 0,
                 bwd_remat: bool = False):
    """q: (B, Sq, K, G, D)  k/v: (B, Sk, K, D)  →  (B, Sq, K, G, D) float32 acc.

    kv_offset: absolute position of q[0] minus k[0] (for cross/chunked use).
    bwd_remat: checkpoint the kv-block step so the backward *recomputes* each
    (block_q, block_k) score tile instead of saving it — the flash-attention
    backward memory/traffic profile (otherwise autodiff of the scan stacks
    every score tile, i.e. the full (Sq, Sk) matrix, as residuals).
    """
    B, Sq, K, G, D = q.shape
    Sk = k.shape[1]
    # a block larger than the sequence is benign (one block); a block that
    # does not DIVIDE the sequence is not — silently rewriting it changed
    # the user's tiling (and FLOP/memory profile) behind their back.  Match
    # the PR 3 truncated-reshape precedent: fail loudly instead.
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"block_q/block_k ({block_q}, {block_k}) must divide the "
            f"sequence lengths ({Sq}, {Sk}); pick dividing blocks (e.g. via "
            f"repro.kernels.autotune.fit_block) instead of relying on "
            f"silent rounding")
    nq = Sq // block_q
    nk = Sk // block_k
    scale = 1.0 / (D ** 0.5)

    qb = q.reshape(B, nq, block_q, K, G, D)
    kb = k.reshape(B, nk, block_k, K, D)
    vb = v.reshape(B, nk, block_k, K, D)

    def kv_step(carry, inputs):
        m, l, acc, qi = carry
        kj, vj, j = inputs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jnp.arange(block_q) + kv_offset
            kpos = j * block_k + jnp.arange(block_k)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new, qi), None

    def one_q_block(i, qi, nk_i):
        nonlocal q_start
        q_start = i * block_q
        m0 = jnp.full((B, K, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, K, G, block_q, D), jnp.float32)
        ks = jnp.moveaxis(kb[:, :nk_i], 1, 0)
        vs = jnp.moveaxis(vb[:, :nk_i], 1, 0)
        js = jnp.arange(nk_i)
        step = jax.checkpoint(kv_step) if bwd_remat else kv_step
        (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, qi), (ks, vs, js))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)  # (B, block_q, K, G, D)

    q_start = 0
    if wedge and causal and nq > 1:
        # python-unrolled prefix lengths: q block i attends kv blocks [0, ...]
        outs = []
        for i in range(nq):
            hi = ((i + 1) * block_q + kv_offset + block_k - 1) // block_k
            hi = max(1, min(nk, hi))
            outs.append(one_q_block(i, qb[:, i], hi))
        out = jnp.stack(outs, axis=1)
    else:
        idx = jnp.arange(nq)
        out = jax.vmap(lambda i, qi: one_q_block(i, qi, nk),
                       in_axes=(0, 1), out_axes=1)(idx, qb)
    return out.reshape(B, Sq, K, G, D)


# ---------------------------------------------------------------------------
# full self-attention layer (training / prefill)
# ---------------------------------------------------------------------------

def attention(params: dict, x: jax.Array, positions: jax.Array, cfg: AttnCfg,
              *, block_q: int = 512, block_k: int = 512, wedge: bool = False,
              return_kv: bool = False, impl: str = "ref",
              bwd_remat: bool = False):
    """x: (B, S, E) → (B, S, E); optionally also the (B, S, K, D) kv tensors.

    ``impl="pallas"``: the score/softmax/value core runs in the Pallas flash
    kernel, fwd AND bwd — the kernel carries a custom VJP whose backward
    recomputes score tiles in VMEM (training-grade since PR 6).
    ``bwd_remat``: flash-style backward residual policy — recompute ``o``
    from (q, k, v, lse) in the backward instead of saving it (pallas path),
    or checkpoint the kv-block scan step (ref path)."""
    B, S, E = x.shape
    K, G, D = cfg.n_kv_heads, cfg.group, cfg.head_dim
    layout = choose_layout(cfg)

    q = jnp.einsum("bse,ehd->bshd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bse,ekd->bskd", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bse,ekd->bskd", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
        k = layers.rmsnorm(params["k_norm"], k)
    if cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = layers.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    kv_out = (k, v) if return_kv else None

    if layout == "repeat":
        k = jnp.repeat(k, G, axis=2)          # (B, S, H, D)
        v = jnp.repeat(v, G, axis=2)
        qg = q[:, :, :, None, :]              # (B, S, H, 1, D)
        q_names = ("batch", None, "q_heads", None, None)
        kv_names = ("batch", None, "q_heads", None)
    else:
        qg = q.reshape(B, S, K, G, D)
        q_names = ("batch", None, "kv_heads", None, None)
        kv_names = ("batch", None, "kv_heads", None)
    if layout == "seq":
        q_names = ("batch", "q_seq") + q_names[2:]
        rules = current_rules()
        sz = rules.axis_size(rules.rules.get("q_seq")) if rules else 1
        if sz > 1 and S % sz == 0:
            block_q = min(block_q, S // sz)
            wedge = False  # python-unrolled prefixes break even seq sharding
    qg = constrain(qg, q_names)
    k = constrain(k, kv_names)
    v = constrain(v, kv_names)

    if impl == "pallas":
        from repro.kernels import interpret_mode
        from repro.kernels.flash_attention import flash
        Bq, Sq, Kq, Gq, Dq = qg.shape
        out = flash(
            qg.reshape(Bq, Sq, Kq * Gq, Dq), k, v, cfg.causal,
            min(block_q, Sq), min(block_k, S), interpret_mode(), bwd_remat,
        ).reshape(Bq, Sq, Kq, Gq, Dq).astype(jnp.float32)
    else:
        out = _blocked_gqa(qg, k, v, causal=cfg.causal,
                           block_q=block_q, block_k=block_k, wedge=wedge,
                           bwd_remat=bwd_remat)
    out = out.astype(x.dtype).reshape(B, S, cfg.n_heads, D)
    out_names = ("batch", "q_seq" if layout == "seq" else None,
                 "q_heads", None)
    out = constrain(out, out_names)
    y = jnp.einsum("bshd,hde->bse", out, params["wo"].astype(x.dtype))
    y = constrain(y, ("batch", None, None))
    return (y, kv_out) if return_kv else y


# ---------------------------------------------------------------------------
# cross-attention (encoder–decoder)
# ---------------------------------------------------------------------------

def cross_attention(params: dict, x: jax.Array, memory: jax.Array,
                    cfg: AttnCfg, *, block_q: int = 512, block_k: int = 512):
    B, S, E = x.shape
    K, G, D = cfg.n_kv_heads, cfg.group, cfg.head_dim
    q = jnp.einsum("bse,ehd->bshd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bse,ekd->bskd", memory, params["wk"].astype(x.dtype))
    v = jnp.einsum("bse,ekd->bskd", memory, params["wv"].astype(x.dtype))
    qg = constrain(q.reshape(B, S, K, G, D), ("batch", None, "kv_heads", None, None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    out = _blocked_gqa(qg, k, v, causal=False, block_q=block_q, block_k=block_k)
    out = out.astype(x.dtype).reshape(B, S, cfg.n_heads, D)
    return jnp.einsum("bshd,hde->bse", out, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# decode (one token vs a sharded KV cache) — explicit flash-decode combine
# ---------------------------------------------------------------------------

def _decode_qkv(params: dict, x: jax.Array, pos: jax.Array, cfg: AttnCfg):
    """Shared decode-step projections: x (B, E) → q (B, H, D), k/v (B, K, D),
    q/k normed and roped at ``pos``.  Used verbatim by the dense and paged
    decode paths so the two stay numerically identical by construction."""
    B = x.shape[0]
    q = jnp.einsum("be,ehd->bhd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("be,ekd->bkd", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("be,ekd->bkd", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
        k = layers.rmsnorm(params["k_norm"], k)
    if cfg.use_rope:
        posb = pos[:, None] if cfg.mrope_sections is None else \
            jnp.broadcast_to(pos[:, None, None], (B, 3, 1))
        q = layers.apply_rope(q[:, None], posb, cfg.rope_theta, cfg.mrope_sections)[:, 0]
        k = layers.apply_rope(k[:, None], posb, cfg.rope_theta, cfg.mrope_sections)[:, 0]
    return q, k, v


def decode_attention(params: dict, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, pos: jax.Array, cfg: AttnCfg,
                     k_sc: jax.Array | None = None,
                     v_sc: jax.Array | None = None):
    """x: (B, E) one new token per sequence.

    k_cache/v_cache: (B, Smax, K, D), sharded along Smax per the `kv_seq`
    rule.  pos: (B,) int32 — current length (index where the new KV is
    written).  Returns (y (B, E), k_cache', v_cache'[, k_sc', v_sc']).

    **int8 KV cache** (beyond-paper, halves decode HBM/state bytes vs bf16):
    when ``k_sc``/``v_sc`` are given the caches are int8 with per-(token,
    head) f32 scales; new KV is quantised symmetrically on write and
    dequantised in-register on read — HBM only ever sees int8 KV.
    """
    B, E = x.shape
    K, G, D = cfg.n_kv_heads, cfg.group, cfg.head_dim
    Smax = k_cache.shape[1]
    quant = k_sc is not None

    q, k, v = _decode_qkv(params, x, pos, cfg)

    kv_names = ("batch", "kv_seq", "kv_heads", None)
    sc_names = ("batch", "kv_seq", "kv_heads")
    # scatter new kv at pos (one-hot write keeps the cache sharding intact)
    onehot = jax.nn.one_hot(pos, Smax, dtype=jnp.float32)          # (B, Smax)
    if quant:
        def q8(t):                       # (B, K, D) → int8 + (B, K) scale
            s = jnp.maximum(jnp.abs(t.astype(jnp.float32)).max(-1), 1e-30) \
                / 127.0
            qv = jnp.clip(jnp.round(t.astype(jnp.float32) / s[..., None]),
                          -127, 127).astype(jnp.int8)
            return qv, s

        kq, ks = q8(k)
        vq, vs = q8(v)
        oh8 = onehot.astype(jnp.int8)
        k_cache = k_cache + oh8[:, :, None, None] * kq[:, None]
        v_cache = v_cache + oh8[:, :, None, None] * vq[:, None]
        k_sc = k_sc + onehot[:, :, None] * ks[:, None]
        v_sc = v_sc + onehot[:, :, None] * vs[:, None]
        k_sc = constrain(k_sc, sc_names)
        v_sc = constrain(v_sc, sc_names)
        k_read = k_cache.astype(jnp.float32) * k_sc[..., None]
        v_read = v_cache.astype(jnp.float32) * v_sc[..., None]
    else:
        k_cache = k_cache + onehot.astype(k_cache.dtype)[:, :, None, None] \
            * k[:, None, :, :]
        v_cache = v_cache + onehot.astype(v_cache.dtype)[:, :, None, None] \
            * v[:, None, :, :]
        k_read, v_read = k_cache, v_cache
    k_cache = constrain(k_cache, kv_names)
    v_cache = constrain(v_cache, kv_names)

    qg = q.reshape(B, K, G, D)
    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_read,
                   preferred_element_type=jnp.float32) * scale     # (B,K,G,Smax)
    valid = (jnp.arange(Smax)[None, :] <= pos[:, None])            # (B, Smax)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    # explicit max/sumexp so a seq-sharded cache lowers to tiny all-reduces
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_read.dtype), v_read,
                     preferred_element_type=jnp.float32)
    out = (out / jnp.maximum(l, 1e-30)).astype(x.dtype).reshape(B, cfg.n_heads, D)
    y = jnp.einsum("bhd,hde->be", out, params["wo"].astype(x.dtype))
    if quant:
        return y, k_cache, v_cache, k_sc, v_sc
    return y, k_cache, v_cache


# ---------------------------------------------------------------------------
# paged decode (block/paged KV cache — DESIGN.md §9)
# ---------------------------------------------------------------------------

def paged_scatter(pool: jax.Array, block_table: jax.Array, pos: jax.Array,
                  new: jax.Array) -> jax.Array:
    """Write ``new`` (B, K, D) into the page pool cell each slot's ``pos``
    maps to through its block table.

    pool: (P, K, page_size, D); block_table: (B, max_pages) int32 (0 = the
    reserved trash page); pos: (B,).  One-hot outer-product ADD, like the
    dense cache's scatter, so the write is jit-shaped for every slot — but
    writes that resolve to the trash page (inactive slots, unallocated
    entries) are *dropped*, keeping page 0 all-zero forever.  The target
    cell is zero by the allocator invariant (pages are zeroed when
    allocated, each cell written once), so ``0 + new`` stores ``new``
    bit-exactly.
    """
    P, ps = pool.shape[0], pool.shape[2]
    page_idx = pos // ps
    phys = jnp.take_along_axis(block_table, page_idx[:, None], axis=1)[:, 0]
    live = (phys != 0).astype(jnp.float32)
    oh_page = jax.nn.one_hot(phys, P, dtype=jnp.float32) * live[:, None]
    oh_row = jax.nn.one_hot(pos % ps, ps, dtype=jnp.float32)
    delta = jnp.einsum("bp,br,bkd->pkrd", oh_page.astype(pool.dtype),
                       oh_row.astype(pool.dtype), new.astype(pool.dtype))
    return pool + delta


def paged_decode_attention(params: dict, x: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           pos: jax.Array, cfg: AttnCfg, *,
                           impl: str = "ref"):
    """Decode step against a paged KV cache.

    x: (B, E); k_pool/v_pool: (P, K, page_size, D) physical page pools
    shared by all slots; block_table: (B, max_pages) int32 slot→page map
    (entry 0 = the trash page); pos: (B,).  Returns (y, k_pool', v_pool').

    ``impl="ref"`` is *bit-exact* against :func:`decode_attention` on the
    equivalent dense cache by construction: the pool is gathered through
    the block table into a dense-cache-shaped array (identical values —
    unallocated entries gather the all-zero trash page, exactly what the
    dense cache holds beyond ``pos``) and the SAME ``decode_attention``
    runs on it; the new KV is then extracted from the updated gather and
    persisted into the pool.  ``impl="pallas"`` writes the pool first and
    runs the block-table-indexed flash-decode kernel
    (:func:`repro.kernels.flash_attention.paged_decode`) over it.
    """
    B, E = x.shape
    K, G, D = cfg.n_kv_heads, cfg.group, cfg.head_dim
    ps = k_pool.shape[2]
    max_pages = block_table.shape[1]

    def gather(pool):          # (B, max_pages, K, ps, D) → (B, S, K, D)
        return jnp.swapaxes(pool[block_table], 2, 3).reshape(
            B, max_pages * ps, K, D)

    if impl == "ref":
        kd, vd = gather(k_pool), gather(v_pool)
        y, k_upd, v_upd = decode_attention(params, x, kd, vd, pos, cfg)
        # the pos cell was zero pre-add, so the one-hot row-pick recovers
        # the freshly written post-rope k/v exactly (1·k + Σ 0·finite = k)
        oh = jax.nn.one_hot(pos, max_pages * ps, dtype=k_upd.dtype)
        k_new = jnp.einsum("bs,bskd->bkd", oh, k_upd)
        v_new = jnp.einsum("bs,bskd->bkd", oh, v_upd)
    else:
        q, k_new, v_new = _decode_qkv(params, x, pos, cfg)
    k_pool = paged_scatter(k_pool, block_table, pos, k_new)
    v_pool = paged_scatter(v_pool, block_table, pos, v_new)
    if impl != "ref":
        from repro.kernels import interpret_mode
        from repro.kernels.flash_attention import paged_decode
        out = paged_decode(q, k_pool, v_pool, block_table, pos,
                           interpret=interpret_mode())
        out = out.astype(x.dtype)
        y = jnp.einsum("bhd,hde->be", out, params["wo"].astype(x.dtype))
    return y, k_pool, v_pool
