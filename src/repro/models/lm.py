"""Unified model builder: one config dataclass → {init, axes, loss_fn,
prefill, serve_step} for every assigned architecture family.

Families: dense / moe / ssm / hybrid (decoder LMs over models.transformer),
vlm (decoder LM + stub vision prefix + M-RoPE), encdec (seamless).

The loss path uses a sequence-chunked, vocab-parallel cross-entropy with an
explicit max/sumexp decomposition so a `vocab`-sharded head lowers to three
tiny all-reduces per chunk instead of gathering (B, S, V) logits — this is
the paper's Fig-4 "split the FC + Softmax" technique as a first-class loss
primitive (the Pallas `xent` kernel is the fused on-chip version).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.cost_model import ModelGraph, SegmentMeta
from repro.core.sharding import constrain
from repro.models import encdec as encdec_mod
from repro.models import frontends, layers
from repro.models import transformer as tfm
from repro.models.attention import AttnCfg
from repro.models.encdec import EncDecCfg
from repro.models.mamba2 import SSDCfg
from repro.models.moe import MoECfg


@dataclasses.dataclass(frozen=True)
class LMCfg:
    name: str
    family: str                        # dense | moe | ssm | hybrid | vlm | encdec
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    # flavour
    norm: str = "rms"
    act: str = "silu"
    gated_mlp: bool = True
    qk_norm: bool = False
    rope_theta: float = 10000.0
    mrope_sections: tuple | None = None
    tie_embeddings: bool = False
    # moe
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    d_ff_expert: int = 0
    moe_every: int = 1
    moe_offset: int = 0
    capacity_factor: float = 1.25
    # ssm / hybrid
    ssd_headdim: int = 64
    ssd_state: int = 128
    d_conv: int = 4
    ssd_chunk: int = 256
    attn_period: int = 0               # hybrid: one attn layer per period
    attn_offset: int = 0
    # encdec
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    # frontend stub
    frontend: str | None = None        # "vision" | "audio"
    frontend_len: int = 0
    # numerics / execution
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "full"
    scan: bool = True
    attn_block_q: int = 512
    attn_block_k: int = 512
    attn_wedge: bool = False
    loss_chunk: int = 512
    vocab_pad_multiple: int = 256
    z_loss_coef: float = 1e-4
    # kernel selection: "ref" (pure jnp — CPU dry-run) or "pallas" (fused
    # kernels, fwd AND bwd via custom VJPs — training-grade since PR 6;
    # interpret-mode on CPU, Mosaic on TPU)
    attn_impl: str = "ref"
    ssd_impl: str = "ref"
    xent_impl: str = "ref"          # loss head: chunked jnp vs fused kernel
    xent_block_t: int = 128         # fused-xent token tile
    xent_block_v: int = 512         # fused-xent vocab tile
    attn_bwd_remat: bool = False    # flash-style attention backward
    kv_cache_dtype: str = "bfloat16"  # "int8": quantised serving KV cache
    # cast f32 master params to the compute dtype ONCE at step entry, so
    # ZeRO-3 all-gathers move (and buffer) bf16, not f32 — halves FSDP
    # gather volume and the per-layer gathered-weight footprint
    cast_params_once: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def padded_vocab(self) -> int:
        return layers.pad_vocab(self.vocab, self.vocab_pad_multiple)

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def attn_cfg(self, causal: bool = True) -> AttnCfg:
        return AttnCfg(d_model=self.d_model, n_heads=self.n_heads,
                       n_kv_heads=self.n_kv_heads, head_dim=self.hd,
                       qk_norm=self.qk_norm, rope_theta=self.rope_theta,
                       mrope_sections=self.mrope_sections, causal=causal)

    def ssd_cfg(self) -> SSDCfg:
        n_heads = (2 * self.d_model) // self.ssd_headdim   # expand = 2
        return SSDCfg(d_model=self.d_model, n_heads=n_heads,
                      headdim=self.ssd_headdim, d_state=self.ssd_state,
                      d_conv=self.d_conv, chunk=self.ssd_chunk)

    def moe_cfg(self) -> MoECfg:
        return MoECfg(d_model=self.d_model, n_experts=self.n_experts,
                      top_k=self.top_k, d_ff_expert=self.d_ff_expert,
                      n_shared=self.n_shared,
                      capacity_factor=self.capacity_factor, act=self.act)

    def encdec_cfg(self) -> EncDecCfg:
        return EncDecCfg(d_model=self.d_model, n_enc_layers=self.n_enc_layers,
                         n_dec_layers=self.n_dec_layers, n_heads=self.n_heads,
                         n_kv_heads=self.n_kv_heads, head_dim=self.hd,
                         d_ff=self.d_ff, norm=self.norm, act=self.act,
                         gated_mlp=self.gated_mlp, remat=self.remat,
                         scan=self.scan, attn_block_q=self.attn_block_q,
                         attn_block_k=self.attn_block_k)


# ---------------------------------------------------------------------------
# pattern construction (scan grouping — repeated-substructure clustering)
# ---------------------------------------------------------------------------

def build_stack_cfg(cfg: LMCfg) -> tfm.StackCfg:
    def block(mixer: str, mlp: str) -> tfm.BlockCfg:
        return tfm.BlockCfg(
            d_model=cfg.d_model, mixer=mixer, mlp=mlp,
            attn=cfg.attn_cfg() if mixer == "attn" else None,
            ssd=cfg.ssd_cfg() if mixer == "ssd" else None,
            moe=cfg.moe_cfg() if mlp == "moe" else None,
            d_ff=cfg.d_ff, norm=cfg.norm, act=cfg.act,
            gated_mlp=cfg.gated_mlp)

    if cfg.family in ("dense", "vlm"):
        pattern, n_rep = (block("attn", "dense"),), cfg.n_layers
    elif cfg.family == "moe":
        if cfg.moe_every == 1:
            pattern, n_rep = (block("attn", "moe"),), cfg.n_layers
        else:
            pat = tuple(
                block("attn", "moe" if i % cfg.moe_every == cfg.moe_offset
                      else "dense")
                for i in range(cfg.moe_every))
            pattern, n_rep = pat, cfg.n_layers // cfg.moe_every
    elif cfg.family == "ssm":
        pattern, n_rep = (block("ssd", "none"),), cfg.n_layers
    elif cfg.family == "hybrid":
        p = cfg.attn_period
        pat = []
        for i in range(p):
            mixer = "attn" if i % p == cfg.attn_offset else "ssd"
            mlp = "moe" if i % 2 == 1 else "dense"
            pat.append(block(mixer, mlp))
        pattern, n_rep = tuple(pat), cfg.n_layers // p
    else:
        raise ValueError(cfg.family)
    return tfm.StackCfg(pattern=pattern, n_rep=n_rep, remat=cfg.remat,
                        scan=cfg.scan, attn_block_q=cfg.attn_block_q,
                        attn_block_k=cfg.attn_block_k,
                        attn_wedge=cfg.attn_wedge, attn_impl=cfg.attn_impl,
                        ssd_impl=cfg.ssd_impl,
                        attn_bwd_remat=cfg.attn_bwd_remat,
                        kv_cache_dtype=cfg.kv_cache_dtype)


# ---------------------------------------------------------------------------
# vocab-parallel chunked cross-entropy (paper Fig-4 split-softmax as a loss)
# ---------------------------------------------------------------------------

def chunked_xent(hidden: jax.Array, head_w: jax.Array, labels: jax.Array,
                 mask: jax.Array, *, vocab: int, chunk: int,
                 z_loss_coef: float = 0.0):
    """hidden: (B, T, E); head_w: (E, Vp) vocab-sharded; labels/mask: (B, T).

    Returns (sum_nll, sum_z_loss, token_count).  Sequence-chunked with remat
    so the (B, chunk, Vp) logits block is the only live logits tensor.
    """
    B, T, E = hidden.shape
    Vp = head_w.shape[1]
    chunk = min(chunk, T)
    n = -(-T // chunk)
    Tc = n * chunk
    if Tc != T:                      # pad (mask 0) so no token is dropped
        pad = Tc - T
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hs = jnp.moveaxis(hidden.reshape(B, n, chunk, E), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    ms = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)
    col = jnp.arange(Vp)

    @jax.checkpoint
    def body(carry, inp):
        h, lab, msk = inp
        logits = jnp.einsum("bce,ev->bcv", h, head_w.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, ("batch", None, "vocab"))
        if Vp > vocab:                       # mask padded vocab columns
            logits = jnp.where(col[None, None, :] < vocab, logits, -1e30)
        m = logits.max(axis=-1)                                   # AR(max) over vocab shards
        se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)     # AR(sum)
        z = jnp.log(se) + m
        correct = jnp.sum(
            jnp.where(col[None, None, :] == lab[..., None], logits, 0.0),
            axis=-1)                                              # AR(sum)
        nll = (z - correct) * msk
        zl = jnp.square(z) * msk
        s_nll, s_zl, s_n = carry
        return (s_nll + nll.sum(), s_zl + zl.sum(), s_n + msk.sum()), None

    init = (jnp.zeros((), jnp.float32),) * 3
    (s_nll, s_zl, s_n), _ = jax.lax.scan(body, init, (hs, ls, ms))
    return s_nll, z_loss_coef * s_zl, s_n


def fused_xent(hidden: jax.Array, head_w: jax.Array, labels: jax.Array,
               mask: jax.Array, *, vocab: int, block_t: int = 128,
               block_v: int = 512, z_loss_coef: float = 0.0,
               interpret: bool | None = None):
    """Pallas fused-kernel twin of :func:`chunked_xent` (same contract).

    One kernel launch streams (E, Vp) head tiles through VMEM and never
    materialises a logits tensor at all; nll AND lse come back together so
    the z-loss term differentiates through the same recompute-over-vocab
    backward (``kernels.xent.ops.xent_with_lse``).
    """
    from repro.kernels import interpret_mode
    from repro.kernels.autotune import fit_block
    from repro.kernels.xent.ops import xent_with_lse
    B, T, E = hidden.shape
    Vp = head_w.shape[1]
    if interpret is None:
        interpret = interpret_mode()
    # rows padded (mask 0) to whole token tiles of a sublane multiple;
    # the vocab tile is a lane-aligned divisor of the padded vocab
    n = B * T
    bt = min(block_t, -(-n // 8) * 8)
    pad = -(-n // bt) * bt - n
    h2 = jnp.pad(hidden.reshape(n, E), ((0, pad), (0, 0)))
    l2 = jnp.pad(labels.reshape(n), (0, pad))
    m2 = jnp.pad(mask.reshape(n).astype(jnp.float32), (0, pad))
    bv = fit_block(Vp, block_v, align=128)
    nll, lse = xent_with_lse(h2, head_w, l2, vocab, bt, bv, interpret)
    s_nll = jnp.sum(nll * m2)
    s_zl = jnp.sum(jnp.square(lse) * m2)
    return s_nll, z_loss_coef * s_zl, m2.sum()


# ---------------------------------------------------------------------------
# the model object
# ---------------------------------------------------------------------------

class Model:
    """Functional model bundle for one LMCfg."""

    def __init__(self, cfg: LMCfg):
        self.cfg = cfg
        if cfg.family == "encdec":
            self.ecfg = cfg.encdec_cfg()
            self.stack = None
        else:
            self.stack = build_stack_cfg(cfg)
            self.ecfg = None

    # ---- params ----
    def init(self, key) -> dict:
        cfg = self.cfg
        ke, kh, kb, ka, kn = jax.random.split(key, 5)
        dt = cfg.pdtype
        p: dict[str, Any] = {
            "embed": layers.init_embedding(ke, cfg.padded_vocab, cfg.d_model, dt),
            "final_norm": layers.make_norm(cfg.norm)[0](cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            p["head"] = layers.init_lm_head(kh, cfg.d_model, cfg.padded_vocab, dt)
        if cfg.family == "encdec":
            p["encdec"] = encdec_mod.init_encdec(kb, self.ecfg, dt)
        else:
            p["blocks"] = tfm.init_stack(kb, self.stack, dt)
        if cfg.frontend is not None:
            p["adapter"] = frontends.init_adapter(ka, cfg.d_model, dt)
        return p

    def axes(self) -> dict:
        cfg = self.cfg
        a: dict[str, Any] = {
            "embed": layers.axes_embedding(),
            "final_norm": layers.make_norm(cfg.norm)[1](),
        }
        if not cfg.tie_embeddings:
            a["head"] = layers.axes_lm_head()
        if cfg.family == "encdec":
            a["encdec"] = encdec_mod.axes_encdec(self.ecfg)
        else:
            a["blocks"] = tfm.axes_stack(self.stack)
        if cfg.frontend is not None:
            a["adapter"] = frontends.axes_adapter()
        return a

    def param_shapes(self) -> dict:
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))

    def graph(self, batch: int, seq: int, *, act_dtype_bytes: int = 2,
              param_dtype_bytes: int = 4,
              src_seq: int | None = None) -> "ModelGraph":
        """Segment-aware cost-model view of this model (see
        :func:`model_graph`): ordered SegmentMeta segments — frontends,
        encoder/decoder stacks, MoE block groups — each with its own
        flops/param/activation arithmetic, flattenable to a legacy
        WorkloadMeta via ``.workload_meta()``."""
        return model_graph(self.cfg, batch, seq,
                           act_dtype_bytes=act_dtype_bytes,
                           param_dtype_bytes=param_dtype_bytes,
                           src_seq=src_seq)

    # ---- shared pieces ----
    def _head_w(self, params) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["embed"]["table"].T
        return params["head"]["w"]

    def _positions(self, B: int, S: int):
        if self.cfg.mrope_sections is not None:
            return frontends.mrope_positions(B, S, self.cfg.frontend_len)
        return jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def _embed_tokens(self, params, tokens, batch):
        cfg = self.cfg
        x = layers.embed(params["embed"], tokens).astype(cfg.adtype)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            P = cfg.frontend_len
            pe = frontends.adapt(params["adapter"],
                                 batch["patch_embeds"].astype(cfg.adtype))
            x = jnp.concatenate([pe, x[:, P:]], axis=1)
        return constrain(x, ("batch", "seq", None))

    def _maybe_cast(self, params):
        if not self.cfg.cast_params_once:
            return params
        adt = self.cfg.adtype
        return jax.tree.map(
            lambda p: p.astype(adt) if p.dtype == jnp.float32 else p, params)

    def _xent(self, hidden, head_w, labels, mask):
        """Loss-head dispatch: chunked jnp scan vs the fused Pallas kernel."""
        cfg = self.cfg
        if cfg.xent_impl == "pallas":
            return fused_xent(hidden, head_w, labels, mask, vocab=cfg.vocab,
                              block_t=cfg.xent_block_t,
                              block_v=cfg.xent_block_v,
                              z_loss_coef=cfg.z_loss_coef)
        return chunked_xent(hidden, head_w, labels, mask, vocab=cfg.vocab,
                            chunk=cfg.loss_chunk,
                            z_loss_coef=cfg.z_loss_coef)

    # ---- training ----
    def loss_fn(self, params, batch) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        params = self._maybe_cast(params)
        if cfg.family == "encdec":
            return self._loss_encdec(params, batch)
        tokens = batch["tokens"]
        B, S = tokens.shape
        with jax.named_scope("embed"):
            x = self._embed_tokens(params, tokens, batch)
        x, aux = tfm.apply_stack(params["blocks"], x, self._positions(B, S),
                                 self.stack)
        with jax.named_scope("loss_head"):
            x = layers.make_norm(cfg.norm)[2](params["final_norm"], x)
            labels = tokens[:, 1:]
            mask = jnp.ones_like(labels, jnp.float32)
            if "loss_mask" in batch:
                mask = mask * batch["loss_mask"][:, 1:]
            if cfg.family == "vlm":
                tgt_pos = jnp.arange(1, S)[None]
                mask = mask * (tgt_pos >= cfg.frontend_len)
            nll, zl, n = self._xent(
                x[:, :-1], self._head_w(params).astype(cfg.adtype), labels,
                mask)
            loss = nll / jnp.maximum(n, 1.0) + zl / jnp.maximum(n, 1.0) \
                + aux["lb_loss"] + aux["z_loss"]
        metrics = {"nll": nll / jnp.maximum(n, 1.0), "tokens": n,
                   "moe_lb": aux["lb_loss"], "moe_z": aux["z_loss"]}
        return loss, metrics

    def _loss_encdec(self, params, batch):
        cfg = self.cfg
        frames = batch["frames"].astype(cfg.adtype)
        tokens = batch["tokens"]
        memory = encdec_mod.encode(params["encdec"],
                                   frontends.adapt(params["adapter"], frames)
                                   if cfg.frontend else frames, self.ecfg)
        dec_in = layers.embed(params["embed"], tokens[:, :-1]).astype(cfg.adtype)
        x = encdec_mod.decode_train(params["encdec"], dec_in, memory, self.ecfg)
        x = layers.make_norm(cfg.norm)[2](params["final_norm"], x)
        labels = tokens[:, 1:]
        mask = jnp.ones_like(labels, jnp.float32)
        nll, zl, n = self._xent(
            x, self._head_w(params).astype(cfg.adtype), labels, mask)
        loss = (nll + zl) / jnp.maximum(n, 1.0)
        return loss, {"nll": nll / jnp.maximum(n, 1.0), "tokens": n,
                      "moe_lb": jnp.zeros(()), "moe_z": jnp.zeros(())}

    # ---- serving ----
    def prefill(self, params, batch, gen_budget: int = 64, last_idx=None):
        """→ (last-token logits (B, Vp), decode state).

        ``last_idx`` (B,) int32: index of each prompt's last *real* token
        when prompts are right-padded to a shared (bucketed) length —
        logits are read at ``last_idx`` instead of the final position,
        ``pos`` starts at ``last_idx + 1``, and the KV cache is zeroed
        beyond ``last_idx`` so the pad tokens' KV can never be attended
        to (decode's one-hot ADD write at ``pos`` lands on a zero cell).
        ``last_idx=None`` keeps the original unbucketed behaviour.
        """
        cfg = self.cfg
        if cfg.family == "encdec":
            if last_idx is not None:
                raise ValueError("last_idx is not supported for encdec "
                                 "prefill (frame inputs are not padded)")
            return self._prefill_encdec(params, batch, gen_budget)
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed_tokens(params, tokens, batch)
        x, caches = tfm.prefill_stack(params["blocks"], x,
                                      self._positions(B, S), self.stack)
        x = layers.make_norm(cfg.norm)[2](params["final_norm"], x)
        if last_idx is None:
            h_last = x[:, -1]
            pos = jnp.full((B,), S, jnp.int32)
        else:
            h_last = jnp.take_along_axis(
                x, last_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            pos = last_idx.astype(jnp.int32) + 1
        logits = h_last @ self._head_w(params).astype(cfg.adtype)

        keep = None
        if last_idx is not None:
            keep = (jnp.arange(S + gen_budget)[None, :]
                    <= last_idx[:, None])                      # (B, S+gb)

        def pad_kv(a):
            # (L, B, S, K, D) → (L, B, S + budget, K, D)
            a = jnp.pad(a, ((0, 0), (0, 0), (0, gen_budget), (0, 0), (0, 0)))
            if keep is not None:
                a = jnp.where(keep[None, :, :, None, None], a, 0)
            return a

        state = {}
        for key, val in caches.items():
            state[key] = jax.tree.map(pad_kv, val)
        # merge ssd states (prefill_stack only returns attn caches; rebuild full)
        full = tfm.init_stack_state(self.stack, B, S + gen_budget, cfg.adtype)
        for key in full:
            if key in state:
                full[key] = state[key]
        # TODO(ssm prefill): chunked-scan final states; for ssm/hybrid archs
        # prefill re-runs through decode in serve.py when exact states needed.
        return logits, {"cache": full, "pos": pos}

    def _prefill_encdec(self, params, batch, gen_budget: int):
        cfg = self.cfg
        frames = batch["frames"].astype(cfg.adtype)
        memory = encdec_mod.encode(params["encdec"],
                                   frontends.adapt(params["adapter"], frames)
                                   if cfg.frontend else frames, self.ecfg)
        B = frames.shape[0]
        state = encdec_mod.init_dec_state(params["encdec"], memory, self.ecfg,
                                          B, max(gen_budget, 1), cfg.adtype)
        bos = jnp.zeros((B,), jnp.int32)
        logits, state = self._serve_encdec(params, bos, state,
                                           jnp.zeros((B,), jnp.int32))
        return logits, {"cache": state, "pos": jnp.ones((B,), jnp.int32)}

    def serve_step(self, params, tokens: jax.Array, state: dict):
        """tokens: (B,) → (logits (B, Vp), state')."""
        cfg = self.cfg
        pos = state["pos"]
        if cfg.family == "encdec":
            logits, cache = self._serve_encdec(params, tokens, state["cache"], pos)
            return logits, {"cache": cache, "pos": pos + 1}
        x = layers.embed(params["embed"], tokens).astype(cfg.adtype)
        x = constrain(x, ("batch", None))
        x, cache = tfm.decode_stack(params["blocks"], x, state["cache"], pos,
                                    self.stack)
        x = layers.make_norm(cfg.norm)[2](params["final_norm"], x[:, None])[:, 0]
        logits = x @ self._head_w(params).astype(cfg.adtype)
        logits = constrain(logits, ("batch", "vocab"))
        return logits, {"cache": cache, "pos": pos + 1}

    def _serve_encdec(self, params, tokens, cache, pos):
        cfg = self.cfg
        x = layers.embed(params["embed"], tokens).astype(cfg.adtype)
        x, cache = encdec_mod.decode_step(params["encdec"], x, cache, pos,
                                          self.ecfg)
        x = layers.make_norm(cfg.norm)[2](params["final_norm"], x[:, None])[:, 0]
        logits = x @ self._head_w(params).astype(cfg.adtype)
        return logits, cache

    # ---- decode-state templates (for dry-run input_specs) ----
    def decode_state_shapes(self, batch: int, cache_len: int):
        cfg = self.cfg
        if cfg.family == "encdec":
            def f():
                mem = jnp.zeros((batch, cache_len, cfg.d_model), cfg.adtype)
                return encdec_mod.init_dec_state(
                    self.init(jax.random.key(0))["encdec"], mem, self.ecfg,
                    batch, cache_len, cfg.adtype)
            cache = jax.eval_shape(f)
        else:
            cache = jax.eval_shape(
                lambda: tfm.init_stack_state(self.stack, batch, cache_len,
                                             cfg.adtype))
        pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
        return {"cache": cache, "pos": pos}

    def state_axes(self) -> dict:
        if self.cfg.family == "encdec":
            ax = encdec_mod.axes_dec_state()
        else:
            ax = tfm.axes_stack_state(self.stack)
        return {"cache": ax, "pos": ("batch",)}

    # ---- paged serving (block-table KV cache, DESIGN.md §9) ----
    @property
    def supports_paged(self) -> bool:
        return (self.cfg.family != "encdec" and self.stack is not None
                and all(b.mixer == "attn" for b in self.stack.pattern)
                and self.stack.kv_cache_dtype != "int8")

    def serve_step_paged(self, params, tokens: jax.Array, state: dict):
        """tokens: (B,) → (logits (B, Vp), state').  ``state`` holds the
        shared page pools plus per-slot ``block_table`` (B, max_pages) and
        ``pos`` (B,); pools are updated in place of the dense cache."""
        cfg = self.cfg
        if not self.supports_paged:
            raise ValueError(f"paged decode unsupported for {cfg.family}")
        pos = state["pos"]
        x = layers.embed(params["embed"], tokens).astype(cfg.adtype)
        x = constrain(x, ("batch", None))
        x, pools = tfm.decode_stack_paged(params["blocks"], x, state["pools"],
                                          state["block_table"], pos,
                                          self.stack)
        x = layers.make_norm(cfg.norm)[2](params["final_norm"], x[:, None])[:, 0]
        logits = x @ self._head_w(params).astype(cfg.adtype)
        logits = constrain(logits, ("batch", "vocab"))
        return logits, {"pools": pools, "block_table": state["block_table"],
                        "pos": pos + 1}

    def paged_state_shapes(self, batch: int, n_pages: int, page_size: int,
                           max_pages: int):
        cfg = self.cfg
        pools = jax.eval_shape(
            lambda: tfm.init_paged_stack_state(self.stack, n_pages, page_size,
                                               cfg.adtype))
        return {"pools": pools,
                "block_table": jax.ShapeDtypeStruct((batch, max_pages),
                                                    jnp.int32),
                "pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}

    def paged_state_axes(self) -> dict:
        return {"pools": tfm.axes_paged_stack_state(self.stack),
                "block_table": ("batch", None), "pos": ("batch",)}


def build(cfg: LMCfg) -> Model:
    return Model(cfg)


def param_count(params) -> int:
    return sum(int(math.prod(p.shape)) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# per-family ModelGraph builders (meta-driven: pure arithmetic on the config)
# ---------------------------------------------------------------------------
#
# The segment-aware successor of core.cost_model's retired family
# if-ladder.  Matmul-dominant terms only (the granularity the roofline
# uses).  For the layer-homogeneous families (dense/moe/ssm/hybrid) the
# single "stack" segment computes the EXACT legacy expressions, so
# ``model_graph(cfg, b, s).workload_meta()`` is byte-identical to the
# retired ``lm_workload_meta`` if-ladder — tests/test_model_graph.py
# freezes that formula and guards the identity across every shipped
# config.
#
# The multimodal families get real graphs (and real pricing fixes):
#
# - ``vlm``: an atomic vision-frontend segment prices the patch adapter
#   (flops over the ``frontend_len`` prefix tokens + the d_model² adapter
#   params) that the legacy ladder silently dropped — vlm ≠ dense now.
# - ``encdec``: encoder and decoder become separate segments; encoder
#   self-attention scores are non-causal (no ×0.5), and decoder
#   cross-attention prices its KV projections over the SOURCE tokens plus
#   full (non-causal) q·k scores against the source memory — the
#   cross-attention KV term the flat meta never carried.


def model_graph(cfg: LMCfg, batch: int, seq: int,
                act_dtype_bytes: int = 2, param_dtype_bytes: int = 4,
                src_seq: int | None = None) -> ModelGraph:
    """Segment-aware workload description for one LMCfg.

    ``src_seq`` (encdec only): source-side sequence length fed to the
    encoder; defaults to ``seq`` (the target length).
    """
    E, V, L = cfg.d_model, cfg.padded_vocab, cfg.n_layers
    T = batch * seq
    hd = cfg.hd
    pdb = param_dtype_bytes

    def attn_flops(t=T, kv=seq, causal=True) -> float:
        H, K = cfg.n_heads, cfg.n_kv_heads
        proj = 2 * t * E * (H * hd) + 2 * 2 * t * E * (K * hd) \
            + 2 * t * (H * hd) * E
        scores = 2 * t * kv * H * hd * 2 * (0.5 if causal else 1.0)
        return proj + scores

    def cross_attn_flops(t_q, t_kv, kv_len) -> float:
        # q/o projections ride the query tokens; k/v projections ride the
        # SOURCE tokens (computed once per layer); scores are full rank —
        # nothing causal about attending to an encoded source
        H, K = cfg.n_heads, cfg.n_kv_heads
        proj = 2 * t_q * E * (H * hd) + 2 * 2 * t_kv * E * (K * hd) \
            + 2 * t_q * (H * hd) * E
        scores = 2 * t_q * kv_len * H * hd * 2
        return proj + scores

    def dense_mlp_flops(t=T) -> float:
        mult = 3 if cfg.gated_mlp else 2
        return 2 * t * E * cfg.d_ff * mult

    def moe_mlp_flops() -> float:
        mult = 3
        routed = 2 * T * E * cfg.d_ff_expert * mult * cfg.top_k
        shared = 2 * T * E * cfg.d_ff_expert * mult * cfg.n_shared
        router = 2 * T * E * cfg.n_experts
        return routed + shared + router

    def ssd_flops() -> float:
        scfg = cfg.ssd_cfg()
        H, P, N, C = scfg.n_heads, scfg.headdim, scfg.d_state, scfg.chunk
        proj = 2 * T * E * (2 * H * P + 2 * N + H) + 2 * T * H * P * E
        intra = 2 * T * C * H * (N + P)
        inter = 2 * T * H * P * N * 2
        return proj + intra + inter

    def attn_params():
        return E * (cfg.n_heads * hd) * 2 + E * (cfg.n_kv_heads * hd) * 2

    def mlp_params():
        return E * cfg.d_ff * (3 if cfg.gated_mlp else 2)

    def moe_params():
        return (cfg.n_experts + cfg.n_shared) * E * cfg.d_ff_expert * 3 \
            + E * cfg.n_experts

    def ssd_params():
        scfg = cfg.ssd_cfg()
        return E * scfg.d_inner * 3 + 2 * E * scfg.d_state + E * scfg.n_heads

    def adapter_segment(name: str, prefix_tokens: int) -> SegmentMeta:
        # frontends.init_adapter: one d_model×d_model projection + bias
        return SegmentMeta(
            name=name, n_layers=1, atomic=True,
            fwd_flops=float(2 * prefix_tokens * E * E),
            param_bytes=float((E * E + E) * pdb),
            act_bytes_per_layer=float(prefix_tokens * E
                                      * act_dtype_bytes * 4))

    act_per_layer = T * E * act_dtype_bytes * 4   # x + 3 intermediates

    def stack_segment(name: str, n_attn: int, n_ssd: int, n_moe: int,
                      n_dense: int, n_layers: int) -> SegmentMeta:
        flops = (n_attn * attn_flops() + n_ssd * ssd_flops()
                 + n_moe * moe_mlp_flops() + n_dense * dense_mlp_flops())
        p_count = (n_attn * attn_params() + n_ssd * ssd_params()
                   + n_moe * moe_params() + n_dense * mlp_params())
        expert_param_bytes = 0.0
        moe_dispatch_bytes = 0.0
        if n_moe:
            expert_param_bytes = (n_moe * cfg.n_experts * E * cfg.d_ff_expert
                                  * 3 * pdb)
            moe_dispatch_bytes = (T * cfg.top_k * cfg.capacity_factor
                                  * E * act_dtype_bytes)
        return SegmentMeta(
            name=name, n_layers=n_layers,
            fwd_flops=float(flops), param_bytes=float(p_count * pdb),
            act_bytes_per_layer=float(act_per_layer),
            n_experts=int(cfg.n_experts if n_moe else 0),
            n_moe_layers=int(n_moe),
            expert_param_bytes=float(expert_param_bytes),
            moe_dispatch_bytes=float(moe_dispatch_bytes))

    if cfg.family == "dense":
        segments = (stack_segment("stack", L, 0, 0, L, max(L, 1)),)
    elif cfg.family == "moe":
        n_moe = L // cfg.moe_every
        segments = (stack_segment("stack", L, 0, n_moe, L - n_moe,
                                  max(L, 1)),)
    elif cfg.family == "ssm":
        segments = (stack_segment("stack", 0, L, 0, 0, max(L, 1)),)
    elif cfg.family == "hybrid":
        n_attn = L // cfg.attn_period
        n_moe = L // 2
        segments = (stack_segment("stack", n_attn, L - n_attn, n_moe,
                                  L - n_moe, max(L, 1)),)
    elif cfg.family == "vlm":
        segments = (adapter_segment("vision-frontend",
                                    batch * cfg.frontend_len),
                    stack_segment("decoder", L, 0, 0, L, max(L, 1)))
    elif cfg.family == "encdec":
        s_src = seq if src_seq is None else src_seq
        t_src = batch * s_src
        n_enc, n_dec = cfg.n_enc_layers, cfg.n_dec_layers
        enc_flops = n_enc * (attn_flops(t_src, s_src, causal=False)
                             + dense_mlp_flops(t_src))
        dec_flops = n_dec * (attn_flops(T, seq, causal=True)
                             + cross_attn_flops(T, t_src, s_src)
                             + dense_mlp_flops(T))
        enc_params = n_enc * (attn_params() + mlp_params())
        dec_params = n_dec * (2 * attn_params() + mlp_params())
        enc_act = t_src * E * act_dtype_bytes * 4
        enc = SegmentMeta(name="encoder", n_layers=max(n_enc, 1),
                          fwd_flops=float(enc_flops),
                          param_bytes=float(enc_params * pdb),
                          act_bytes_per_layer=float(enc_act))
        dec = SegmentMeta(name="decoder", n_layers=max(n_dec, 1),
                          fwd_flops=float(dec_flops),
                          param_bytes=float(dec_params * pdb),
                          act_bytes_per_layer=float(act_per_layer))
        segments = (enc, dec)
        if cfg.frontend:
            segments = (adapter_segment(f"{cfg.frontend}-frontend", t_src),
                        ) + segments
    else:
        raise ValueError(f"unknown model family {cfg.family!r}")

    head = 2 * T * E * V
    embed = V * E * (1 if cfg.tie_embeddings else 2)
    return ModelGraph(
        name=cfg.name, segments=segments, batch=batch,
        extra_fwd_flops=float(head),
        extra_param_bytes=float(embed * pdb),
        logits_bytes=float(T * V * 4),
        head_param_bytes=float(E * V * pdb))
