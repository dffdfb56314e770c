"""Plain float32 Qwen3 decoder: the reference the benchmark compares with.

Follows the published Qwen3 block (arXiv:2505.09388; the Hugging Face
``Qwen3ForCausalLM`` code): pre-norm RMSNorm, GQA attention with a
per-head RMSNorm on q and k (qk-norm) before rotate-half RoPE, causal
softmax at 1/sqrt(head_dim), SwiGLU MLP, a final RMSNorm and an LM head
tied to the embedding.  Weights arrive in the benchmark's weight layout
(``bench/weights.py``): layer leaves stacked on a leading layer axis.

Nothing here imports the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import mm, rmsnorm, rope, token_loss


def dims(c: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys."""
    vp = -(-c["vocab_size"] // c["vocab_pad_multiple"]) * c["vocab_pad_multiple"]
    return dict(L=c["num_hidden_layers"], E=c["hidden_size"],
                H=c["num_attention_heads"], K=c["num_key_value_heads"],
                D=c["head_dim"], F=c["intermediate_size"],
                V=c["vocab_size"], Vp=vp, eps=c["rms_norm_eps"],
                theta=float(c["rope_theta"]), z=c["z_loss_coef"])


def shapes(c: dict) -> dict:
    """The weight layout: leaf path → shape (layer leaves stacked)."""
    d = dims(c)
    L, E, H, K, D, F = d["L"], d["E"], d["H"], d["K"], d["D"], d["F"]
    return {
        "embed/table": (d["Vp"], E),
        "final_norm/scale": (E,),
        "blocks/p0/norm1/scale": (L, E),
        "blocks/p0/norm2/scale": (L, E),
        "blocks/p0/attn/wq": (L, E, H, D),
        "blocks/p0/attn/wk": (L, E, K, D),
        "blocks/p0/attn/wv": (L, E, K, D),
        "blocks/p0/attn/wo": (L, H, D, E),
        "blocks/p0/attn/q_norm/scale": (L, D),
        "blocks/p0/attn/k_norm/scale": (L, D),
        "blocks/p0/mlp/wi": (L, E, F),
        "blocks/p0/mlp/wg": (L, E, F),
        "blocks/p0/mlp/wo": (L, F, E),
    }


def init_rules(c: dict) -> dict:
    """Leaf path → (distribution, scale) for the seeded weights."""
    d = dims(c)
    E, H, D, F = d["E"], d["H"], d["D"], d["F"]
    return {
        "embed/table": ("normal", 1 / math.sqrt(E)),
        "final_norm/scale": ("ones", 0.0),
        "blocks/p0/norm1/scale": ("ones", 0.0),
        "blocks/p0/norm2/scale": ("ones", 0.0),
        "blocks/p0/attn/wq": ("normal", 1 / math.sqrt(E)),
        "blocks/p0/attn/wk": ("normal", 1 / math.sqrt(E)),
        "blocks/p0/attn/wv": ("normal", 1 / math.sqrt(E)),
        "blocks/p0/attn/wo": ("normal", 1 / math.sqrt(H * D)),
        "blocks/p0/attn/q_norm/scale": ("ones", 0.0),
        "blocks/p0/attn/k_norm/scale": ("ones", 0.0),
        "blocks/p0/mlp/wi": ("normal", 1 / math.sqrt(E)),
        "blocks/p0/mlp/wg": ("normal", 1 / math.sqrt(E)),
        "blocks/p0/mlp/wo": ("normal", 1 / math.sqrt(F)),
    }


def _attention(p, h, d, mode, q_block):
    """Causal GQA over the whole sequence, walked in query blocks."""
    B, S, _ = h.shape
    H, K, D = d["H"], d["K"], d["D"]
    G = H // K
    pos = jnp.arange(S)
    q = mm("bse,ehd->bshd", h, p["wq"], mode)
    k = mm("bse,ekd->bskd", h, p["wk"], mode)
    v = mm("bse,ekd->bskd", h, p["wv"], mode)
    q = rope(rmsnorm(q, p["q_norm"]["scale"], d["eps"]), pos, d["theta"])
    k = rope(rmsnorm(k, p["k_norm"]["scale"], d["eps"]), pos, d["theta"])
    k = jnp.repeat(k, G, axis=2)            # query head h reads kv head h // G
    v = jnp.repeat(v, G, axis=2)
    bq = min(q_block, S)
    nq = -(-S // bq)
    qp = jnp.pad(q, ((0, 0), (0, nq * bq - S), (0, 0), (0, 0)))
    qs = jnp.moveaxis(qp.reshape(B, nq, bq, H, D), 1, 0)

    @jax.checkpoint
    def one(i, qi):
        s = mm("bqhd,bkhd->bhqk", qi, k, mode) / math.sqrt(D)
        live = (i * bq + jnp.arange(bq))[:, None] >= pos[None, :]
        s = jnp.where(live[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", w, v, mode)

    o = jax.lax.map(lambda a: one(*a), (jnp.arange(nq), qs))
    o = jnp.moveaxis(o, 0, 1).reshape(B, nq * bq, H, D)[:, :S]
    return mm("bshd,hde->bse", o, p["wo"], mode)


def _mlp(p, h, mode):
    a = mm("bse,ef->bsf", h, p["wg"], mode)
    b = mm("bse,ef->bsf", h, p["wi"], mode)
    g = jax.nn.silu(a) * b
    return mm("bsf,fe->bse", g, p["wo"], mode)


def hidden(params, tokens, c, mode="f32", q_block=512):
    """Final-normed hidden states (B, S, E) for ``tokens`` (B, S)."""
    d = dims(c)
    x = params["embed"]["table"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def layer(x, p):
        h = rmsnorm(x, p["norm1"]["scale"], d["eps"])
        x = x + _attention(p["attn"], h, d, mode, q_block)
        h = rmsnorm(x, p["norm2"]["scale"], d["eps"])
        return x + _mlp(p["mlp"], h, mode), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["p0"])
    return rmsnorm(x, params["final_norm"]["scale"], d["eps"])


def loss(params, tokens, c, mode="f32"):
    """The training loss: next-token cross-entropy + z-loss."""
    d = dims(c)
    h = hidden(params, tokens, c, mode)
    return token_loss(h[:, :-1], params["embed"]["table"], tokens[:, 1:],
                      d["V"], d["z"], mode)

