"""Mamba2 — SSD (state-space duality) mixer, chunked-scan formulation.

The sequence is processed in chunks of ``chunk`` tokens: within a chunk the
SSD dual form is a masked (decay-weighted) quadratic attention computed on the
MXU; across chunks a single (B, H, P, N) state is carried by a `lax.scan` —
O(S) work, O(1) decode state.  Heads (`ssm_heads`) are the tensor-parallel
target; B/C projections use ngroups=1 and stay replicated (they are tiny).

Projections are stored per-role (wz/wx/wB/wC/wdt) rather than one fused
in_proj so each weight gets a clean Multi-Dimension annotation.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.sharding import constrain
from repro.models import layers


@dataclasses.dataclass(frozen=True)
class SSDCfg:
    d_model: int
    n_heads: int              # d_inner // headdim
    headdim: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 256
    ngroups: int = 1
    act: str = "silu"

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.headdim


def init_ssd(key, cfg: SSDCfg, dtype) -> dict:
    kz, kx, kb, kc, kd, ko, kcv = jax.random.split(key, 7)
    D, H, Pd, G, N = cfg.d_model, cfg.n_heads, cfg.headdim, cfg.ngroups, cfg.d_state
    return {
        "wz": layers.dense_init(kz, D, (D, H, Pd), dtype),
        "wx": layers.dense_init(kx, D, (D, H, Pd), dtype),
        "wB": layers.dense_init(kb, D, (D, G, N), dtype),
        "wC": layers.dense_init(kc, D, (D, G, N), dtype),
        "wdt": layers.dense_init(kd, D, (D, H), dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D_skip": jnp.ones((H,), jnp.float32),
        "conv_x": (jax.random.normal(kcv, (H, Pd, cfg.d_conv), jnp.float32)
                   * 0.1).astype(dtype),
        "norm_scale": jnp.ones((H, Pd), dtype),
        "wo": layers.dense_init(ko, cfg.d_inner, (H, Pd, D), dtype),
    }


def axes_ssd(cfg: SSDCfg) -> dict:
    return {
        "wz": ("embed", "ssm_heads", None),
        "wx": ("embed", "ssm_heads", None),
        "wB": ("embed", None, "state"),
        "wC": ("embed", None, "state"),
        "wdt": ("embed", "ssm_heads"),
        "dt_bias": ("ssm_heads",),
        "A_log": ("ssm_heads",),
        "D_skip": ("ssm_heads",),
        "conv_x": ("ssm_heads", None, None),
        "norm_scale": ("ssm_heads", None),
        "wo": ("ssm_heads", None, "embed"),
    }


def _causal_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, H, P), kernel: (H, P, W)."""
    W = kernel.shape[-1]
    out = x * kernel[None, None, :, :, -1]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0), (0, 0)))[:, :-i or None]
        out = out + shifted * kernel[None, None, :, :, -1 - i]
    return out


def _gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array,
                   eps: float = 1e-6) -> jax.Array:
    """Mamba2 gated norm over the full d_inner = (H, P) dims."""
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    gf = g.astype(jnp.float32)
    var = jnp.mean(gf * gf, axis=(-2, -1), keepdims=True)
    return (gf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(y.dtype)


def _segsum(a: jax.Array) -> jax.Array:
    """(..., T) → (..., T, T) lower-triangular segment sums (f32, -inf above)."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    # seg[i, j] = sum_{k=j+1..i} a_k  (decay applied moving j's input to i)
    seg = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(T)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, seg, -jnp.inf)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int, h0: jax.Array | None = None):
    """Chunked SSD forward.

    x: (B, S, H, P)   dt: (B, S, H) post-softplus   A: (H,) negative
    Bm/Cm: (B, S, G, N) with G broadcast over heads.
    Returns y: (B, S, H, P) and final state (B, H, P, N).
    """
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    L = max(S // chunk, 1)
    Q = S // L
    rep = H // G

    dA = (dt * A[None, None, :]).astype(jnp.float32)               # (B,S,H) ≤ 0
    xd = x * dt[..., None].astype(x.dtype)                         # dt-weighted input
    # chunked views
    xc = xd.reshape(Bsz, L, Q, H, Pd)
    Bc = jnp.repeat(Bm.reshape(Bsz, L, Q, G, N), rep, axis=3)       # (B,L,Q,H,N)
    Cc = jnp.repeat(Cm.reshape(Bsz, L, Q, G, N), rep, axis=3)
    dAc = dA.reshape(Bsz, L, Q, H).transpose(0, 3, 1, 2)            # (B,H,L,Q)
    A_cum = jnp.cumsum(dAc, axis=-1)                                # (B,H,L,Q)

    # --- intra-chunk (dual quadratic form) ---
    Lmat = jnp.exp(_segsum(dAc))                                    # (B,H,L,Q,Q)
    scores = jnp.einsum("blqhn,blshn->bhlqs", Cc, Bc,
                        preferred_element_type=jnp.float32)
    y_diag = jnp.einsum("bhlqs,bhlqs,blshp->blqhp", scores, Lmat,
                        xc.astype(jnp.float32),
                        preferred_element_type=jnp.float32)

    # --- chunk states + inter-chunk recurrence (lax.scan) ---
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)                 # (B,H,L,Q)
    states = jnp.einsum("blqhn,bhlq,blqhp->blhpn", Bc, decay_states,
                        xc.astype(jnp.float32),
                        preferred_element_type=jnp.float32)         # (B,L,H,P,N)
    chunk_decay = jnp.exp(A_cum[..., -1])                           # (B,H,L)

    def step(h, inp):
        s_l, d_l = inp                                              # (B,H,P,N), (B,H)
        h_new = h * d_l[..., None, None] + s_l
        return h_new, h                                             # emit state *before* chunk

    init = jnp.zeros((Bsz, H, Pd, N), jnp.float32) if h0 is None else h0
    hT, h_prev = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 2, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                             # (B,L,H,P,N)

    # --- contribution of carried state to each position ---
    state_decay = jnp.exp(A_cum)                                    # (B,H,L,Q)
    y_off = jnp.einsum("blqhn,blhpn,bhlq->blqhp", Cc, h_prev, state_decay,
                       preferred_element_type=jnp.float32)
    y = (y_diag + y_off).reshape(Bsz, S, H, Pd)
    return y, hT


def ssd_block(params: dict, x: jax.Array, cfg: SSDCfg,
              impl: str = "ref"):
    """Full mamba2 mixer. x: (B, S, D) → (B, S, D)."""
    B, S, D = x.shape
    H, Pd, N, G = cfg.n_heads, cfg.headdim, cfg.d_state, cfg.ngroups
    z = jnp.einsum("bsd,dhp->bshp", x, params["wz"].astype(x.dtype))
    xi = jnp.einsum("bsd,dhp->bshp", x, params["wx"].astype(x.dtype))
    Bm = jnp.einsum("bsd,dgn->bsgn", x, params["wB"].astype(x.dtype))
    Cm = jnp.einsum("bsd,dgn->bsgn", x, params["wC"].astype(x.dtype))
    dt = jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32),
                    params["wdt"].astype(jnp.float32))
    dt = jax.nn.softplus(dt + params["dt_bias"][None, None, :])

    xi = constrain(xi, ("batch", None, "ssm_heads", None))
    z = constrain(z, ("batch", None, "ssm_heads", None))
    xi = _causal_conv(xi, params["conv_x"].astype(x.dtype))
    xi = jax.nn.silu(xi)

    A = -jnp.exp(params["A_log"])
    if impl == "pallas":
        from repro.kernels import interpret_mode
        from repro.kernels.ssd import ops as ssd_ops
        y, _ = ssd_ops.ssd(xi, dt, A, Bm, Cm, chunk=min(cfg.chunk, S),
                           interpret=interpret_mode())
    else:
        y, _ = ssd_scan(xi, dt, A, Bm, Cm, chunk=cfg.chunk)
    y = y.astype(x.dtype)
    y = y + params["D_skip"].astype(x.dtype)[None, None, :, None] * xi
    y = _gated_rmsnorm(y, z, params["norm_scale"]).astype(x.dtype)
    y = constrain(y, ("batch", None, "ssm_heads", None))
    return jnp.einsum("bshp,hpd->bsd", y, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# O(1)-state decode
# ---------------------------------------------------------------------------

def init_ssd_state(batch: int, cfg: SSDCfg, dtype=jnp.float32) -> dict:
    return {
        "h": jnp.zeros((batch, cfg.n_heads, cfg.headdim, cfg.d_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.n_heads, cfg.headdim), dtype),
    }


def axes_ssd_state() -> dict:
    return {"h": ("batch", "ssm_heads", None, None),
            "conv": ("batch", None, "ssm_heads", None)}


def ssd_decode_step(params: dict, x: jax.Array, state: dict, cfg: SSDCfg):
    """x: (B, D) single token → (y (B, D), new state)."""
    B, D = x.shape
    H, Pd, N = cfg.n_heads, cfg.headdim, cfg.d_state
    z = jnp.einsum("bd,dhp->bhp", x, params["wz"].astype(x.dtype))
    xi = jnp.einsum("bd,dhp->bhp", x, params["wx"].astype(x.dtype))
    Bm = jnp.einsum("bd,dgn->bgn", x, params["wB"].astype(x.dtype))
    Cm = jnp.einsum("bd,dgn->bgn", x, params["wC"].astype(x.dtype))
    dt = jax.nn.softplus(
        jnp.einsum("bd,dh->bh", x.astype(jnp.float32),
                   params["wdt"].astype(jnp.float32))
        + params["dt_bias"][None, :])

    # rolling causal conv state
    conv_hist = jnp.concatenate([state["conv"], xi[:, None]], axis=1)  # (B,W,H,P)
    k = params["conv_x"].astype(x.dtype)                                # (H,P,W)
    xi = jnp.einsum("bwhp,hpw->bhp", conv_hist, k)
    xi = jax.nn.silu(xi)
    new_conv = conv_hist[:, 1:]

    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt * A[None, :])                                       # (B,H)
    rep = H // cfg.ngroups
    Bh = jnp.repeat(Bm, rep, axis=1)                                    # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1)
    dBx = (dt[..., None, None] * Bh[:, :, None, :].astype(jnp.float32)
           * xi[..., None].astype(jnp.float32))                          # (B,H,P,N)
    h = state["h"] * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch.astype(jnp.float32))
    y = y.astype(x.dtype) + params["D_skip"].astype(x.dtype)[None, :, None] * xi
    y = _gated_rmsnorm(y[:, None].reshape(B, 1, H, Pd),
                       z.reshape(B, 1, H, Pd),
                       params["norm_scale"]).reshape(B, H, Pd).astype(x.dtype)
    out = jnp.einsum("bhp,hpd->bd", y, params["wo"].astype(x.dtype))
    return out, {"h": h, "conv": new_conv}
