"""Kernel families vs pure-jnp oracles: values AND gradients (interpret).

Built on tests/kernel_harness.py — see its module docstring for the
tolerance policy and for why SSD-pallas and quant are value-only.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.flash import flash_attention
from repro.kernels.flash_attention.ops import flash
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quant.quant import dequantize, quantize
from repro.kernels.quant.ref import dequant_ref, quant_ref
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.ssd.ssd import ssd_scan_pallas
from repro.kernels.xent.ops import bwd_chunk, xent, xent_with_lse
from repro.kernels.xent.ref import xent_ref
from repro.kernels.xent.xent import xent_fwd

from kernel_harness import check_fwd_bwd, rand, tol_for


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False),
                                           ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, want):
    """Interpreted on the CPU, compiled on a TPU, and an error elsewhere
    rather than an interpreter fallback that would hide the device."""
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            interpret_mode()
    else:
        assert interpret_mode() is want


def _qkv(key, B, Sq, Sk, H, K, D, dtype):
    q = rand(key, (B, Sq, H, D), dtype)
    k = rand(jax.random.fold_in(key, 1), (B, Sk, K, D), dtype)
    v = rand(jax.random.fold_in(key, 2), (B, Sk, K, D), dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# flash attention: fwd + the custom-VJP backward kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,K,D,bq,bk", [
    (1, 128, 4, 4, 32, 64, 64),      # MHA
    (2, 256, 4, 2, 64, 128, 64),     # GQA group 2
    (1, 256, 8, 1, 64, 64, 128),     # MQA
    (1, 128, 4, 4, 16, 128, 128),    # block == seq (single block)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_bwd_matches_ref(B, S, H, K, D, bq, bk, dtype):
    q, k, v = _qkv(jax.random.key(0), B, S, S, H, K, D, dtype)
    check_fwd_bwd(
        lambda q, k, v: flash(q, k, v, True, bq, bk, True, True),
        lambda q, k, v: attention_ref(q, k, v, causal=True),
        (q, k, v), diff_argnums=(0, 1, 2), tol=tol_for(dtype),
        msg=f"flash B{B}S{S}H{H}K{K}D{D}")


@pytest.mark.parametrize("remat", [True, False])
def test_flash_bwd_residual_policies_agree(remat):
    """bwd_remat only changes what is saved, never the gradients."""
    q, k, v = _qkv(jax.random.key(1), 1, 128, 128, 4, 2, 32, jnp.float32)
    check_fwd_bwd(
        lambda q, k, v: flash(q, k, v, True, 64, 64, True, remat),
        lambda q, k, v: attention_ref(q, k, v, causal=True),
        (q, k, v), diff_argnums=(0, 1, 2), tol=tol_for(jnp.float32),
        msg=f"flash remat={remat}")


def test_flash_non_causal_uneven_lengths():
    """Cross-attention shape: Sq != Sk, no mask, grads included."""
    q, k, v = _qkv(jax.random.key(2), 1, 128, 256, 2, 2, 32, jnp.float32)
    check_fwd_bwd(
        lambda q, k, v: flash(q, k, v, False, 64, 64, True, True),
        lambda q, k, v: attention_ref(q, k, v, causal=False),
        (q, k, v), diff_argnums=(0, 1, 2), tol=tol_for(jnp.float32),
        msg="flash non-causal Sq!=Sk")


def test_flash_lse_matches_ref():
    """The saved residual itself (logsumexp over keys) is exact."""
    q, k, v = _qkv(jax.random.key(3), 1, 128, 128, 2, 2, 32, jnp.float32)
    _, lse = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True, return_lse=True)
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) / (D ** 0.5)
    mask = jnp.arange(128)[:, None] >= jnp.arange(128)[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    ref = jax.scipy.special.logsumexp(s, axis=-1)          # (B, H, Sq)
    np.testing.assert_allclose(lse, ref, atol=2e-5, rtol=2e-5)


def test_flash_rejects_ragged_blocks():
    q = jnp.zeros((1, 100, 2, 32))
    k = v = jnp.zeros((1, 100, 2, 32))
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)


def test_blocked_gqa_rejects_ragged_blocks():
    """Regression (PR 6): _blocked_gqa used to silently rewrite user
    block sizes that don't divide the sequence; it must now raise."""
    from repro.models.attention import _blocked_gqa
    q = jnp.zeros((1, 100, 2, 1, 16))
    k = v = jnp.zeros((1, 100, 2, 16))
    with pytest.raises(ValueError, match="must divide"):
        _blocked_gqa(q, k, v, causal=True, block_q=64, block_k=64)
    # block > seq stays benign: clamped to one block, no error
    out = _blocked_gqa(q, k, v, causal=True, block_q=512, block_k=512)
    assert out.shape == (1, 100, 2, 1, 16)


# ---------------------------------------------------------------------------
# fused xent: fwd + both custom VJPs (nll-only and nll+lse for z-loss)
# ---------------------------------------------------------------------------

def _xent_inputs(key, T, E, V, vocab):
    h = rand(key, (T, E))
    w = rand(jax.random.fold_in(key, 1), (E, V), scale=0.1)
    lab = jax.random.randint(jax.random.fold_in(key, 2), (T,), 0, vocab)
    return h, w, lab


@pytest.mark.parametrize("T,E,V,vocab,bt,bv", [
    (128, 64, 512, 500, 64, 128),        # padded vocab
    (256, 32, 1024, 1024, 128, 512),     # exact vocab
    (128, 128, 256, 256, 128, 256),      # single vocab tile
])
def test_xent_fwd_matches_ref(T, E, V, vocab, bt, bv):
    h, w, lab = _xent_inputs(jax.random.key(0), T, E, V, vocab)
    nll, lse = xent_fwd(h, w, lab, vocab=vocab, block_t=bt, block_v=bv,
                        interpret=True)
    nll_ref, lse_ref = xent_ref(h, w, lab, vocab=vocab)
    np.testing.assert_allclose(nll, nll_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(lse, lse_ref, atol=1e-4, rtol=1e-4)


# backward vocab sweeps (bwd_chunk): 4 full chunks of 128; 2 of 384 and a
# 128-column tail (896 has no lane-multiple divisor in [288, 576]); 1 chunk
XENT_BWD_SHAPES = pytest.mark.parametrize("T,E,V,vocab", [
    (128, 32, 512, 500),
    (64, 192, 896, 880),
    (128, 64, 128, 100),
])


@XENT_BWD_SHAPES
def test_xent_custom_vjp_matches_autodiff(T, E, V, vocab):
    h, w, lab = _xent_inputs(jax.random.key(3), T, E, V, vocab)
    check_fwd_bwd(
        lambda h, w: xent(h, w, lab, vocab, 64, 128, True),
        lambda h, w: xent_ref(h, w, lab, vocab=vocab)[0],
        (h, w), diff_argnums=(0, 1), tol=tol_for(jnp.float32),
        msg="xent nll")


@XENT_BWD_SHAPES
def test_xent_with_lse_vjp_matches_autodiff(T, E, V, vocab):
    """Both outputs carry cotangents — the z-loss gradient path."""
    h, w, lab = _xent_inputs(jax.random.key(4), T, E, V, vocab)
    check_fwd_bwd(
        lambda h, w: xent_with_lse(h, w, lab, vocab, 64, 128, True),
        lambda h, w: xent_ref(h, w, lab, vocab=vocab),
        (h, w), diff_argnums=(0, 1), tol=tol_for(jnp.float32),
        msg="xent nll+lse")


def test_xent_bwd_chunk_rule():
    """The backward's vocab chunk comes from (T, E, V) alone: a lane-multiple
    divisor of V in [3E/2, 3E], else 3E/2-wide chunks and one tail, capped
    in bytes; the backward's loop runs that many chunks."""
    # Qwen3-1.7B's padded head at batch 2 x 4096: 44 chunks of 3456
    assert bwd_chunk(8192, 2048, 152064) == (3456, 44, 0)
    # Mamba2's padded 50432 = 128·2·197 has no divisor in [3072, 6144]
    assert bwd_chunk(8192, 2048, 50432) == (3072, 16, 1280)
    # a long step: the (T, chunk) f32 tile stays within the byte ceiling
    chunk, n, tail = bwd_chunk(65536, 2048, 152064)
    assert 65536 * chunk * 4 <= 256 << 20
    assert chunk % 128 == 0 and chunk < 2048
    assert chunk * n + tail == 152064

    T, E, V = 8192, 2048, 152064
    args = (jax.ShapeDtypeStruct((T, E), jnp.bfloat16),
            jax.ShapeDtypeStruct((E, V), jnp.bfloat16),
            jax.ShapeDtypeStruct((T,), jnp.int32))

    def grads(h, w, lab):
        def loss(h, w):
            nll, lse = xent_with_lse(h, w, lab, 151936, 128, 256, True)
            return nll.sum() + lse.sum()
        return jax.grad(loss, argnums=(0, 1))(h, w)

    jaxpr = jax.make_jaxpr(grads)(*args).jaxpr
    loops = [e.params["length"] for e in _all_eqns(jaxpr)
             if e.primitive.name == "scan"]
    assert loops == [44]


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _all_eqns(sub)


def test_fused_xent_loss_head_matches_chunked():
    """models.lm.fused_xent (the pallas loss head) ≡ chunked_xent, grads
    included — the hook `xent_impl="pallas"` routes training through."""
    from repro.models.lm import chunked_xent, fused_xent
    key = jax.random.key(5)
    B, T, E, V, vocab = 2, 64, 32, 512, 500
    h = rand(key, (B, T, E))
    w = rand(jax.random.fold_in(key, 1), (E, V), scale=0.1)
    lab = jax.random.randint(jax.random.fold_in(key, 2), (B, T), 0, vocab)
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (B, T)) > 0.2) \
        .astype(jnp.float32)

    def total(fn):
        def s(h, w):
            nll, zl, n = fn(h, w)
            return (nll + zl) / jnp.maximum(n, 1.0)
        return s

    kern = total(lambda h, w: fused_xent(
        h, w, lab, mask, vocab=vocab, block_t=64, block_v=128,
        z_loss_coef=1e-3, interpret=True))
    ref = total(lambda h, w: chunked_xent(
        h, w, lab, mask, vocab=vocab, chunk=32, z_loss_coef=1e-3))
    np.testing.assert_allclose(kern(h, w), ref(h, w), atol=1e-5, rtol=1e-5)
    gk = jax.grad(kern, argnums=(0, 1))(h, w)
    gr = jax.grad(ref, argnums=(0, 1))(h, w)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-3)


# ---------------------------------------------------------------------------
# SSD: pallas fwd vs oracle; gradients via the trainable jnp twin
# (pallas_call with scratch accumulators has no autodiff — by design the
# training path is models.mamba2.ssd_scan, gradchecked below)
# ---------------------------------------------------------------------------

def _ssd_inputs(key, B, S, H, P, G, N):
    x = rand(key, (B, S, H, P))
    dt = jax.nn.softplus(rand(jax.random.fold_in(key, 1), (B, S, H)))
    A = -jnp.exp(rand(jax.random.fold_in(key, 2), (H,), scale=0.3))
    Bm = rand(jax.random.fold_in(key, 3), (B, S, G, N), scale=0.3)
    Cm = rand(jax.random.fold_in(key, 4), (B, S, G, N), scale=0.3)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("B,S,H,P,G,N,C", [
    (1, 128, 2, 32, 1, 16, 64),
    (2, 256, 4, 16, 2, 32, 128),      # grouped B/C
    (1, 64, 2, 64, 1, 64, 64),        # single chunk
])
def test_ssd_pallas_matches_sequential_oracle(B, S, H, P, G, N, C):
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.key(0), B, S, H, P, G, N)
    y_ref, h_ref = ssd_ref(x, dt, A, Bm, Cm)
    y, hT = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=C, interpret=True)
    np.testing.assert_allclose(y, y_ref, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(hT, h_ref, atol=5e-4, rtol=5e-4)


def test_ssd_trainable_path_fwd_bwd_matches_oracle():
    """models.mamba2.ssd_scan (what training differentiates) vs the
    sequential oracle — values and gradients."""
    from repro.models.mamba2 import ssd_scan
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.key(5), 2, 128, 4, 16, 1, 32)
    check_fwd_bwd(
        lambda x, dt, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm, chunk=32)[0],
        lambda x, dt, Bm, Cm: ssd_ref(x, dt, A, Bm, Cm)[0],
        (x, dt, Bm, Cm), diff_argnums=(0, 1, 2, 3),
        tol=dataclasses.replace(tol_for(jnp.float32), fwd=5e-4, grad=5e-3),
        msg="ssd jnp chunked")


def test_ssd_decode_matches_scan():
    """O(1)-state decode steps reproduce the chunked scan token-by-token."""
    from repro.models import mamba2
    cfg = mamba2.SSDCfg(d_model=32, n_heads=2, headdim=32, d_state=16,
                        d_conv=4, chunk=16)
    key = jax.random.key(0)
    params = mamba2.init_ssd(key, cfg, jnp.float32)
    x = rand(jax.random.fold_in(key, 9), (1, 32, 32), scale=0.5)
    y_full = mamba2.ssd_block(params, x, cfg)
    state = mamba2.init_ssd_state(1, cfg, jnp.float32)
    ys = []
    for t in range(32):
        y_t, state = mamba2.ssd_decode_step(params, x[:, t], state, cfg)
        ys.append(y_t)
    y_dec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_dec, y_full, atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# LM integration: the "--attn pallas --xent pallas" training path is
# loss- AND gradient-identical to the ref path (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_lm_pallas_training_matches_ref_path():
    import dataclasses as dc

    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("tinyllama-1.1b", smoke=True)
    cfg = dc.replace(cfg, n_layers=1, dtype="float32")
    cfg_p = dc.replace(cfg, attn_impl="pallas", xent_impl="pallas",
                       attn_bwd_remat=True)
    key = jax.random.key(0)
    tokens = jax.random.randint(jax.random.fold_in(key, 7), (2, 64), 0,
                                cfg.vocab)
    batch = {"tokens": tokens}
    m_ref, m_pal = lm.build(cfg), lm.build(cfg_p)
    params = m_ref.init(key)
    (l_ref, _), g_ref = jax.value_and_grad(m_ref.loss_fn, has_aux=True)(
        params, batch)
    (l_pal, _), g_pal = jax.value_and_grad(m_pal.loss_fn, has_aux=True)(
        params, batch)
    np.testing.assert_allclose(l_pal, l_ref, atol=1e-4, rtol=1e-4)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_ref),
                            jax.tree.leaves(g_pal)):
        np.testing.assert_allclose(
            b, a, atol=5e-4, rtol=5e-3,
            err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# quant (+ hypothesis property) — non-differentiable by construction:
# round() has zero gradient a.e., so only value/roundtrip properties apply
# ---------------------------------------------------------------------------

def test_quant_matches_ref():
    x = jax.random.normal(jax.random.key(0), (2048,)) * 5
    q, s = quantize(x, block=256, interpret=True)
    qr, sr = quant_ref(x, block=256)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(s, sr, rtol=1e-6)
    np.testing.assert_allclose(dequantize(q, s, block=256, interpret=True),
                               dequant_ref(qr, sr, block=256), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([64, 128, 256]),
       st.floats(1e-3, 1e3))
def test_quant_roundtrip_error_bound(seed, block, scale):
    """Property: |dequant(quant(x)) − x|∞ ≤ max|x|/127 per block."""
    x = (np.random.default_rng(seed).standard_normal(4 * block)
         * scale).astype(np.float32)
    qr, sr = quant_ref(jnp.asarray(x), block=block)
    xd = np.asarray(dequant_ref(qr, sr, block=block))
    bound = np.abs(x).reshape(4, block).max(1, keepdims=True) / 127.0 + 1e-6
    assert (np.abs(xd - x).reshape(4, block) <= bound + 1e-7).all()
