#!/usr/bin/env python3
"""Bring-up smoke test: the trainer and the server on a TPU, through the
same entry points a user calls (``repro.launch.train.main`` and
``repro.launch.serve.main``), at Qwen3-1.7B's published widths.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: 2x2 and pp=2 trainers
                                     # against a one-chip run

One process drives the chip(s); it starts no JAX children.  Weights are
random, made from a seed.  Any failed check exits non-zero.  On success the
last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Off a TPU the script exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "tests"))

ARCH = "qwen3-1.7b"
GiB = 2**30

# One-chip train phase.  Published widths (d_model 2048, 16 q / 8 kv heads
# of 128, qk-norm, d_ff 6144, tied vocab 151936); depth cut to 6 of 28
# layers so that f32 params + AdamW moments (≈ 16 B/param, 613M params)
# and the step's working set fit one v5e's 16 GB.  seq 4096 is
# configs/shapes.py train_4k; batch 1 is what the ref step it is compared
# with fits beside that state (compiled for v5e: 14.6 of 15.75 GiB, where
# the Pallas step needs 11.0).
TRAIN = dict(layers=6, batch=1, seq=4096, steps=3)
# Four-chip phase: the same cut model on the ref path — Mosaic kernels
# cannot be partitioned by GSPMD, so the Pallas path runs on one chip only.
# Global batch 4 so that pp=2 with 2 micro-batches still splits 2-way over
# data; seq 1024 so that the one-chip ref run it is compared with fits
# (11.0 GiB).
MESH = dict(layers=6, batch=4, seq=1024, steps=4)
# Serve phase: full published depth (28 layers, ≈ 6.9 GB of f32 params).
SERVE = dict(requests=8, prompt=512, gen=32, slots=8, max_len=1024)

# Step-0 losses of the Pallas and ref paths are the same function of the
# same params and batch, computed with bf16 activations.  Each bf16
# rounding is ≤ 2^-8 relative; the two paths round at different points
# (flash's online softmax vs the materialised one, the fused xent's
# per-tile logsumexp vs the chunked one), and the loss averages those
# differences over 4096 tokens.  At a loss of ≈ 12 nats, 0.05 is ~0.4% —
# far above that noise, far below what a wrong mask or a dropped tile
# moves (a whole key block is several nats for the rows it feeds).
LOSS_ATOL = 0.05
# The same model on a different mesh: identical math, different reduction
# orders (sharded matmuls, the pipeline's micro-batch mean), which bf16
# then carries through AdamW for three updates.
MESH_LOSS_ATOL = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class IRDump:
    """Collect the StableHLO JAX emits for each jitted function while
    active (``jax_dump_ir_to``).  Lowering runs on every jit, cache hit or
    not, so a kernel's ``tpu_custom_call`` shows up either way."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")

    def __enter__(self):
        import jax
        jax.config.update("jax_dump_ir_to", self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.config.update("jax_dump_ir_to", "")

    def modules(self, fn_name: str) -> list[str]:
        """Texts of the dumped modules of ``jit(fn_name)``."""
        tag = f"_jit_{fn_name}_"
        out = []
        for f in sorted(os.listdir(self.dir)):
            if tag in f and f.endswith(".mlir"):
                with open(os.path.join(self.dir, f)) as fh:
                    out.append(fh.read())
        return out

    def has_kernel(self, fn_name: str) -> bool:
        mods = self.modules(fn_name)
        check(bool(mods), f"no module of jit({fn_name}) was lowered")
        return all("tpu_custom_call" in m for m in mods)

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_train(*, layers: int, batch: int, seq: int, steps: int,
              impl: str, extra: tuple = ()) -> dict:
    """One ``launch/train.py:main`` run with a fresh checkpoint dir."""
    from repro.launch import train
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    argv = ["--arch", ARCH, "--overrides", f"n_layers={layers}",
            "--batch", str(batch), "--seq", str(seq),
            "--steps", str(steps), "--log-every", "1", "--seed", "0",
            "--attn", impl, "--xent", impl, "--ckpt-dir", ckpt, *extra]
    log(f"[train] {' '.join(argv)}")
    t0 = time.perf_counter()
    try:
        out = train.main(argv)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    out["seconds"] = time.perf_counter() - t0
    log(f"[train] {impl}: losses {out['losses']}; step seconds "
        f"{out['step_seconds']} (the first compiles); {out['seconds']:.1f}s "
        f"in all")
    check(out["final_step"] == steps,
          f"final_step {out['final_step']} != {steps} steps asked for")
    check(len(out["losses"]) == steps,
          f"{len(out['losses'])} losses for {steps} steps")
    check(all(l == l and abs(l) != float("inf") for l in out["losses"]),
          f"non-finite loss: {out['losses']}")
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def kernel_phase(*, interpret: bool = False) -> None:
    """Each kernel the smoke's paths run, on the device, against its
    ``ref.py`` oracle: values and (flash, xent) gradients, with the
    bf16 tolerances of tests/kernel_harness.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from kernel_harness import check_fwd_bwd, rand, tol_for
    from repro.kernels.flash_attention import paged_decode
    from repro.kernels.flash_attention.ops import flash
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.xent.ops import xent_with_lse
    from repro.kernels.xent.ref import xent_ref

    tol = tol_for(jnp.bfloat16)
    key = jax.random.key(0)
    B, S, H, K, D = 1, 1024, 16, 8, 128
    q = rand(key, (B, S, H, D), jnp.bfloat16)
    k = rand(jax.random.fold_in(key, 1), (B, S, K, D), jnp.bfloat16)
    v = rand(jax.random.fold_in(key, 2), (B, S, K, D), jnp.bfloat16)
    def exact(fn):
        """``fn`` at full f32 matmul precision (a TPU's default for f32 is
        one bf16 pass).  Oracles only: Mosaic refuses an fp32 contraction
        of the kernels' bf16 operands."""
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return run

    check_fwd_bwd(
        lambda q, k, v: flash(q, k, v, True, 512, 512, interpret, False),
        exact(lambda q, k, v: attention_ref(q, k, v, causal=True)),
        (q, k, v), diff_argnums=(0, 1, 2), tol=tol, msg="flash")
    log("[kernels] flash fwd+bwd matches attention_ref")

    T, E, V, vocab = 512, 256, 2048, 2000
    h = rand(jax.random.fold_in(key, 3), (T, E), jnp.bfloat16)
    w = rand(jax.random.fold_in(key, 4), (E, V), jnp.bfloat16)
    lab = jax.random.randint(jax.random.fold_in(key, 5), (T,), 0, vocab)
    check_fwd_bwd(
        lambda h, w: xent_with_lse(h, w, lab, vocab, 256, 512, interpret),
        exact(lambda h, w: xent_ref(h, w, lab, vocab=vocab)),
        (h, w), diff_argnums=(0, 1), tol=tol, msg="xent")
    log("[kernels] xent nll+lse fwd+bwd matches xent_ref")

    Bs, ps, mp, P = 4, 64, 4, 13
    qd = rand(jax.random.fold_in(key, 6), (Bs, H, D), jnp.bfloat16)
    kp = rand(jax.random.fold_in(key, 7), (P, K, ps, D), jnp.bfloat16)
    vp = rand(jax.random.fold_in(key, 8), (P, K, ps, D), jnp.bfloat16)
    table = jnp.array([[3, 7, 1, 0], [2, 5, 9, 11], [4, 0, 0, 0],
                       [12, 6, 8, 10]], jnp.int32)
    pos = jnp.array([130, 255, 10, 200], jnp.int32)
    got = paged_decode(qd, kp, vp, table, pos, interpret=interpret)

    @exact
    def gather_ref(qd, kp, vp):
        kg = jnp.swapaxes(kp[table], 2, 3).reshape(Bs, mp * ps, K, D)
        vg = jnp.swapaxes(vp[table], 2, 3).reshape(Bs, mp * ps, K, D)
        s = jnp.einsum("bkgd,bskd->bkgs",
                       qd.astype(jnp.float32).reshape(Bs, K, H // K, D),
                       kg.astype(jnp.float32)) * D ** -0.5
        live = jnp.arange(mp * ps)[None, :] <= pos[:, None]
        s = jnp.where(live[:, None, None, :], s, -1e30)
        return jnp.einsum("bkgs,bskd->bkgd", jax.nn.softmax(s, axis=-1),
                          vg.astype(jnp.float32)).reshape(Bs, H, D)

    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(gather_ref(qd, kp, vp)),
                               atol=tol.fwd, rtol=tol.fwd,
                               err_msg="paged_decode")
    log("[kernels] paged_decode matches the gather reference")


def train_phase(cfg: dict, *, expect_kernels: bool = True) -> None:
    """The trainer with the fused kernels, then with the ref path on the
    same params and batches (same seed): every step taken, every loss
    finite, the step-0 losses within LOSS_ATOL."""
    import jax
    ir = IRDump()
    try:
        with ir:
            fused = run_train(impl="pallas", **cfg)
        fused.pop("state")
        if expect_kernels:
            check(ir.has_kernel("step_fn"),
                  "the pallas train step has no tpu_custom_call")
            log("[train] the compiled step calls the Pallas kernels "
                "(tpu_custom_call)")
    finally:
        ir.close()
    log(f"[train] peak device memory {peak_bytes(jax.devices()[0]) / GiB:.2f}"
        f" GiB")
    ref = run_train(impl="ref", **cfg)
    ref.pop("state")
    d0 = abs(fused["losses"][0] - ref["losses"][0])
    log(f"[train] step-0 loss pallas {fused['losses'][0]!r} ref "
        f"{ref['losses'][0]!r} |diff| {d0:.3g} (tol {LOSS_ATOL})")
    check(d0 <= LOSS_ATOL, f"step-0 loss differs from ref by {d0}")


def serve_phase(cfg: dict, *, expect_kernels: bool = True) -> None:
    """The server with a paged cache and the Pallas attention kernels:
    every request completes with all its tokens."""
    import jax
    from repro.launch import serve
    argv = ["--arch", ARCH, "--cache", "paged", "--attn", "pallas",
            "--requests", str(cfg["requests"]),
            "--prompt-len", str(cfg["prompt"]), "--gen", str(cfg["gen"]),
            "--batch-slots", str(cfg["slots"]),
            "--max-len", str(cfg["max_len"]), "--seed", "0"]
    argv += list(cfg.get("extra", ()))
    log(f"[serve] {' '.join(argv)}")
    ir = IRDump()
    try:
        t0 = time.perf_counter()
        with ir:
            out = serve.main(argv)
        dt = time.perf_counter() - t0
        if expect_kernels:
            check(ir.has_kernel("serve"),
                  "the paged decode step has no tpu_custom_call")
            log("[serve] the decode step calls paged_decode "
                "(tpu_custom_call)")
    finally:
        ir.close()
    log(f"[serve] {out['completed']} requests, {out['tokens']} tokens, "
        f"{out['steps']} decode steps in {dt:.1f}s (compile included); "
        f"peak device memory {peak_bytes(jax.devices()[0]) / GiB:.2f} GiB")
    check(out["completed"] == cfg["requests"],
          f"{out['completed']}/{cfg['requests']} requests completed")
    short = {r: n for r, n in out["request_tokens"].items()
             if n != cfg["gen"]}
    check(not short, f"requests without their {cfg['gen']} tokens: {short}")


def mesh_phase(cfg: dict, *, min_bytes: int = GiB) -> None:
    """The cut trainer on 2x2 (replica × split) and on pp=2 × data 2
    (GPipe, 2 micro-batches), each against one chip on the same params and
    batches: step-0 loss and the loss after three steps."""
    import jax
    devs = jax.devices()[:4]
    cfg = dict(cfg)
    base = tuple(cfg.pop("extra", ()))
    one = run_train(impl="ref", extra=base + ("--mesh", "1"), **cfg)
    one.pop("state")
    runs = {"2x2": ("--mesh", "2x2"),
            "pp2": ("--pp", "2", "--micro-batches", "2")}
    for name, extra in runs.items():
        out = run_train(impl="ref", extra=base + extra, **cfg)
        state = out.pop("state")
        # spread: every device holds a shard of the params, some leaf is
        # split (its shard smaller than the whole), and each device's
        # allocator shows real use while the state is alive
        leaves = jax.tree.leaves(state["params"])
        held = {s.device for l in leaves for s in l.addressable_shards}
        check(held >= set(devs), f"{name}: params not on all 4 devices")
        split = [l.shape for l in leaves
                 if l.addressable_shards[0].data.shape != l.shape]
        check(bool(split), f"{name}: no parameter is split across devices")
        used = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in devs]
        log(f"[{name}] {len(split)}/{len(leaves)} param leaves split; "
            f"bytes_in_use per device (GiB) "
            f"{[round(u / GiB, 2) for u in used]}")
        check(min(used) >= min_bytes,
              f"{name}: a device holds < {min_bytes} bytes: {used}")
        del state
        for i in (0, cfg["steps"] - 1):
            d = abs(out["losses"][i] - one["losses"][i])
            log(f"[{name}] loss at step {i}: {out['losses'][i]!r} vs one "
                f"chip {one['losses'][i]!r} |diff| {d:.3g} "
                f"(tol {MESH_LOSS_ATOL})")
            check(d <= MESH_LOSS_ATOL, f"{name}: loss {i} off by {d}")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel, train and serve phases on one chip; "
                         "4: only the 2x2 / pp=2 trainers against one chip")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} device(s)",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[device] {dev.platform} {dev.device_kind!r} x{len(devs)}; "
        f"compile cache {enable_compile_cache()}")

    if args.chips == 4:
        phases = {"mesh": lambda: mesh_phase(MESH)}
    else:
        phases = {"kernels": kernel_phase, "train": lambda: train_phase(TRAIN),
                  "serve": lambda: serve_phase(SERVE)}
    failed = []
    for name, phase in phases.items():
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:       # report it, run the other phases, fail below
            traceback.print_exc()
            failed.append(name)
        log(f"[{name}] {'FAILED' if name in failed else 'ok'} in "
            f"{time.perf_counter() - t0:.1f}s")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
