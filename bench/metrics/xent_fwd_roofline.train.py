"""xent_fwd_roofline.train: the least time the loss head's forward
cross-entropy needs for the train steps in the traced window (logits
FLOPs over the padded vocabulary at the bf16 peak, or its bytes at the
HBM bandwidth; bench/counts/xent.py) over the summed device time of the
fused xent kernel's events, as a percentage.  The loss head's backward
runs as XLA ops inside a loop, which the trace does not name, so it is
not part of this share."""
from bench import trace as tr
from bench.counts import xent

KERNELS = ("_xent_kernel",)
STEP_MODULE = "jit_step_fn"


def read(ctx):
    t = ctx["trace"]
    if not t.devices:
        return None
    busy = tr.kernel_ns(t, ctx["kernels"], KERNELS) * 1e-9
    if busy == 0:
        return None
    lo, hi = t.window
    steps = tr.module_runs(t.devices[0], STEP_MODULE, lo, hi)
    c = ctx["config"]
    m = c["vocab_pad_multiple"]
    Vp = -(-c["vocab_size"] // m) * m
    T = ctx["batch"] * (ctx["seq"] - 1)
    E = c.get("hidden_size") or c["d_model"]
    pk = ctx["peaks"]
    least = max(xent.flops(T, E, Vp) / pk["bf16_flops_per_s"],
                xent.bytes_moved(T, E, Vp) / pk["hbm_bytes_per_s"])
    return 100.0 * steps * least / busy
