"""xent_bwd_roofline.train: the least time the loss head's backward
cross-entropy needs per train step (dh and dW over the padded vocabulary
at the bf16 peak, or its bytes at the HBM bandwidth; bench/counts/
xent_bwd.py) over the device time per step of the ops under the program's
``loss_head`` scope in the backward phase (bench/scopes.py), as a
percentage.  Those ops also hold the final norm's and the head cast's
backward, which the count leaves out.  0 where the step carries no
scope, as a step compiled before the scopes does."""
from bench import scopes
from bench.counts import xent_bwd


def read(ctx):
    ms = scopes.layer_ms(ctx, "loss_head", ("bwd",))
    if not ms:
        return ms
    c = ctx["config"]
    m = c["vocab_pad_multiple"]
    Vp = -(-c["vocab_size"] // m) * m
    T = ctx["batch"] * (ctx["seq"] - 1)
    E = c.get("hidden_size") or c["d_model"]
    pk = ctx["peaks"]
    least = max(xent_bwd.flops(T, E, Vp) / pk["bf16_flops_per_s"],
                xent_bwd.bytes_moved(T, E, Vp) / pk["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
