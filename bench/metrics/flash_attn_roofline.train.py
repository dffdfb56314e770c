"""flash_attn_roofline.train: the least time causal attention needs for
the train steps in the traced window (its FLOPs at the bf16 peak, or its
bytes at the HBM bandwidth, whichever is longer; bench/counts/attention.py)
over the summed device time of the flash kernels' events, as a percentage.
"""
from bench import trace as tr
from bench.counts import attention

KERNELS = ("_flash_fwd_kernel", "_flash_bwd_dq_kernel",
           "_flash_bwd_dkv_kernel")
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
    B, S, L = ctx["batch"], ctx["seq"], c["num_hidden_layers"]
    H, K, D = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    flops = L * attention.flops(B, S, H, D, backward=True)
    nbytes = L * attention.bytes_moved(B, S, H, K, D, backward=True)
    pk = ctx["peaks"]
    least = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * steps * least / busy
