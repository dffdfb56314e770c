"""train_mfu: model FLOP/s of the train steps in the traced window over
the chips' bf16 peak.  Steps are counted from the device's own record of
the step program's executions (``XLA Modules``), each by the share of it
that lies in the window; FLOPs per step come from the configuration's
bench/counts/models/<reference>.py.
"""
from bench import trace as tr

STEP_MODULE = "jit_step_fn"      # the jitted train step's program name


def read(ctx):
    t = ctx["trace"]
    if not t.devices:
        return None
    lo, hi = t.window
    steps = tr.module_runs(t.devices[0], STEP_MODULE, lo, hi)
    if steps == 0:
        return None
    secs = (hi - lo) * 1e-9
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * steps * ctx["step_flops"] / (secs * peak)
