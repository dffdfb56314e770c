"""optimizer_ms.train: device milliseconds per train step of the ops under
the program's ``optimizer`` scope (AdamW's update of every leaf, gradient
clipping included), from the op_names the trace keeps (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "optimizer")
