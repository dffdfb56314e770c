"""loss_head_ms.train: device milliseconds per train step of the ops under
the program's ``loss_head`` scope (final norm, the tied head's transpose
and cast, the fused cross-entropy forward kernel and its backward loop),
from the op_names the trace keeps (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "loss_head")
