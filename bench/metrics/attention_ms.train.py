"""attention_ms.train: device milliseconds per train step of the ops under
the program's ``attention`` scope (pre-norm, projections, rope, qk-norm,
the flash kernels and the residual add; forward, recompute and backward),
from the op_names the trace keeps (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "attention")
