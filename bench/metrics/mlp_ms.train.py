"""mlp_ms.train: device milliseconds per train step of the ops under the
program's ``mlp`` scope (pre-norm, the gated MLP's matmuls and the
residual add; forward, recompute and backward), from the op_names the
trace keeps (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "mlp")
