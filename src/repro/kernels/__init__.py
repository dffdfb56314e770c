"""Pallas TPU kernels for the perf-critical compute layers.

All kernels are TPU-targeted (pl.pallas_call + BlockSpec VMEM tiling) and
validated in interpret mode on CPU against pure-jnp oracles (ref.py).
"""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on the current backend.

    True only on the CPU, where the interpreter is the only way to run
    them; False on a TPU, where they compile with Mosaic.  Any other
    platform is an error: no kernel here has a lowering for it, and
    falling back to the interpreter there would hide the device.
    """
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(f"no Pallas lowering for platform {platform!r} "
                           f"(kernels compile for tpu, interpret on cpu)")
    return platform == "cpu"
