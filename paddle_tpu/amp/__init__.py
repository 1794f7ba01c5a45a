from .auto_cast import auto_cast, amp_guard, decorate, amp_decorate, is_auto_cast_enabled, get_amp_dtype  # noqa: F401
from .grad_scaler import GradScaler, AmpScaler  # noqa: F401
from . import amp_lists  # noqa: F401
from .debugging import check_numerics, enable_operator_stats_collection, disable_operator_stats_collection  # noqa: F401


def is_float16_supported(device=None):
    """Reference: amp/__init__.py is_float16_supported. fp16 compute is an
    accelerator capability; the CPU fallback path upcasts."""
    if device is not None:
        plat = str(device).split(":")[0]
    else:
        # probe only when needed: jax.devices() initializes the backend
        import jax
        try:
            plat = jax.devices()[0].platform
        except Exception:
            plat = "cpu"
    return plat in ("tpu", "gpu")


def is_bfloat16_supported(device=None):
    """Reference: amp/__init__.py is_bfloat16_supported. bf16 is native on
    every TPU generation and emulated losslessly by XLA:CPU."""
    return True
