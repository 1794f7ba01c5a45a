"""paddle_tpu: a TPU-native deep-learning framework.

A from-scratch framework with the API surface of the reference (a PaddlePaddle
dev snapshot, see SURVEY.md) built on JAX/XLA/Pallas/pjit: eager tensors with
define-by-run autograd, nn layers/optimizers/dataloaders, jit compilation of
dygraph code, bf16 AMP, and a full hybrid-parallel distributed stack mapped
onto TPU meshes (ICI/DCN) instead of NCCL.
"""

from __future__ import annotations

import sys as _sys

import jax as _jax

# Mosaic/MLIR lowering of Pallas kernels inside large jaxprs (deep models,
# autograd-built training steps) recurses per jaxpr eqn; the CPython default
# limit of 1000 aborts compilation of real-size models with RecursionError.
if _sys.getrecursionlimit() < 20000:
    _sys.setrecursionlimit(20000)

import os as _os

# Persistent compilation cache. Where JAX_COMPILATION_CACHE_DIR is set, jax
# reads it itself and nothing is set here. Otherwise the cache lives at one
# fixed path inside the checkout: the path is part of the cache key, so a
# directory that moves never hits. A process that must not share it turns it
# off explicitly (`jax.config.update("jax_enable_compilation_cache", False)`).
REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir",
                       _os.path.join(REPO_ROOT, ".jax_cache"))
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)

# int64/float64 must exist as real dtypes (reference semantics: int64 is the
# default integer type). Float defaults remain float32 — creation ops and
# `to_tensor` normalize python floats to the framework default dtype.
_jax.config.update("jax_enable_x64", True)

# -- core ------------------------------------------------------------------
from .core.dtype import (  # noqa: F401
    DType, bool_, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128, float8_e4m3fn, float8_e5m2,
    get_default_dtype, set_default_dtype, finfo, iinfo,
)
from .core.dtype import bool_ as bool  # noqa: F401  (paddle.bool)
from .core.tensor import Tensor, to_tensor, is_tensor  # noqa: F401
from .core.flags import set_flags, get_flags  # noqa: F401
from .core.generator import seed, get_rng_state, set_rng_state  # noqa: F401
from .core import enforce  # noqa: F401

# -- autograd --------------------------------------------------------------
from .autograd import no_grad, enable_grad, set_grad_enabled, is_grad_enabled, grad  # noqa: F401
from . import autograd  # noqa: F401

# -- ops (flat paddle.* namespace) ----------------------------------------
from .ops import *  # noqa: F401,F403
from . import ops  # noqa: F401
from . import linalg  # noqa: F401

# -- framework -------------------------------------------------------------
from .framework.io import save, load  # noqa: F401
from .framework.framework import (  # noqa: F401
    CPUPlace, CUDAPlace, TPUPlace, get_device, set_device, is_compiled_with_cuda,
    is_compiled_with_xpu, is_compiled_with_rocm, is_compiled_with_custom_device,
    in_dynamic_mode, device_count, enable_static, disable_static,
    set_printoptions, CUDAPinnedPlace, get_cuda_rng_state,
    set_cuda_rng_state, disable_signal_handler, check_shape,
)
from .framework import ParamAttr  # noqa: F401
from .core.dtype import DType as dtype  # noqa: F401
from .framework.parameter import create_parameter, LazyGuard  # noqa: F401
from .batch import batch  # noqa: F401

# -- subpackages (paddle.nn, paddle.optimizer, ...) ------------------------
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import amp  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import device  # noqa: F401
from . import framework  # noqa: F401

__version__ = "0.1.0"


def __getattr__(name):
    # heavy subpackages loaded lazily to keep import light
    if name == "distributed":
        import importlib
        mod = importlib.import_module(".distributed", __name__)
        globals()["distributed"] = mod
        return mod
    if name == "profiler":
        import importlib
        mod = importlib.import_module(".profiler", __name__)
        globals()["profiler"] = mod
        return mod
    if name == "vision":
        import importlib
        mod = importlib.import_module(".vision", __name__)
        globals()["vision"] = mod
        return mod
    if name == "incubate":
        import importlib
        mod = importlib.import_module(".incubate", __name__)
        globals()["incubate"] = mod
        return mod
    if name in ("distribution", "text", "quantization", "static",
                "auto_tuner", "audio", "sparse", "fft", "signal",
                "sysconfig", "hub", "dataset", "geometric", "inference",
                "onnx", "decomposition", "cost_model", "reader", "version",
                "strings", "observability", "resilience", "serving",
                "planner"):
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    if name in ("hapi", "Model", "callbacks"):
        import importlib
        mod = importlib.import_module(".hapi", __name__)
        globals()["hapi"] = mod
        globals()["Model"] = mod.Model
        globals()["callbacks"] = mod.callbacks
        return globals()[name]
    if name in ("summary", "flops"):
        import importlib
        mod = importlib.import_module(".hapi.model_summary", __name__)
        globals()["summary"] = mod.summary
        globals()["flops"] = mod.flops
        return globals()[name]
    if name == "utils":
        import importlib
        mod = importlib.import_module(".utils", __name__)
        globals()["utils"] = mod
        return mod
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def DataParallel(layers, strategy=None, comm_buffer_size_MB=25,
                 last_comm_buffer_size_MB=1, find_unused_parameters=False,
                 group=None):
    """Reference paddle.DataParallel(layer): data-parallel wrapper. Under
    SPMD the wrapping is fleet.distributed_model over a dp-only topology;
    if fleet was never initialized, initialize a pure-dp world first
    (matching the reference's init_parallel_env + DataParallel pairing)."""
    from .distributed.fleet import DistributedStrategy, fleet
    from .distributed.topology import get_hybrid_communicate_group
    if get_hybrid_communicate_group() is None:
        import jax
        s = DistributedStrategy()
        s.hybrid_configs = {"dp_degree": len(jax.devices()), "mp_degree": 1,
                            "pp_degree": 1, "sharding_degree": 1,
                            "sep_degree": 1}
        fleet.init(is_collective=True, strategy=s)
    return fleet.distributed_model(layers)
