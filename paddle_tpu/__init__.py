"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capability surface of the reference
(PaddlePaddle ~2.2/2.3-dev snapshot at /root/reference, see SURVEY.md),
re-designed TPU-first: eager tensors + tape autograd over JAX/XLA, a jitted
program path, Fleet-style hybrid parallelism compiled to GSPMD/shard_map over
a `jax.sharding.Mesh`, and native C++ runtime components where the reference
is native.

Top-level namespace mirrors `import paddle`.
"""
from __future__ import annotations

import time as _time

_t_import = _time.perf_counter_ns()  # ``setup_import_ns``: to the last line

# Paddle semantics: int64 indices/labels, explicit float management. JAX's
# x64-off mode silently truncates to int32, so enable it; every float path in
# this package passes dtypes explicitly (default float32 / bf16 on MXU).
import jax as _jax

_jax.config.update("jax_enable_x64", True)
# Matmul/conv precision is left at JAX's default. The reference's own fp32
# default is TF32 tensor cores on Ampere (cuDNN/cuBLAS allow_tf32=true),
# which corresponds to the MXU's default bf16-pass mode — while forcing
# "highest" makes every fp32 conv a multi-pass emulation that the TPU
# compiler autotunes pathologically slowly (minutes-long compiles for
# conv grads) and that runs ~3-6x slower. fp64 stays exact; use
# `with jax.default_matmul_precision("highest")` for reference-exact fp32.

# Core types -----------------------------------------------------------------
from .core.dtype import (  # noqa: F401
    bool_ as bool,  # type: ignore[misc]
    uint8, int8, int16, int32, int64,
    float16, bfloat16, float32, float64, complex64, complex128,
    set_default_dtype, get_default_dtype,
)
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.place import (  # noqa: F401
    CPUPlace, TPUPlace, CUDAPlace, Place,
    is_compiled_with_cuda, is_compiled_with_tpu,
)
from .core.engine import no_grad, enable_grad, set_grad_enabled, grad_enabled  # noqa: F401
from .core.random import seed, get_rng_state, set_rng_state, program_rng  # noqa: F401

# Ops (also monkey-patches Tensor methods) -----------------------------------
from . import ops as _ops  # noqa: F401
from .ops.creation import (  # noqa: F401
    zeros, ones, full, empty, zeros_like, ones_like, full_like, empty_like,
    arange, linspace, logspace, eye, diag, diagflat, tril, triu, meshgrid,
    assign, clone, numel, rand, randn, randint, randint_like, randperm,
    uniform, normal, gaussian, standard_normal, bernoulli, multinomial,
    shard_index,
)
from .ops.math import (  # noqa: F401
    add, subtract, multiply, divide, floor_divide, remainder, mod, pow,
    maximum, minimum, fmax, fmin, atan2, exp, expm1, log, log2, log10, log1p,
    sqrt, rsqrt, abs, sign, floor, ceil, round, trunc, frac, sin, cos, tan,
    asin, acos, atan, sinh, cosh, tanh, asinh, acosh, atanh, erf, erfinv,
    reciprocal, square, digamma, lgamma, sigmoid, clip, lerp, nan_to_num,
    stanh, isnan, isinf, isfinite, equal, not_equal, greater_than,
    greater_equal, less_than, less_equal, logical_and, logical_or,
    logical_not, logical_xor, bitwise_and, bitwise_or, bitwise_xor,
    bitwise_not, equal_all, allclose, isclose, sum, mean, max, min, prod,
    amax, amin, all, any, std, var, median, quantile, nanmean, nansum,
    logsumexp, argmax, argmin, cumsum, cumprod, cummax, cummin, logcumsumexp,
    matmul, mm, dot, inner, outer, addmm, bmm, kron, trace, diagonal, mv,
    dist, cast, scale, increment, neg, heaviside, hypot, copysign, nextafter,
    gcd, lcm, ldexp,
)
from .ops.manipulation import (  # noqa: F401
    reshape, reshape_, transpose, t, concat, stack, split, chunk, unbind,
    unstack, squeeze, unsqueeze, flatten, expand, expand_as, broadcast_to,
    broadcast_shape, broadcast_tensors, tile, repeat_interleave, flip, roll,
    rot90, gather, gather_nd, take_along_axis, put_along_axis, scatter,
    scatter_nd, scatter_nd_add, index_select, index_sample, index_add,
    masked_select, masked_fill, where, nonzero, slice, strided_slice, crop,
    topk, sort, argsort, searchsorted, unique, unique_consecutive, bincount,
    histogram, atleast_1d, atleast_2d, atleast_3d, as_real, as_complex, real,
    imag, conj, moveaxis, swapaxes,
)

from .ops import generated as _generated  # noqa: F401
from .ops import inplace as _inplace  # noqa: F401 (attaches Tensor methods)
from .ops import control_flow as _control_flow  # noqa: F401
from .ops.extra import (  # noqa: F401
    einsum, segment_sum, segment_mean, segment_max, segment_min, histogramdd,
)

# generated ops join the top-level namespace without clobbering hand-written
for _n, _fn in _generated.GENERATED.items():
    if _n not in globals():
        globals()[_n] = _fn
del _n, _fn

from .ops.misc import (  # noqa: F401
    is_tensor, is_floating_point, is_integer, is_complex, is_empty, rank,
    shape, tolist, reverse, multiplex, mode, poisson, set_printoptions,
    create_parameter, disable_signal_handler, is_compiled_with_cinn,
    is_compiled_with_rocm, is_compiled_with_xpu, is_compiled_with_npu,
    is_compiled_with_mlu, is_compiled_with_ipu, get_cuda_rng_state,
    set_cuda_rng_state,
)
from .linalg import (  # noqa: F401
    cholesky, cholesky_solve, cond, cov, eig, eigvals, eigvalsh, lstsq, lu,
    multi_dot, qr, triangular_solve, norm, inverse,
)
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import linalg  # noqa: F401
from . import autograd  # noqa: F401
from .autograd import grad  # noqa: F401
from . import device  # noqa: F401
from .device import set_device, get_device  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from .framework.io import save, load  # noqa: F401
from .framework.flags import set_flags, get_flags  # noqa: F401
from . import distributed  # noqa: F401
from . import fault  # noqa: F401
from . import incubate  # noqa: F401
from . import inference  # noqa: F401
from . import text  # noqa: F401
from . import sparse  # noqa: F401
from . import quantization  # noqa: F401
from . import utils  # noqa: F401
from . import profiler  # noqa: F401
from .hapi.model import Model, summary  # noqa: F401
from .hapi.flops import flops  # noqa: F401
from . import onnx  # noqa: F401
from . import hub  # noqa: F401
from . import reader  # noqa: F401  (v1 reader decorators)
from . import dataset  # noqa: F401  (v1 generator datasets)
from . import tensor  # noqa: F401  (paddle.tensor namespace)
from . import cost_model  # noqa: F401
from . import callbacks  # noqa: F401
from .batch import batch  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .nn import ParamAttr  # noqa: F401
from .core.place import (  # noqa: F401
    CUDAPinnedPlace, IPUPlace, MLUPlace, NPUPlace, XPUPlace, CustomPlace,
)
from .core.engine import grad_enabled as is_grad_enabled  # noqa: F401
from .ops.math import floor_mod  # noqa: F401
from .ops.inplace import INPLACE_OPS as _INPLACE_OPS

# v1 top-level in-place names (paddle.tanh_ etc.)
for _n in ("scatter_", "squeeze_", "tanh_", "unsqueeze_", "relu_", "clip_",
           "exp_", "sqrt_", "subtract_", "add_"):
    if _n in _INPLACE_OPS:
        globals()[_n] = _INPLACE_OPS[_n]
del _n

# paddle.dtype — the dtype TYPE for isinstance checks (all framework dtypes,
# including the ml_dtypes bfloat16, are numpy dtype instances)
import numpy as _np  # noqa: E402

dtype = _np.dtype


def get_cudnn_version():
    """No cuDNN in a TPU-native build (the reference returns a version int
    on CUDA installs; None means 'not compiled with cuDNN' there too)."""
    return None
from . import distribution  # noqa: F401

from .io import DataLoader  # noqa: F401
from .nn.layer.common import ParameterList  # noqa: F401

disable_static = lambda *a, **k: None  # eager is the default (reference: paddle.disable_static)
enable_static = lambda *a, **k: None
in_dynamic_mode = lambda: True

# Warm executable starts: the lazy-flush signatures (and per-op jit keys) are
# stable across processes, so XLA's persistent compilation cache turns the
# first step of a rerun into a disk hit instead of a compile. Off via
# FLAGS_xla_persistent_cache=0 (see framework/flags.py).
from .core.compat import enable_persistent_compilation_cache as _enable_pcc  # noqa: E402

_enable_pcc()

__version__ = "0.1.0"

profiler.counter_inc("setup_import_ns", _time.perf_counter_ns() - _t_import)
