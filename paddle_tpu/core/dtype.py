"""Dtype policy.

The reference is compiled for one `real` type (float or double, cf.
WITH_DOUBLE, CMakeLists.txt:44). Here dtype is a runtime policy: float32 is
the default numeric type for parity with gradient-check tolerances; bfloat16
is the TPU performance type for matmul-heavy benchmarks (MXU-native).
"""

import jax.numpy as jnp
import numpy as np

from paddle_tpu.utils import flags

float32 = jnp.float32
bfloat16 = jnp.bfloat16
float16 = jnp.float16
int32 = jnp.int32
int64 = jnp.int64
bool_ = jnp.bool_

_NAMES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float64": jnp.float64,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "bool": jnp.bool_,
}


def canonical(dtype):
    if dtype is None:
        return default_dtype()
    if isinstance(dtype, str):
        return _NAMES[dtype]
    return jnp.dtype(dtype).type


def default_dtype():
    return _NAMES[flags.get_flag("default_dtype")]


def set_default_dtype(dtype):
    name = np.dtype(dtype).name if not isinstance(dtype, str) else dtype
    flags.set_flag("default_dtype", name)


def matmul_precision():
    """jax.lax precision for MXU matmuls; 'highest' keeps fp32 accumulation so
    numeric-vs-analytic gradient checks pass with reference tolerances
    (cf. SURVEY.md hard-parts: fp32-on-TPU toggle)."""
    return flags.get_flag("matmul_precision")


def compute_dtype():
    """Forward-pass compute dtype, or None for 'same as parameters'.

    The TPU mixed-precision training policy: parameters (and optimizer
    state) stay float32 masters, but the traced forward/backward runs in
    bfloat16 — single-pass MXU matmuls/convs with float32 accumulation,
    half the HBM traffic for activations. Gradients re-emerge float32 at
    the parameter-cast boundary (the VJP of convert_element_type), so the
    optimizer update is exact. Numerically sensitive reductions
    (batch-norm statistics, cost/log-softmax) upcast locally to float32.
    Replaces the reference's single compiled `real` type (WITH_DOUBLE) and
    the round-1 blanket bf16x3 'high' precision with the idiomatic policy.
    """
    name = flags.get_flag("compute_dtype")
    return _NAMES[name] if name else None


def set_mixed_precision(dtype="bfloat16"):
    """Enable (or disable with None/'') the mixed-precision policy."""
    if not dtype:
        flags.set_flag("compute_dtype", "")
        return
    name = np.dtype(dtype).name if not isinstance(dtype, str) else dtype
    flags.set_flag("compute_dtype", name)


def to_compute(x):
    """Cast a floating array to the compute dtype (no-op when unset)."""
    cd = compute_dtype()
    if cd is not None and hasattr(x, "dtype") and \
            jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != cd:
        return x.astype(cd)
    return x


def wide(dtype):
    """The dtype sums and decays are kept in beside operands of ``dtype``:
    float32, or wider where the operands are."""
    return jnp.promote_types(dtype, jnp.float32)


def upcast_f32(x):
    """Locally lift low-precision values to float32 (cost layers, BN stats)."""
    if hasattr(x, "dtype") and x.dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32)
    return x
