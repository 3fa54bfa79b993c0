"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle-2017 capability parity.

Built from scratch on JAX/XLA/Pallas/pjit. The reference codebase
(/root/reference, dawsongzhao/Paddle) defines WHAT we build — the layer
inventory, sequence semantics, trainer/evaluator/optimizer surface, distributed
roles — while the HOW is TPU-first: one coherent stack of

  * pure-functional layers traced into a single jit-compiled XLA program
    (replacing paddle/gserver's virtual-dispatch Layer::forward/backward loop,
    reference: gserver/gradientmachines/NeuralNetwork.cpp:235-285),
  * autodiff via jax.grad (replacing hand-written backward() methods),
  * data parallelism via jax.sharding.Mesh + psum over ICI (replacing
    MultiGradientMachine ring copies and the ParameterServer2 RPC stack,
    reference: gserver/gradientmachines/MultiGradientMachine.h:43-106,
    pserver/ParameterServer2.cpp),
  * packed segment-id sequence batches (replacing
    Argument.sequenceStartPositions, reference: parameter/Argument.h:84-90),
  * lax.scan recurrent groups with beam search (replacing
    RecurrentGradientMachine dynamic frame expansion).

Public surface (mirrors the reference's python/paddle/v2 API, reference:
python/paddle/v2/__init__.py):

    import paddle_tpu as paddle
    paddle.init(use_tpu=True)
    x = paddle.layer.data(name='x', type=paddle.data_type.dense_vector(784))
    y = paddle.layer.fc(input=x, size=10, act=paddle.activation.Softmax())
    cost = paddle.layer.classification_cost(input=y, label=label)
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(cost, params, paddle.optimizer.Momentum(...))
    trainer.train(reader=..., event_handler=...)
"""

import importlib as _importlib

from paddle_tpu.utils import flags as _flags
from paddle_tpu.utils.error import EnforceError, enforce
from paddle_tpu.core import dtype as _dtype
from paddle_tpu.core.place import (
    Place,
    CPUPlace,
    TPUPlace,
    default_place,
    set_default_place,
    device_count,
)

# Lazily-imported public submodules (PEP 562): keeps `import paddle_tpu` cheap
# and free of import cycles while exposing the full v2-style surface.
_SUBMODULES = (
    "activation", "attr", "data_type", "layer", "networks", "pooling",
    "initializer", "optimizer", "parameters", "trainer", "event", "inference",
    "evaluator", "reader", "minibatch", "dataset", "parallel", "image",
    "topology", "config", "ops", "models", "interop", "serve", "data",
)


def __getattr__(name):
    if name in _SUBMODULES:
        from paddle_tpu.observe import spans as observe_spans

        # the set-up span `import` (docs/observability.md): the first
        # `paddle.layer` of a process imports the layers, ops and kernels
        # (a first use inside another's import is that one's time)
        with observe_spans.phase("import", args={"module": name},
                                 unless_in="import"):
            mod = _importlib.import_module("paddle_tpu." + name)
        globals()[name] = mod
        return mod
    if name == "infer":
        from paddle_tpu.inference import infer as fn
        return fn
    if name == "batch":
        from paddle_tpu.minibatch import batch as fn
        return fn
    raise AttributeError("module 'paddle_tpu' has no attribute %r" % name)


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES) + ["infer", "batch"])


__version__ = "0.1.0"

_initialized = False


def init(use_tpu=None, trainer_count=1, seed=None, log_level=None, **kwargs):
    """Initialize the framework process-wide.

    Parity with ``paddle.v2.init(use_gpu=..., trainer_count=...)`` (reference:
    python/paddle/v2/__init__.py + paddle/utils/Flags.cpp flag plumbing), but
    flags configure JAX/XLA instead of gflags: ``use_tpu=True`` demands a
    TPU (an ``EnforceError`` names what ``jax.devices()`` returned when
    there is none — nothing trains on a CPU by accident), ``False`` pins
    the process to the CPU platform, ``None`` takes JAX's default backend.
    ``trainer_count`` declares the data-parallel width used by
    :mod:`paddle_tpu.parallel` when building the device mesh. Also places
    the persistent compile cache (:mod:`paddle_tpu.utils.compile_cache`).
    The body is the set-up span ``init`` (docs/observability.md).
    """
    global _initialized
    from paddle_tpu.observe import spans as observe_spans

    with observe_spans.phase("init"):
        import jax

        from paddle_tpu.core.place import backend_initialized, tpu_devices
        from paddle_tpu.utils import compile_cache

        compile_cache.enable()
        if use_tpu is not None and not use_tpu:
            # really the CPU: pin the platform while that is still
            # possible, and refuse once JAX has already opened another
            # backend
            if not backend_initialized():
                jax.config.update("jax_platforms", "cpu")
            enforce(jax.default_backend() == "cpu",
                    "init(use_tpu=False) after JAX initialised the %r "
                    "backend: call init before any other JAX use, or set "
                    "JAX_PLATFORMS=cpu", jax.default_backend())
        elif use_tpu:
            # EnforceError naming jax.devices() when none is a TPU
            tpu_devices()
        else:
            use_tpu = jax.default_backend() == "tpu"
        _flags.set_flag("use_tpu", bool(use_tpu))
        _flags.set_flag("trainer_count", int(trainer_count))
        if seed is not None:
            _flags.set_flag("seed", int(seed))
        for key, value in kwargs.items():
            _flags.set_flag(key, value, create=True)
        if log_level is not None:
            from paddle_tpu.utils import logger as _logger

            _logger.set_level(log_level)
        # FPE-trap parity (reference: feenableexcept(FE_INVALID|
        # FE_DIVBYZERO|FE_OVERFLOW) at trainer start, TrainerMain.cpp:49):
        # fail fast on NaN/Inf from jitted programs instead of training
        # through garbage. Set unconditionally so re-init with
        # trap_fpe=False turns it back off.
        _trap = bool(_flags.get_flag("trap_fpe"))
        jax.config.update("jax_debug_nans", _trap)
        jax.config.update("jax_debug_infs", _trap)
        set_default_place(TPUPlace() if use_tpu else CPUPlace())
    _initialized = True
    return None


def is_initialized():
    return _initialized
