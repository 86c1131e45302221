"""Optimizer factory: config ``optimizer.type`` -> optax GradientTransformation.

TPU-native counterpart of the reference's optimizer zoo
(``deepspeed/ops/adam`` FusedAdam/DeepSpeedCPUAdam, ``ops/lamb`` FusedLamb,
``ops/lion``, ``ops/adagrad``, and the engine's optimizer selection at
``runtime/engine.py:1405 _configure_basic_optimizer``).  On TPU "fused" is the
default: XLA fuses the whole optax update chain into a handful of kernels, so
the CUDA multi-tensor-apply machinery (csrc/adam/multi_tensor_adam.cu) has no
translation — the per-param lax ops below compile to the same fused form.

1-bit optimizers (OnebitAdam ``runtime/fp16/onebit/adam.py:14``, OnebitLamb,
ZeroOneAdam) are provided via the error-feedback sign-compression wrapper in
``deepspeed_tpu/comm/compressed.py`` composed around the base Adam here.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import optax

from ..utils.logging import log_dist

ADAM = "adam"
ADAMW = "adamw"
FUSED_ADAM = "fusedadam"
CPU_ADAM = "deepspeedcpuadam"
LAMB = "lamb"
FUSED_LAMB = "fusedlamb"
LION = "lion"
FUSED_LION = "fusedlion"
ADAGRAD = "adagrad"
SGD = "sgd"
ONEBIT_ADAM = "onebitadam"
ZERO_ONE_ADAM = "zerooneadam"
ONEBIT_LAMB = "onebitlamb"
MUON = "muon"


def build_optimizer(
    type_name: str,
    params: Optional[Dict[str, Any]] = None,
    learning_rate=None,
) -> optax.GradientTransformation:
    """``learning_rate`` (scalar or schedule fn) overrides ``params['lr']`` —
    the engine passes its schedule here so LR lives inside the jitted step."""
    params = dict(params or {})
    name = type_name.lower().replace("_", "")
    lr = learning_rate if learning_rate is not None else params.get("lr", 1e-3)
    wd = params.get("weight_decay", 0.0)
    betas = params.get("betas", (0.9, 0.999))
    eps = params.get("eps", 1e-8)

    if name in (ONEBIT_ADAM, ZERO_ONE_ADAM, ONEBIT_LAMB):
        # no silent dense fallback: the compressed-communication step lives in
        # runtime/onebit.py and only the engine can run it (it owns the
        # shard_map over the DP axes)
        raise ValueError(
            f"{type_name} is engine-managed: pass it as config optimizer.type "
            "to deepspeed_tpu.initialize(); it has no standalone optax form"
        )
    if name in (ADAM, FUSED_ADAM, CPU_ADAM):
        if params.get("adam_w_mode", True) and name == ADAM:
            # reference FusedAdam defaults to adam_w_mode=True (ops/adam)
            return optax.adamw(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)
        if wd:
            return optax.chain(
                optax.scale_by_adam(b1=betas[0], b2=betas[1], eps=eps),
                optax.add_decayed_weights(wd),
                optax.scale_by_learning_rate(lr),
            )
        return optax.adam(lr, b1=betas[0], b2=betas[1], eps=eps)
    if name == ADAMW:
        return optax.adamw(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)
    if name in (LAMB, FUSED_LAMB):
        return optax.lamb(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)
    if name in (LION, FUSED_LION):
        b = params.get("betas", (0.9, 0.99))
        return optax.lion(lr, b1=b[0], b2=b[1], weight_decay=wd)
    if name == ADAGRAD:
        return optax.adagrad(lr, eps=params.get("eps", 1e-10))
    if name == SGD:
        return optax.sgd(lr, momentum=params.get("momentum", 0.0), nesterov=params.get("nesterov", False))
    if name == MUON:
        try:
            return optax.contrib.muon(lr)
        except AttributeError:
            log_dist("optax has no muon; falling back to adamw")
            return optax.adamw(lr, weight_decay=wd)
    raise ValueError(f"unknown optimizer type '{type_name}'")
