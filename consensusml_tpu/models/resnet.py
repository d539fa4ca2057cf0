"""ResNet family (ResNet-18/34/50/101/152) for the vision workloads.

Reference parity: "ResNet-50 on CIFAR-10, 8-worker ring consensus
all-reduce" and the headline imgs/sec/chip metric (BASELINE.json
configs[1] + metric; SURVEY.md L5 — mount empty, so the architecture is
the canonical He et al. 2015 bottleneck ResNet rather than a port).

TPU-first choices:
- NHWC layout (XLA:TPU's native conv layout — channels on the 128-lane
  minor dimension feeds the MXU directly);
- bf16 compute / f32 BatchNorm statistics and params (MXU-native mixed
  precision);
- BatchNorm stays on the XLA path by default (``norm_impl="flax"``,
  ``norm_dtype`` selecting the elementwise dtype; statistic reductions
  are f32 either way). Hand-written fused Pallas BN(+ReLU) kernels
  exist behind ``norm_impl="auto"|"pallas"``
  (:mod:`consensusml_tpu.models.fused_bn`), unmeasured on this
  installation (ROADMAP C2);
- BatchNorm running stats live in the ``batch_stats`` collection and are
  returned as ``model_state`` so the trainer gossip-averages them across
  workers along with the weights.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.models.fused_bn import FusedBatchNorm
from consensusml_tpu.models.losses import softmax_cross_entropy

__all__ = ["ResNet", "resnet18", "resnet50", "resnet_loss_fn"]

ModuleDef = Any


def _flax_norm_act(use_running_average: bool, dtype: Any):
    """``norm_impl="flax"`` factory: BN + optional relu, applied inline.

    The ``nn.BatchNorm`` is created inside the CALLER's compact scope, so
    params keep the pre-fused-BN names (``BatchNorm_N`` at block level) —
    flax-path checkpoints stay compatible across the fused-BN change. The
    fused path (``FusedBatchNorm_N``) necessarily names them differently.
    """

    def make(act: Any = None, scale_init: Any = nn.initializers.ones_init()):
        if act not in (None, "relu"):
            raise ValueError(f"unsupported act {act!r}")

        def apply(x):
            y = nn.BatchNorm(
                use_running_average=use_running_average,
                momentum=0.9,
                epsilon=1e-5,
                dtype=dtype,
                scale_init=scale_init,
            )(x)
            return nn.relu(y) if act == "relu" else y

        return apply

    return make


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1(4x) with projection shortcut (ResNet-50/101/152)."""

    filters: int
    strides: int = 1
    conv: ModuleDef = nn.Conv
    norm: Any = None  # factory/Module partial: norm(act=..., scale_init=...)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # standalone use (no norm passed): train-mode flax BN
        norm = self.norm or _flax_norm_act(False, self.dtype)
        residual = x
        y = self.conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = norm(act="relu")(y)
        y = self.conv(
            self.filters, (3, 3), (self.strides, self.strides), use_bias=False, dtype=self.dtype
        )(y)
        y = norm(act="relu")(y)
        y = self.conv(self.filters * 4, (1, 1), use_bias=False, dtype=self.dtype)(y)
        # zero-init the last BN scale: residual branch starts as identity
        y = norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4,
                (1, 1),
                (self.strides, self.strides),
                use_bias=False,
                dtype=self.dtype,
            )(residual)
            residual = norm()(residual)
        return nn.relu(residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 (ResNet-18/34)."""

    filters: int
    strides: int = 1
    conv: ModuleDef = nn.Conv
    norm: Any = None  # factory/Module partial: norm(act=..., scale_init=...)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # standalone use (no norm passed): train-mode flax BN
        norm = self.norm or _flax_norm_act(False, self.dtype)
        residual = x
        y = self.conv(
            self.filters, (3, 3), (self.strides, self.strides), use_bias=False, dtype=self.dtype
        )(x)
        y = norm(act="relu")(y)
        y = self.conv(self.filters, (3, 3), use_bias=False, dtype=self.dtype)(y)
        y = norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters,
                (1, 1),
                (self.strides, self.strides),
                use_bias=False,
                dtype=self.dtype,
            )(residual)
            residual = norm()(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """Configurable ResNet with ImageNet (7x7/2 + maxpool) or CIFAR (3x3)
    stem."""

    stage_sizes: Sequence[int]
    block: Callable[..., nn.Module]
    num_classes: int = 1000
    width: int = 64
    stem: str = "imagenet"  # or "cifar"
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = None  # flax-BN elementwise dtype; None => same as dtype
    norm_impl: str = "flax"  # flax (XLA, default) | auto|pallas|jnp (fused)
    norm_pack_small: bool = True  # lane-pack C<128 BNs (vs XLA fallback)

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, padding="SAME")
        if self.norm_impl == "flax":
            # mean/var reductions stay float32 inside flax regardless
            norm = _flax_norm_act(
                not train,
                self.dtype if self.norm_dtype is None else self.norm_dtype,
            )
        elif self.norm_impl in ("auto", "pallas", "jnp", "interpret"):
            if self.norm_dtype is not None:
                raise ValueError(
                    "norm_dtype only applies to norm_impl='flax'; the fused "
                    "kernels always read the input dtype with f32 arithmetic"
                )
            norm = functools.partial(
                FusedBatchNorm,
                use_running_average=not train,
                impl=self.norm_impl,
                pack_small=self.norm_pack_small,
            )
        else:
            raise ValueError(f"unknown norm_impl {self.norm_impl!r}")
        x = jnp.asarray(x, self.dtype)
        if self.stem == "imagenet":
            x = conv(self.width, (7, 7), (2, 2), use_bias=False, dtype=self.dtype)(x)
            x = norm(act="relu")(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        elif self.stem == "cifar":
            x = conv(self.width, (3, 3), use_bias=False, dtype=self.dtype)(x)
            x = norm(act="relu")(x)
        else:
            raise ValueError(f"unknown stem {self.stem!r}")
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block(
                    filters=self.width * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    dtype=self.dtype,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return jnp.asarray(x, jnp.float32)


def resnet18(
    num_classes: int = 10, stem: str = "cifar", dtype=jnp.bfloat16,
    norm_dtype=None, norm_impl: str = "flax",
) -> ResNet:
    return ResNet(
        stage_sizes=[2, 2, 2, 2], block=BasicBlock, num_classes=num_classes,
        stem=stem, dtype=dtype, norm_dtype=norm_dtype, norm_impl=norm_impl,
    )


def resnet50(
    num_classes: int = 1000, stem: str = "imagenet", dtype=jnp.bfloat16,
    norm_dtype=None, norm_impl: str = "flax", norm_pack_small: bool = True,
) -> ResNet:
    return ResNet(
        stage_sizes=[3, 4, 6, 3],
        block=BottleneckBlock,
        num_classes=num_classes,
        stem=stem,
        dtype=dtype,
        norm_dtype=norm_dtype,
        norm_impl=norm_impl,
        norm_pack_small=norm_pack_small,
    )


def resnet_loss_fn(model: ResNet):
    """``loss_fn(params, model_state, batch, rng) -> (loss, new_state)``.

    ``model_state`` is ``{"batch_stats": ...}``; the trainer gossips it
    with the weights so BN statistics reach cross-worker consensus.
    """

    def loss_fn(params, model_state, batch, rng):
        logits, updated = model.apply(
            {"params": params, **model_state},
            batch["image"],
            train=True,
            mutable=["batch_stats"],
        )
        return softmax_cross_entropy(logits, batch["label"]), updated

    return loss_fn


def resnet_init(model: ResNet, input_shape=(1, 32, 32, 3)):
    """``init(rng) -> (params, model_state)`` for ``init_stacked_state``."""

    def init(rng):
        variables = model.init(rng, jnp.zeros(input_shape), train=True)
        params = variables["params"]
        model_state = {k: v for k, v in variables.items() if k != "params"}
        return params, model_state

    return init
