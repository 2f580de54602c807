"""ResNet for the benchmark harness.

The reference benchmarked ResNet-50 on synthetic 224x224x3 batches
(notebooks/ml/Benchmarks/benchmark.ipynb cell 2, SURVEY.md §6). This is
a fresh flax ResNet-v1.5 (stride-2 in the 3x3 of bottlenecks, as the
benchmark model family) built to move fewer HBM bytes a step (the
cell `resnet50.train-bs128` runs it; its per-op breakdown is PERF §5):

- bfloat16 conv compute so the FLOPs land on the MXU;
- bfloat16 norm *output* (``norm_dtype``) so the residual stream and
  every BN/relu chain move half the bytes — flax's BatchNorm still
  accumulates mean/var in float32 internally, and running statistics
  and all parameters stay float32 (``param_dtype`` default);
- a space-to-depth stem (``s2d_stem``): the 7x7 stride-2 conv over
  3-channel 224x224 input is algebraically rewritten as a 4x4 stride-1
  conv over the 2x2-space-to-depth input (112x112x12), which uses the
  MXU's input rows 4x better while keeping the parameter a standard
  7x7x3xW kernel (checkpoint-compatible; the rewrite happens at apply
  time);
- optional per-block rematerialization (``remat``): save only the
  residual stream at block boundaries and recompute the 3-4 intra-block
  conv/BN/relu activations during backward (gradients are numerically
  identical; no cell sets it, so what it costs or saves on the chip is
  not measured: PERF §7).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

Conv = partial(nn.Conv, use_bias=False)


def space_to_depth(x: jax.Array, block: int = 2) -> jax.Array:
    """NHWC space-to-depth: (B, H, W, C) -> (B, H/b, W/b, b*b*C)."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, block * block * c)


def _s2d_stem_kernel(kernel: jax.Array) -> jax.Array:
    """Rewrite a 7x7xCxW stride-2 kernel as the equivalent 4x4x(4C)xW
    stride-1 kernel over 2x2-space-to-depth input.

    Derivation: output(i,j) sums In[2i+kr-3, 2j+kc-3]*K[kr,kc]. Writing
    input rows as 2p+a (s2d block row p, sub-row a in {0,1}) gives
    kr = 2*pa + a - 1 for s2d tap pa in 0..3 — i.e. pad the 7x7 kernel
    to 8x8 at the leading edge, then fold the parity bit into channels
    in the same (a, b, c) order ``space_to_depth`` produces.
    """
    kh, kw, c, out = kernel.shape  # 7, 7, C, W
    k8 = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
    k8 = k8.reshape(4, 2, 4, 2, c, out)  # (pa, a, qb, b, c, o)
    k8 = k8.transpose(0, 2, 1, 3, 4, 5)  # (pa, qb, a, b, c, o)
    return k8.reshape(4, 4, 4 * c, out)


class BottleneckBlock(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    dtype: jnp.dtype = jnp.bfloat16
    norm: Callable[..., Any] = nn.BatchNorm
    norm_dtype: jnp.dtype | None = None  # None = follow ``dtype``

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = partial(
            self.norm,
            use_running_average=not train,
            momentum=0.9,
            dtype=self.norm_dtype if self.norm_dtype is not None else self.dtype,
        )
        residual = x
        y = Conv(self.filters, (1, 1), dtype=self.dtype)(x)
        y = norm()(y)
        y = nn.relu(y)
        y = Conv(self.filters, (3, 3), self.strides, dtype=self.dtype)(y)
        y = norm()(y)
        y = nn.relu(y)
        y = Conv(self.filters * 4, (1, 1), dtype=self.dtype)(y)
        y = norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = Conv(
                self.filters * 4, (1, 1), self.strides, dtype=self.dtype, name="proj"
            )(residual)
            residual = norm(name="proj_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    norm_dtype: jnp.dtype | None = None  # None = follow ``dtype``
    s2d_stem: bool = True
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        # The stem parameter is always the canonical 7x7xCxW kernel; the
        # space-to-depth rewrite is an apply-time algebraic identity.
        stem_kernel = self.param(
            "stem_conv",
            nn.initializers.lecun_normal(),
            (7, 7, x.shape[-1], self.width),
            jnp.float32,
        ).astype(self.dtype)
        if self.s2d_stem and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            x = jax.lax.conv_general_dilated(
                space_to_depth(x),
                _s2d_stem_kernel(stem_kernel),
                window_strides=(1, 1),
                padding=((2, 1), (2, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        else:
            x = jax.lax.conv_general_dilated(
                x,
                stem_kernel,
                window_strides=(2, 2),
                padding=((3, 3), (3, 3)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        x = nn.BatchNorm(
            use_running_average=not train,
            momentum=0.9,
            dtype=self.norm_dtype if self.norm_dtype is not None else self.dtype,
        )(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        # static_argnums=(2,): the train flag is Python control flow
        # inside the block, not a traceable input. Blocks carry explicit
        # names so the parameter tree is identical with remat on or off
        # (nn.remat would otherwise rename to CheckpointBottleneckBlock_n,
        # making checkpoints non-interchangeable).
        block_cls = nn.remat(BottleneckBlock, static_argnums=(2,)) if self.remat else BottleneckBlock
        n = 0
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(
                    self.width * 2**i, strides, self.dtype, norm_dtype=self.norm_dtype,
                    name=f"BottleneckBlock_{n}",
                )(x, train)
                n += 1
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)


def ResNet50(
    num_classes: int = 1000,
    dtype: jnp.dtype = jnp.bfloat16,
    norm_dtype: jnp.dtype | None = None,
    s2d_stem: bool = True,
    remat: bool = False,
) -> ResNet:
    return ResNet(
        [3, 4, 6, 3],
        num_classes=num_classes,
        dtype=dtype,
        norm_dtype=norm_dtype,
        s2d_stem=s2d_stem,
        remat=remat,
    )


def ResNet18ish(
    num_classes: int = 10, dtype: jnp.dtype = jnp.bfloat16, remat: bool = False
) -> ResNet:
    """Small bottleneck variant for CI-scale tests."""
    return ResNet([1, 1, 1, 1], num_classes=num_classes, width=16, dtype=dtype, remat=remat)
