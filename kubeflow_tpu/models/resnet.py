"""ResNet family — north-star config #2 (BASELINE.md: ResNet-50 images/sec/chip).

TPU-first choices:
  - channels-last NHWC (XLA's native conv layout on TPU; MXU tiles want the
    channel dim innermost),
  - bf16 compute / f32 params via the `dtype` attr (trainer casts inputs),
  - BatchNorm under jit SPMD: the batch axis is sharded over the mesh's
    data axes, so the mean/var reductions XLA inserts are *global* psums —
    sync-BN for free, no NCCL sync-BN plumbing like the reference's user
    images (kubeflow/examples resnet — SURVEY.md L6) need,
  - static shapes everywhere; stride/padding arithmetic resolved at trace.

Parity target: the reference platform launches torchvision/TF ResNet-50 user
images under TFJob/PyTorchJob (SURVEY.md §2.2 data-parallel row); here the
model is in-tree so every parallelism axis can be tested end-to-end.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


def s2d_pack(x):
    """Space-to-depth 2x2 pack: (B, H, W, C) -> (B, H/2, W/2, 4C).

    Channel order: c' = di*2C + dj*C + c for the (di, dj) sub-pixel — the
    layout `stem_weights_7x7_to_s2d` assumes. The packed stem trades the
    lane-starved K=49*3=147 stem GEMM for a lane-denser K=16*12=192 one
    with identical FLOPs (probe_resnet.py section B measures the win)."""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(
        0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)


def stem_weights_7x7_to_s2d(w7):
    """EXACT weight transform: 7x7/s2 SAME stem kernel -> the equivalent
    4x4/s1 kernel over the s2d-packed input.

    On an even input, SAME for k=7/s=2 pads (2, 3); the 7x7 kernel
    embeds in an 8x8/s2 kernel with a trailing zero row/col
    (w8[:7, :7] = w7, taps at rows 2i-2 .. 2i+5 with the +5 tap zero).
    An 8x8/s2 conv equals a 4x4/s1 conv on the packed input with
    w4[u, v, di*2C+dj*C+c, o] = w8[2u+di, 2v+dj, c, o] and packed
    padding (1, 2), output exactly H/2 — so logits match the 7x7 model
    to dtype rounding (pinned by tests/test_models_resnet.py)."""
    kh, kw, cin, cout = w7.shape
    assert (kh, kw) == (7, 7), w7.shape
    w8 = jnp.zeros((8, 8, cin, cout), w7.dtype).at[:7, :7].set(w7)
    # split each 8-tap axis a = 2u + di into (u, di)
    w4 = w8.reshape(4, 2, 4, 2, cin, cout).transpose(0, 2, 1, 3, 4, 5)
    return w4.reshape(4, 4, 4 * cin, cout)


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut on shape change."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.act(self.norm()(y))
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = self.act(self.norm()(y))
        y = self.conv(self.filters * 4, (1, 1))(y)
        # zero-init the last BN scale: residual branch starts as identity,
        # the standard trick for stable large-batch training
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), strides=(self.strides, self.strides)
            )(residual)
            residual = self.norm()(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 block (ResNet-18/34)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = self.act(self.norm()(y))
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters, (1, 1), strides=(self.strides, self.strides)
            )(residual)
            residual = self.norm()(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    """Configurable ResNet over NHWC images.

    stage_sizes/block pick the variant; `small_inputs` swaps the 7x7/stride-2
    stem + maxpool for a 3x3 stem (CIFAR/MNIST-scale images).
    """

    #: MXU-heavy: the Trainer's AUTO compute dtype resolves to bf16 on
    #: accelerator backends (trainer.resolve_compute_dtype clones the
    #: module with `dtype` flipped; params stay f32)
    PREFERRED_COMPUTE_DTYPE = jnp.bfloat16

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.float32
    small_inputs: bool = False
    # Conv lowering: "xla" = lax conv HLO, "im2col" = slices+matmul
    # (models/conv.py — param-compatible), "auto" = "xla" (which lowering
    # is faster per shape on the chip is not measured; ROADMAP S3/D4).
    # PER-STAGE override: a sequence of 5 impls (stem, stage1..stage4) —
    # e.g. ("im2col", "xla", "xla", "xla", "xla") — so a probe verdict
    # like "im2col wins only at the lane-starved shapes" is shippable as
    # a config flip, no model surgery.
    conv_impl: str | Sequence[str] = "auto"
    # Stem variant: "7x7" = canonical 7x7/s2 + maxpool; "s2d" = space-to-
    # depth 2x2 pack + 4x4/s1 conv (+ the same maxpool) — identical math
    # under `stem_weights_7x7_to_s2d` (exact, tested), lane-denser GEMM
    # (K 147 -> 192). Shipped as config so a probe_resnet verdict flips
    # the bench via KFT_RESNET_STEM with zero code change.
    stem: str = "7x7"

    def _impl_for(self, stage: int) -> str:
        """stage 0 = stem, 1..4 = residual stages."""
        impl = self.conv_impl
        if not isinstance(impl, str):
            impl = impl[stage]
        return "xla" if impl == "auto" else impl

    def _conv_cls(self, stage: int = 0) -> ModuleDef:
        impl = self._impl_for(stage)
        if impl == "im2col":
            from kubeflow_tpu.models.conv import ConvCompat

            return ConvCompat  # Im2ColConv under the flax name "Conv"
        if impl == "xla":
            return nn.Conv
        raise ValueError(f"unknown conv_impl {self.conv_impl!r}")

    @nn.compact
    def __call__(self, x, train: bool = False):
        if x.ndim == 2:  # flat grayscale vectors (mnist-style fixtures)
            side = int(x.shape[-1] ** 0.5)
            x = x.reshape((x.shape[0], side, side, 1))
        stem_conv = partial(self._conv_cls(0), use_bias=False,
                            dtype=self.dtype)
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        x = x.astype(self.dtype)
        if self.small_inputs:
            x = stem_conv(self.width, (3, 3), name="conv_init")(x)
        elif self.stem == "s2d":
            x = s2d_pack(x)
            # SAME for k=4/s=1 pads (1,2) — exactly the 7x7/s2 SAME
            # receptive field (see stem_weights_7x7_to_s2d); default
            # padding keeps the stem compatible with ConvCompat/im2col,
            # which supports SAME only. Output is H/2 x W/2.
            x = stem_conv(self.width, (4, 4), name="conv_init")(x)
        elif self.stem == "7x7":
            x = stem_conv(self.width, (7, 7), strides=(2, 2),
                          name="conv_init")(x)
        else:
            raise ValueError(f"unknown stem {self.stem!r}")
        x = nn.relu(norm(name="bn_init")(x))
        if not self.small_inputs:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, n_blocks in enumerate(self.stage_sizes):
            conv = partial(self._conv_cls(i + 1), use_bias=False,
                           dtype=self.dtype)
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(
                    filters=self.width * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                )(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=(2, 2, 2, 2), block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=(3, 4, 23, 3), block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=(3, 8, 36, 3), block_cls=BottleneckBlock)
