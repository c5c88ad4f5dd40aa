"""Convolution as im2col + matmul — a conv path that never emits a conv HLO.

Why this exists: an alternative lowering for backends or shapes where
`lax.conv_general_dilated` runs far below matmul throughput. Whether any
ResNet shape on the v5e is such a case is not measured (ROADMAP S3/D4
decide; `ResNet(conv_impl="auto")` is the stock conv). Expressing the conv
as statically-unrolled shifted slices + ONE matmul keeps all FLOPs on the
MXU's well-trodden dot path:

  patches[b, oy, ox, (i*kw + j)*cin + ci] = x_pad[b, oy*sh + i, ox*sw + j, ci]
  y = patches @ kernel.reshape(kh*kw*cin, cout)

which is exactly the reference's im2col/GEMM formulation of conv (the CUDA
lineage: cuDNN IMPLICIT_GEMM), done the XLA way — slices and concats fuse
into the matmul's operand, and autodiff yields pad/slice-add + matmuls for
the backward (no conv-transpose HLO either).

The module is param-compatible with `flax.linen.Conv` (same "kernel"/"bias"
names and HWIO shape), so checkpoints interchange and `ResNet(conv_impl=...)`
can flip per backend with no other change. SAME padding, positive strides,
NHWC only — the shapes ResNet uses.

Reference parity note: the reference platform never owns convs (they live in
user torch/TF images — SURVEY.md §2.2 DP row); this in-tree path exists so
the north-star ResNet bench reflects the framework, not a backend gap.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


def _same_pads(size: int, k: int, s: int) -> tuple[int, int, int]:
    """(pad_lo, pad_hi, out_size) for SAME padding along one spatial dim."""
    out = -(-size // s)  # ceil div
    total = max(0, (out - 1) * s + k - size)
    lo = total // 2
    return lo, total - lo, out


def im2col_conv(
    x: jax.Array,
    kernel: jax.Array,
    strides: Sequence[int] = (1, 1),
) -> jax.Array:
    """SAME-padded NHWC conv computed as shifted slices + one matmul.

    x: (B, H, W, Cin); kernel: (kh, kw, Cin, Cout) [HWIO, as flax]. Matches
    `lax.conv_general_dilated(..., padding="SAME")` numerics in the same
    dtype up to dot-order rounding.
    """
    kh, kw, cin, cout = kernel.shape
    b, h, w, _ = x.shape
    sh, sw = strides
    plo_h, phi_h, oh = _same_pads(h, kh, sh)
    plo_w, phi_w, ow = _same_pads(w, kw, sw)

    if kh == kw == 1:
        # 1x1: pure (strided) matmul, no patches needed
        y = x[:, ::sh, ::sw, :] if (sh, sw) != (1, 1) else x
        return (y.reshape(-1, cin) @ kernel.reshape(cin, cout)).reshape(
            b, oh, ow, cout
        )

    xp = jnp.pad(x, ((0, 0), (plo_h, phi_h), (plo_w, phi_w), (0, 0)))
    # statically-unrolled kh*kw shifted strided views; concat order matches
    # the row-major flatten of the HWIO kernel's leading (kh, kw, cin) dims
    cols = [
        jax.lax.slice(
            xp,
            (0, i, j, 0),
            (b, i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1, cin),
            (1, sh, sw, 1),
        )
        for i in range(kh)
        for j in range(kw)
    ]
    patches = jnp.concatenate(cols, axis=-1)  # (B, OH, OW, kh*kw*cin)
    y = patches.reshape(-1, kh * kw * cin) @ kernel.reshape(kh * kw * cin, cout)
    return y.reshape(b, oh, ow, cout)


class Im2ColConv(nn.Module):
    """Drop-in for `nn.Conv(features, kernel_size, strides, use_bias, dtype)`
    restricted to NHWC + SAME padding, lowering via `im2col_conv`."""

    features: int
    kernel_size: Sequence[int]
    strides: Sequence[int] = (1, 1)
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kh, kw = self.kernel_size
        cin = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (kh, kw, cin, self.features),
            self.param_dtype,
        )
        y = im2col_conv(
            x.astype(self.dtype), kernel.astype(self.dtype), tuple(self.strides)
        )
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (self.features,),
                self.param_dtype,
            )
            y = y + bias.astype(self.dtype)
        return y


# Flax auto-names submodules by CLASS name ("Conv_0", "Im2ColConv_0", ...),
# so a drop-in replacement must also be NAMED "Conv" for param trees (and
# therefore checkpoints) to interchange with nn.Conv-built models. A real
# class statement (not type(...)) keeps it picklable: pickle resolves
# kubeflow_tpu.models.conv.Conv by attribute lookup.
class Conv(Im2ColConv):
    pass


ConvCompat = Conv
