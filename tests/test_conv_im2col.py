"""Im2ColConv (models/conv.py) must match nn.Conv numerics and params.

The im2col lowering is the alternative to the stock conv HLO (models/conv.py);
correctness is established here on CPU against the XLA conv.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.conv import Im2ColConv, im2col_conv
from kubeflow_tpu.models.resnet import ResNet18


# every (kernel, stride, size) shape class ResNet-50 emits
CASES = [
    ((1, 1), (1, 1), 8, 16, 12),
    ((1, 1), (2, 2), 8, 16, 12),
    ((3, 3), (1, 1), 8, 16, 12),
    ((3, 3), (2, 2), 8, 16, 12),
    ((3, 3), (2, 2), 8, 16, 13),   # odd size: asymmetric SAME pads
    ((7, 7), (2, 2), 3, 8, 28),    # the stem
]


@pytest.mark.parametrize("kernel,strides,cin,cout,size", CASES)
def test_matches_lax_conv(kernel, strides, cin, cout, size):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (2, size, size, cin), jnp.float32)
    w = jax.random.normal(k2, (*kernel, cin, cout), jnp.float32)
    want = jax.lax.conv_general_dilated(
        x, w, strides, "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    got = im2col_conv(x, w, strides)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_grads_match_lax_conv():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (2, 9, 9, 4), jnp.float32)
    w = jax.random.normal(k2, (3, 3, 4, 8), jnp.float32)

    def loss_ref(x, w):
        return (jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) ** 2).mean()

    def loss_im2col(x, w):
        return (im2col_conv(x, w, (2, 2)) ** 2).mean()

    gx_ref, gw_ref = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    gx, gw = jax.grad(loss_im2col, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(gx, gx_ref, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(gw, gw_ref, atol=2e-4, rtol=2e-4)


def test_module_param_compatible_with_nn_conv():
    """Same param tree; params initialised by one module drive the other."""
    x = jnp.ones((2, 8, 8, 3))
    ours = Im2ColConv(features=16, kernel_size=(3, 3), strides=(2, 2))
    theirs = nn.Conv(features=16, kernel_size=(3, 3), strides=(2, 2),
                     padding="SAME")
    p_ours = ours.init(jax.random.PRNGKey(0), x)
    p_theirs = theirs.init(jax.random.PRNGKey(0), x)
    assert jax.tree.structure(p_ours) == jax.tree.structure(p_theirs)
    assert [a.shape for a in jax.tree.leaves(p_ours)] == [
        a.shape for a in jax.tree.leaves(p_theirs)
    ]
    np.testing.assert_allclose(
        ours.apply(p_theirs, x), theirs.apply(p_theirs, x),
        atol=2e-5, rtol=2e-5,
    )


def test_resnet_im2col_matches_xla_with_shared_params():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3), jnp.float32)
    m_xla = ResNet18(num_classes=10, conv_impl="xla", small_inputs=True)
    m_i2c = ResNet18(num_classes=10, conv_impl="im2col", small_inputs=True)
    variables = m_xla.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        m_i2c.apply(variables, x), m_xla.apply(variables, x),
        atol=5e-4, rtol=5e-4,
    )


def test_conv_impl_auto_selection():
    """auto is the stock conv on every backend; im2col only by name."""
    from kubeflow_tpu.models import conv as conv_mod
    from kubeflow_tpu.models.resnet import ResNet

    m = ResNet(stage_sizes=(1,), block_cls=None, conv_impl="auto")
    assert m._conv_cls() is nn.Conv
    per_stage = ResNet(stage_sizes=(1,), block_cls=None,
                       conv_impl=("im2col", "auto", "xla", "xla", "xla"))
    assert per_stage._conv_cls(0) is conv_mod.ConvCompat
    assert per_stage._conv_cls(1) is nn.Conv
