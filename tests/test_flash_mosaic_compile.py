"""The forward flash kernel as Mosaic compiles it, and the XLA backward that ships beside
it, for a v5e that is described, not attached: what interpret mode cannot see (a slice off the tiling, more VMEM than a kernel
may use) is refused here, at no chip time. Nothing runs, so nothing here is a result or a
time; `test_flash_forward_on_the_chip_matches_float32_attention` is the verdict on both.

The topology is described inside a fixture, never at import: one process a machine may
load the TPU's library, and every xdist worker imports this file."""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.parallel import ring_attention as ra
from kubeflow_tpu.parallel.attention_mask import BlockDiffusion, Causal


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library from loading here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


CALLS = [
    # (b, lq, h, d), dtype, mask -> the kernel the rule names
    ((8, 1024, 16, 64), jnp.bfloat16, Causal(), "flash_fwd_resident_q256_k512"),  # gpt2m-train-1k
    ((8, 512, 12, 64), jnp.bfloat16, None, "flash_fwd_resident_q512_k512_g4"),  # BERT-base
    ((8, 256, 12, 64), jnp.bfloat16, None, "flash_fwd_resident_q256_k256_g4"),  # ViT-B/16
    ((1, 4096, 32, 128), jnp.bfloat16, Causal(1024), "flash_fwd_resident_q256_k512"),  # Mistral's window
    ((1, 8192, 16, 64), jnp.bfloat16, Causal(), "flash_fwd_resident_q256_k512"),
    ((1, 32768, 8, 128), jnp.bfloat16, Causal(), "flash_fwd_kvgrid_q512_k1024"),
    ((1, 32768, 4, 128), jnp.bfloat16, Causal(4096), "flash_fwd_kvgrid_q512_k1024"),
    ((2, 1024, 16, 64), jnp.float32, Causal(), "flash_fwd_resident_q256_k512"),
    # trinitym-train-8k: a sliding and a full layer
    ((1, 8192, 32, 128), jnp.bfloat16, Causal(2048), "flash_fwd_resident_q256_k512"),
    ((1, 8192, 32, 128), jnp.bfloat16, Causal(), "flash_fwd_resident_q256_k512"),
    # sdar30b-train-4k: 4,096 tokens, clean and noisy copies, blocks of 4;
    # and K/V past the budget under the same mask
    ((1, 8192, 32, 128), jnp.bfloat16, BlockDiffusion(4096, 4),
     "flash_fwd_resident_q256_k512_blockdiff"),
    ((1, 32768, 4, 128), jnp.bfloat16, BlockDiffusion(16384, 4),
     "flash_fwd_kvgrid_q512_k1024_blockdiff"),
    # dsv2lite-train-8k: keys of 192 (256 lanes), values of 128, (b, lq, h, d, dv): K and V
    # resident count 15.0 MiB, over the rule's budget, so the KV axis is on the grid
    ((1, 8192, 16, 192, 128), jnp.bfloat16, Causal(), "flash_fwd_kvgrid_q512_k1024_d192v128"),
]
#: the scale latent attention gives its cell: 192^-0.5 x YaRN's mscale squared
MLA_SCALE = 0.1147214


def _shapes(shape):
    """(q's and k's shape, v's): a fifth number is v's head size."""
    return (shape[:4], (*shape[:3], shape[4])) if len(shape) == 5 else (shape, shape)


@pytest.mark.parametrize("shape,dtype,mask,name", CALLS)
def test_forward_kernel_compiles_for_the_v5e(one_chip, monkeypatch, shape, dtype, mask, name):
    (b, length, _, _), v_shape = _shapes(shape)
    qk = jax.ShapeDtypeStruct(shape[:4], dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct(v_shape, dtype, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((b, 1, 1, length), jnp.float32, sharding=one_chip)
    # `_flash_forward_tiled` asks the backend whether to interpret; here the CPU answers
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(lambda q, k, v, bias: ra._flash_forward(
        q, k, v, bias, 128, 128, mask, want_lse=True, scale=MLA_SCALE if len(shape) == 5 else None)
    ).lower(qk, qk, v, bias).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the branch and the tile, where a trace and the ledger's `device_ops` show them
    assert f"/{name}/pallas_call" in text


@pytest.mark.parametrize("width,weights", [(2048, (16, 2048, 2048)), (1024, (16, 1024, 2048))])
def test_grouped_expert_product_compiles_for_the_v5e(one_chip, monkeypatch, width, weights):
    """`parallel/moe.py`'s grouped product at `trinitym-train-8k`'s widths, forward and
    backward: 65,536 rows (8,192 tokens x 8 choices) against the gate-and-up weights of 16
    held experts, and against their down weights. A tile that asks more VMEM than the
    kernel may use is refused here (512 x 2048 x 1024 was, on the chip)."""
    from kubeflow_tpu.parallel import moe

    rows = jax.ShapeDtypeStruct((65536, width), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct(weights, jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(rows, w, sizes):
        return moe.grouped_matmul(rows, w, sizes).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(rows, w, sizes).compile().as_text()
    assert text.count("tpu_custom_call") >= 3  # gmm forward, gmm for the rows' gradient, tgmm


@pytest.mark.parametrize("shape,mask,name", [
    ((8, 1024, 16, 64), Causal(), "flash_bwd_xla_q128_k256_live20of32"),  # gpt2m-train-1k
    # trinitym-train-8k: a sliding and a full layer
    ((1, 8192, 32, 128), Causal(2048), "flash_bwd_xla_q512_k512_live70of256"),
    ((1, 8192, 32, 128), Causal(), "flash_bwd_xla_q512_k512_live136of256"),
    ((8, 512, 12, 64), None, "flash_bwd_xla_q512_k128_live4of4"),  # BERT-base: nothing to skip
    # sdar30b-train-4k: 36 clean-clean + 36 noisy-clean + 8 noisy-noisy pairs
    ((1, 8192, 32, 128), BlockDiffusion(4096, 4), "flash_bwd_xla_q512_k512_live80of256_blockdiff"),
    # dsv2lite-train-8k: dq and dk at 192, dv and the cotangent at 128
    ((1, 8192, 16, 192, 128), Causal(), "flash_bwd_xla_q512_k512_live136of256_d192v128"),
])
def test_xla_backward_compiles_for_the_v5e_under_its_scope(one_chip, shape, mask, name):
    """The shipped backward (XLA's, not a kernel) at the rule's blocks: the scope a trace
    shows, and temporaries of a few score tiles, not of the square."""
    (b, length, h, d), v_shape = _shapes(shape)
    qk = jax.ShapeDtypeStruct(shape[:4], jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct(v_shape, jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((b, 1, 1, length), jnp.float32, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((b * h, length, 1), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v, bias, o, lse, g: ra._flash_backward(
        q, k, v, bias, o, lse, g, 128, 128, mask, impl="xla", scale=MLA_SCALE if len(shape) == 5 else None)
    ).lower(qk, qk, v, bias, v, lse, v).compile()
    assert f"/{name}/" in compiled.as_text()
    block_q, block_k = ra.flash_backward_xla_blocks(length, length, 128, 128, mask)
    tile = b * h * block_q * block_k * 4
    operands = 7 * b * h * length * d * 4  # q, k, v, dO, and dq, dk, dv in float32
    assert compiled.memory_analysis().temp_size_in_bytes <= operands + 6 * tile


def test_a_mamba2_block_compiles_for_the_v5e_within_its_temporaries(one_chip, monkeypatch):
    """`granite4h-train-8k`'s Mamba-2 block (`models/granite_hybrid.py` under `nn.remat`, the
    mixer of `parallel/ssm.py`) forward and backward at 8,192 positions and the published
    widths (64 heads of 64, state 128, chunks of 256): the five scopes are in the program,
    the scan's four kernels (each chunk's own state and each chunk's output, forward and
    backward) compile under its scope, `train_ssm.kind_of` finds the kernels' forward, the
    forward `remat` runs again and the backward, and the temporaries stay under 2 GB
    (1.39 GB: no chunk's (Q, Q) decay or mix reaches HBM)."""
    import flax.linen as nn

    from benchmarks.layer_metrics import train_ssm
    from kubeflow_tpu.models.granite_hybrid import MAMBA, GraniteHybridBlock, GraniteHybridConfig

    class Stage(nn.Module):  # one layer, named as the model names its layers
        @nn.compact
        def __call__(self, x, train):
            return nn.remat(GraniteHybridBlock, static_argnums=(2,), policy=ra.FLASH_REMAT_POLICY)(
                GraniteHybridConfig(num_layers=10, dtype=jnp.bfloat16, remat=True), MAMBA,
                name="layer_0")(x, train)

    block = Stage()
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                          jax.eval_shape(lambda k, x: block.init(k, x, False), jax.random.PRNGKey(0), x))

    def loss(p, x):
        y, _ = block.apply(p, x, True, mutable=["ssm_state"])
        return (y.astype(jnp.float32) ** 2).mean()

    def _train_step(p, x):  # the step's name, as `train_ssm` reads a trace's
        return jax.value_and_grad(loss)(p, x)

    # the kernels ask the backend whether to interpret; here the CPU answers
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(_train_step).lower(params, x).compile()
    text = compiled.as_text()
    assert all(f"/mamba/{scope}/" in text for scope in
               ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj"))
    kernels = set(re.findall(r'op_name="([^"]*/ssd_\w+_q256_h8/pallas_call)"', text))
    assert {n.rsplit("/", 2)[1] for n in kernels} == {
        f"ssd_{part}_{way}_q256_h8" for part in ("state", "chunk") for way in ("fwd", "bwd")}
    assert all("/mamba/ssm.scan/" in n for n in kernels)
    assert {train_ssm.kind_of(n) for n in kernels} == set(train_ssm.SCAN_KINDS)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
