"""State-space layers: the Mamba-2 mixer (Dao & Gu, "Transformers are SSMs",
arXiv:2405.21060), its chunked scan, causal depthwise convolution and gated
norm, on the training path.

For one row of `L` positions, with `H` heads of `P`, a state of `N` and `G`
groups of heads that share their `B` and `C`:

    [z | xBC | dt] = u W_in                       (H P | H P + 2 G N | H)
    xBC = silu(conv_causal(xBC; w, b))            position t sees t-K+1 .. t
    [x | B | C] = xBC
    D_t = softplus(dt_t + dt_bias),  A = -exp(A_log)
    S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T      (S: P x N a head, S_0 = 0)
    y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z); g over all H P in each of G groups) W_o

`ssd_chunked` computes the recurrence in chunks of `chunk` positions (the
state-space duality's block form): within a chunk the outputs are products
under the segment-sum decay `exp(cum_t - cum_s)`, s <= t, of the chunk's own
inputs; across chunks the state at each chunk's end is carried, and every
position reads the carried state through `exp(cum_t)`. One `lax.scan` walks
the chunks, so a chunk's `(H, Q, Q)` decay is the largest temporary; the
backward is the scan's own, its body checkpointed: the scan keeps the carried
states alone and runs each chunk's body again (at 8,192 positions and the
published widths a block's temporaries are 1.27 GB so, 4.09 GB when every
chunk's decay and products are kept). Decays and exponents are float32, the
products' operands are the compute dtype with float32 accumulation.

Device scopes, one per stage, so a trace splits the mixer's time:
`ssm.in_proj`, `ssm.conv`, `ssm.scan`, `ssm.gate_norm`, `ssm.out_proj`. The
step counter `ssm_chunk_decay` is kept in the collection SSM_STATE, as the
routers' are (`parallel/moe.py`): the mean over layers, heads and chunks of
`exp(sum over a chunk of D_t A)`, the share of a state that survives one
chunk (`ssm_counters`).

Training only: a serving cache that keeps the state and the convolution's
last K-1 inputs beside keys and values is ROADMAP M4, and a row is one
stream (no reset of the state or the convolution inside it).
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel.mesh import AXIS_FSDP

SSM_STATE = "ssm_state"
#: the range of the seeded step, softplus(dt_bias), log-uniform (Mamba2Mixer)
STEP_INIT = (1e-4, 1e-2)

#: the mixer's projections split over `fsdp` on the hidden side; the conv,
#: the per-head scalars and the gated norm's gain are small and stay whole
SSM_PARTITION_RULES: list[tuple[str, P]] = [
    (r"mamba/in_proj/kernel$", P(AXIS_FSDP, None)),
    (r"mamba/out_proj/kernel$", P(None, AXIS_FSDP)),
]


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal convolution: x (B, L, C), w (C, K), b (C,) ->
    `y_t = b + sum_k w[:, k] x_{t-K+1+k}`, zeros before the row's first
    position. Summed in float32, returned in x's dtype."""
    k = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    length = x.shape[1]
    y = b.astype(jnp.float32) + sum(
        xp[:, i:i + length] * w[:, i].astype(jnp.float32) for i in range(k))
    return y.astype(x.dtype)


def gated_rms_norm(y: jax.Array, z: jax.Array, gain: jax.Array, groups: int,
                   eps: float) -> jax.Array:
    """`RMSNorm(y * silu(z))` over each of `groups` equal parts of the last
    axis, times `gain`: the gate before the norm. Float32 inside."""
    h = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = h.reshape(*h.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(h.shape) * gain.astype(jnp.float32)


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The recurrence above in chunks of `chunk` positions.

    x (Bt, L, H, P) in the compute dtype; dt (Bt, L, H) float32, the step
    after softplus; a (H,) float32, negative; b and c (Bt, L, G, N).
    Returns (y (Bt, L, H, P) float32 without the `D x` term, the chunks'
    decays `exp(sum of dt a over the chunk)`, (Bt, chunks, H) float32). The
    chunk, or the row where it is shorter than the chunk, tiles the row."""
    bt, length, heads, hp = x.shape
    groups, n = b.shape[-2:]
    per_group = heads // groups
    q = min(chunk, length)
    if length % q:
        raise ValueError(f"{length} positions are no multiple of the chunk {q}")
    chunks = length // q
    dtype = x.dtype

    def by_chunk(v):  # (Bt, L, ...) -> (chunks, Bt, Q, ...)
        return v.reshape(bt, chunks, q, *v.shape[2:]).swapaxes(0, 1)

    da = by_chunk(dt * a).transpose(0, 1, 3, 2)          # (chunks, Bt, H, Q)
    seen = jnp.tril(jnp.ones((q, q), bool))               # (t, s): s <= t

    @jax.checkpoint
    def one_chunk(state, inputs):
        xq, daq, dtq, bq, cq = inputs                     # state (Bt, H, P, N) f32
        cum = jnp.cumsum(daq, axis=-1)                    # (Bt, H, Q)
        # within the chunk: C_t . B_s exp(cum_t - cum_s) dt_s, s <= t
        decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->bgts", cq, bq, preferred_element_type=jnp.float32)
        mix = (decay.reshape(bt, groups, per_group, q, q) * cb[:, :, None]
               * dtq.transpose(0, 2, 1).reshape(bt, groups, per_group, 1, q))
        xg = xq.reshape(bt, q, groups, per_group, hp)
        y = jnp.einsum("bgkts,bsgkp->btgkp", mix.astype(dtype), xg,
                       preferred_element_type=jnp.float32)
        # the carried state, read at every position of the chunk
        sg = state.reshape(bt, groups, per_group, hp, n).astype(dtype)
        carried = jnp.einsum("btgn,bgkpn->btgkp", cq, sg, preferred_element_type=jnp.float32)
        y = y + carried * jnp.exp(cum).transpose(0, 2, 1).reshape(bt, q, groups, per_group, 1)
        # the chunk's own part of the state at its end
        to_end = (jnp.exp(cum[..., -1:] - cum) * dtq.transpose(0, 2, 1))  # (Bt, H, Q)
        weighted = (xg * to_end.transpose(0, 2, 1).reshape(bt, q, groups, per_group, 1)
                    ).astype(dtype)
        own = jnp.einsum("bsgkp,bsgn->bgkpn", weighted, bq, preferred_element_type=jnp.float32)
        kept = jnp.exp(cum[..., -1])                      # (Bt, H)
        state = state * kept[..., None, None] + own.reshape(bt, heads, hp, n)
        return state, (y.reshape(bt, q, heads, hp), kept)

    state0 = jnp.zeros((bt, heads, hp, n), jnp.float32)
    _, (y, kept) = jax.lax.scan(
        one_chunk, state0,
        (by_chunk(x), da, by_chunk(dt), by_chunk(b.astype(dtype)), by_chunk(c.astype(dtype))))
    y = y.swapaxes(0, 1).reshape(bt, length, heads, hp)
    return y, kept.swapaxes(0, 1)


def _uniform(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, lo, hi)
    return init


def _log_uniform_step(lo: float, hi: float):
    """`dt_bias` such that softplus(dt_bias) is log-uniform on [lo, hi]: the
    inverse of softplus, `y + log(-expm1(-y))`, of such a draw."""
    def init(key, shape, dtype=jnp.float32):
        y = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(lo), math.log(hi)))
        return (y + jnp.log(-jnp.expm1(-y))).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    """u (B, L, hidden) -> (B, L, hidden): the equations in the module's
    docstring. Parameters: `in_proj` and `out_proj` (no bias), `conv_weight`
    (C, K) and `conv_bias` over the C = H P + 2 G N channels of xBC, `A_log`,
    `D` and `dt_bias` a head, the gated norm's `norm_gain` over H P.

    Seeded as Mamba-2 is, `A_log = log U[1, 16]` and `D = 1`, the
    convolution as a depthwise `Conv1d` of width K is, U(-1/sqrt(K),
    1/sqrt(K)) for weight and bias; but `softplus(dt_bias)` log-uniform on
    [1e-4, 1e-2] (STEP_INIT), a decade below Mamba-2's [1e-3, 1e-1]. At
    theirs nearly every head forgets a chunk of 256 whole (the state's mean
    share that survives one, `ssm_chunk_decay`, about 1.4 %), so the carried
    state is nothing a comparison of the first step can see; a decade lower
    keeps the same two decades of memory lengths, shifted to span chunks
    (about 21 %)."""

    hidden_size: int
    num_heads: int
    head_dim: int
    state_size: int
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 256
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jax.Array, train: bool = False) -> jax.Array:
        heads, hp, n, g = self.num_heads, self.head_dim, self.state_size, self.n_groups
        if heads % g:
            raise ValueError(f"{g} groups do not divide {heads} heads")
        inner = heads * hp
        channels = inner + 2 * g * n
        bt, length, _ = u.shape
        with jax.named_scope("ssm.in_proj"):
            zxbcdt = nn.Dense(inner + channels + heads, use_bias=False, dtype=self.dtype,
                              name="in_proj")(u)
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + channels], axis=-1)
        with jax.named_scope("ssm.conv"):
            bound = 1.0 / math.sqrt(self.conv_kernel)
            w = self.param("conv_weight", _uniform(-bound, bound), (channels, self.conv_kernel))
            cb = self.param("conv_bias", _uniform(-bound, bound), (channels,))
            xbc = nn.silu(causal_conv1d(xbc, w, cb))
            x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        a_log = self.param("A_log", lambda k, s: jnp.log(_uniform(1.0, 16.0)(k, s)), (heads,))
        dt_bias = self.param("dt_bias", _log_uniform_step(*STEP_INIT), (heads,))
        d = self.param("D", nn.initializers.ones, (heads,))
        with jax.named_scope("ssm.scan"):
            step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            x = x.reshape(bt, length, heads, hp)
            y, kept = ssd_chunked(x, step, -jnp.exp(a_log), b.reshape(bt, length, g, n),
                                  c.reshape(bt, length, g, n), self.chunk_size)
            y = y + x.astype(jnp.float32) * d[:, None]
        with jax.named_scope("ssm.gate_norm"):
            gain = self.param("norm_gain", nn.initializers.ones, (inner,))
            y = gated_rms_norm(y.reshape(bt, length, inner), z, gain, g, self.norm_eps)
        with jax.named_scope("ssm.out_proj"):
            out = nn.Dense(self.hidden_size, use_bias=False, dtype=self.dtype,
                           name="out_proj")(y.astype(self.dtype))
        decay = self.variable(SSM_STATE, "chunk_decay", lambda: jnp.zeros((), jnp.float32))
        if train and self.is_mutable_collection(SSM_STATE) and not self.is_initializing():
            decay.value = jax.lax.stop_gradient(kept.mean())
        return out


def ssm_counters(ssm_state) -> dict[str, jax.Array]:
    """The step's `ssm_chunk_decay`: the mean over the mixers of what each
    kept in SSM_STATE (its mean over heads and chunks of the chunk's decay)."""
    from flax.traverse_util import flatten_dict

    kept = [v for path, v in flatten_dict(ssm_state).items() if path[-1] == "chunk_decay"]
    return {"ssm_chunk_decay": sum(kept) / len(kept)} if kept else {}
