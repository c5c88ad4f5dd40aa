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

`ssd_chunked` computes the recurrence in chunks of Q positions (the
state-space duality's block form), every chunk at once. With `cum` the running
sum of `D_t A` within a chunk, position t of chunk k reads

    y_t = sum_{s<=t in k} exp(cum_t - cum_s) (C_t . B_s) D_s x_s + exp(cum_t) C_t . S_{k-1}
    S_k = exp(cum_end) S_{k-1} + sum_{s in k} exp(cum_end - cum_s) D_s x_s B_s^T

Four pallas kernels and a little XLA, each over (chunk, block of HEAD_BLOCK
heads) where it is a kernel:

  XLA          the steps' running sums `cum` and each chunk's decay
               `exp(cum_end)`, batched over the chunks;
  ssd_state    each chunk's own state, the sum in `S_k`
               (`ssd_state_fwd_q{Q}_h{heads}`);
  XLA          the carried state, the one sequential part: an elementwise
               `lax.scan` of c steps over (H, P, N) in float32;
  ssd_chunk    each chunk's output (`ssd_chunk_fwd_q{Q}_h{heads}`): `C B^T` once a
               program, then for each head the (Q, Q) decay and mix built in VMEM
               on the (t, s) tiles on and below the diagonal (WALK_TILE), their
               product with x, and the read of the carried state.

Each kernel's backward is a kernel of its own (`..._bwd_...`, `jax.custom_vjp`)
that keeps the inputs alone and builds what it needs again in VMEM, summing a
group's `dB` and `dC` over its blocks of heads. No (Q, Q) tensor reaches HBM,
and the custom backward takes the place of a recomputation: two forwards (the
step's and the block's `remat`) and one backward a step. Decays and exponents
are float32, the products' operands are the compute dtype with float32
accumulation, the carried state is float32.

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

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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


#: the walk within a chunk: (t, s) tiles of this many positions where the chunk is a
#: multiple of it, else the whole chunk as one tile
WALK_TILE = 128
#: heads a kernel's program takes, where they divide a group's
HEAD_BLOCK = 8
#: dot_general's dimension numbers of `a b^T` and of `a^T b`
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _walk(q: int):
    """(rows of t, rows of s, whether the diagonal crosses the tile) for every tile on and
    below the diagonal of a chunk of `q`, a row of t at a time, its diagonal tile last."""
    tile = WALK_TILE if q % WALK_TILE == 0 else q
    spans = [slice(i * tile, (i + 1) * tile) for i in range(q // tile)]
    return [(spans[i], spans[j], i == j) for i in range(len(spans)) for j in range(i + 1)]


def _decay(cum_t, cum_s, diagonal: bool):
    """`exp(cum_t - cum_s)` on a tile from a (T, 1) column and a (1, T) row; on a tile the
    diagonal crosses, 0 where s > t."""
    d = cum_t - cum_s
    if diagonal:
        t = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
        s = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        d = jnp.where(s <= t, d, -jnp.inf)
    return jnp.exp(d)


def _add(into: dict, key, value):
    into[key] = value if key not in into else into[key] + value


def _chunk_fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, s_ref, y_ref):
    """One (chunk, block of heads) of `ssd_chunk`, a head at a time."""
    heads, q = dt_ref.shape[2:]
    hp = x_ref.shape[2] // heads
    dtype = x_ref.dtype
    cb = jax.lax.dot_general(c_ref[0, 0], b_ref[0, 0], _NT, preferred_element_type=jnp.float32)
    cum, dt = cum_ref[0, 0], dt_ref[0, 0]                 # (heads, Q)
    cum_col = cum.T                                       # (Q, heads)
    for h in range(heads):
        lanes = slice(h * hp, (h + 1) * hp)
        carried = jax.lax.dot_general(c_ref[0, 0], s_ref[0, 0, h].astype(dtype), _NT,
                                      preferred_element_type=jnp.float32)    # (Q, P)
        acc = {}
        for t, s, diagonal in _walk(q):
            decay = _decay(cum_col[t, h:h + 1], cum[h:h + 1, s], diagonal)
            mix = decay * cb[t, s] * dt[h:h + 1, s]
            _add(acc, t.start, jnp.dot(mix.astype(dtype), x_ref[0, s, lanes],
                                       preferred_element_type=jnp.float32))
            if diagonal:
                y_ref[0, t, lanes] = acc[t.start] + carried[t] * jnp.exp(cum_col[t, h:h + 1])


def _chunk_bwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, s_ref, dy_ref,
                      dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, ds_ref, dcb_ref, dc_acc_ref,
                      *, per_group: int):
    """The backward of one (chunk, block of heads), a head at a time: decay, mix and the
    chunk's output built again from the inputs. With `dmix = dy_t . x_s`: `dx = mix^T dy`;
    `d dt_s` the column sums of `dmix * decay * CB`; `d cum` the row sums of `dmix * mix`
    less their column sums, taken as `dy_t . y_t - x_s . dx_s` (the same products summed
    two ways, so the two sides cancel where they should) and the carried read's
    `dy_t . y_off_t`; `d S` a head; `d(C B^T)` and the carried read's `dC` summed over the
    group's heads in VMEM, which the group's last block turns into `dB` and `dC`."""
    heads, q = dt_ref.shape[2:]
    hp = x_ref.shape[2] // heads
    dtype = x_ref.dtype
    block = pl.program_id(1)

    @pl.when(block * heads % per_group == 0)
    def _():
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        dc_acc_ref[...] = jnp.zeros_like(dc_acc_ref)

    c = c_ref[0, 0]
    cb = jax.lax.dot_general(c, b_ref[0, 0], _NT, preferred_element_type=jnp.float32)
    cum, dt = cum_ref[0, 0], dt_ref[0, 0]
    cum_col = cum.T
    by_t = []                                             # each head's d cum, a column
    for h in range(heads):
        lanes = slice(h * hp, (h + 1) * hp)
        dy_f32 = dy_ref[0, :, lanes]                      # (Q, P)
        # the carried read, exp(cum_t) C_t . S
        s_h = s_ref[0, 0, h].astype(dtype)
        grow = jnp.exp(cum_col[:, h:h + 1])
        y = jax.lax.dot_general(c, s_h, _NT, preferred_element_type=jnp.float32) * grow
        dcarried = (dy_f32 * grow).astype(dtype)
        ds_ref[0, 0, h] = jax.lax.dot_general(dcarried, c, _TN, preferred_element_type=jnp.float32
                                              ).astype(ds_ref.dtype)
        dc_acc_ref[...] += jnp.dot(dcarried, s_h, preferred_element_type=jnp.float32)
        # the diagonal block
        dy = dy_f32.astype(dtype)
        dx, y_diag, ddt = {}, {}, {}
        for t, s, diagonal in _walk(q):
            decay = _decay(cum_col[t, h:h + 1], cum[h:h + 1, s], diagonal)
            decay_cb = decay * cb[t, s]
            mix = (decay_cb * dt[h:h + 1, s]).astype(dtype)
            x_s = x_ref[0, s, lanes]
            dmix = jax.lax.dot_general(dy[t], x_s, _NT, preferred_element_type=jnp.float32)
            _add(dx, s.start, jax.lax.dot_general(mix, dy[t], _TN,
                                                  preferred_element_type=jnp.float32))
            _add(y_diag, t.start, jnp.dot(mix, x_s, preferred_element_type=jnp.float32))
            _add(ddt, s.start, (dmix * decay_cb).sum(0, keepdims=True))
            dcb_ref[t, s] += dmix * decay * dt[h:h + 1, s]
        starts = sorted(dx)
        dx_h = jnp.concatenate([dx[k] for k in starts], 0)
        dx_ref[0, :, lanes] = dx_h.astype(dx_ref.dtype)
        ddt_ref[0, 0, h:h + 1, :] = jnp.concatenate([ddt[k] for k in starts], 1)
        y_diag = jnp.concatenate([y_diag[k] for k in starts], 0)
        by_t.append((dy_f32 * y + dy.astype(jnp.float32) * y_diag
                     - x_ref[0, :, lanes].astype(jnp.float32) * dx_h).sum(1, keepdims=True))
    dcum_ref[0, 0] = jnp.concatenate(by_t, 1).T

    @pl.when((block + 1) * heads % per_group == 0)
    def _():
        dcb = dcb_ref[...].astype(dtype)
        dc_ref[0, 0] = (jnp.dot(dcb, b_ref[0, 0], preferred_element_type=jnp.float32)
                        + dc_acc_ref[...]).astype(dc_ref.dtype)
        db_ref[0, 0] = jax.lax.dot_general(dcb, c, _TN, preferred_element_type=jnp.float32
                                           ).astype(db_ref.dtype)


def _specs(x, dt, b):
    """The kernels' grid, block specs and name: x-like (Bt, L, H P), step-like (Bt, c, H,
    Q), B-like (Bt, G, L, N), state-like (Bt, c, H, P, N). A program takes one chunk of one
    row and a block of heads inside one group."""
    bt, chunks, heads, q = dt.shape
    hp, n = x.shape[2] // heads, b.shape[3]
    per_group = heads // b.shape[1]
    block = math.gcd(per_group, HEAD_BLOCK)
    row = lambda i, j: (i // chunks, i % chunks, j)            # noqa: E731
    specs = {
        "x": pl.BlockSpec((1, q, block * hp), row),
        "step": pl.BlockSpec((1, 1, block, q), lambda i, j: (*row(i, j), 0)),
        "b": pl.BlockSpec((1, 1, q, n), lambda i, j: (i // chunks, j * block // per_group,
                                                      i % chunks, 0)),
        "state": pl.BlockSpec((1, 1, block, hp, n), lambda i, j: (*row(i, j), 0, 0)),
    }
    common = dict(grid=(bt * chunks, heads // block), interpret=jax.default_backend() == "cpu")
    return specs, common, f"q{q}_h{block}", per_group


def _state_fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, own_ref):
    """One (chunk, block of heads) of `ssd_state`."""
    heads, q = dt_ref.shape[2:]
    hp = x_ref.shape[2] // heads
    cum = cum_ref[0, 0]
    to_end = (jnp.exp(cum[:, q - 1:q] - cum) * dt_ref[0, 0]).T        # (Q, heads)
    for h in range(heads):
        weighted = (x_ref[0, :, h * hp:(h + 1) * hp].astype(jnp.float32) * to_end[:, h:h + 1]
                    ).astype(x_ref.dtype)
        own_ref[0, 0, h] = jax.lax.dot_general(weighted, b_ref[0, 0], _TN,
                                               preferred_element_type=jnp.float32)


def _state_bwd_kernel(x_ref, dt_ref, cum_ref, b_ref, down_ref,
                      dx_ref, ddt_ref, dcum_ref, db_ref, db_acc_ref, *, per_group: int):
    """The backward of one (chunk, block of heads) of `ssd_state`: `dx`, `d dt` and `d cum`
    a head, and `dB` summed over the group's heads in VMEM."""
    heads, q = dt_ref.shape[2:]
    hp = x_ref.shape[2] // heads
    dtype = x_ref.dtype
    block = pl.program_id(1)

    @pl.when(block * heads % per_group == 0)
    def _():
        db_acc_ref[...] = jnp.zeros_like(db_acc_ref)

    cum = cum_ref[0, 0]
    decay = jnp.exp(cum[:, q - 1:q] - cum)                # (heads, Q)
    to_end = decay * dt_ref[0, 0]
    to_end_col = to_end.T
    d_to_end = []
    for h in range(heads):
        x = x_ref[0, :, h * hp:(h + 1) * hp].astype(jnp.float32)
        down = down_ref[0, 0, h].astype(dtype)            # (P, N)
        dw = jax.lax.dot_general(b_ref[0, 0], down, _NT, preferred_element_type=jnp.float32)
        dx_ref[0, :, h * hp:(h + 1) * hp] = (dw * to_end_col[:, h:h + 1]).astype(dx_ref.dtype)
        d_to_end.append((dw * x).sum(1, keepdims=True))
        weighted = (x * to_end_col[:, h:h + 1]).astype(dtype)
        db_acc_ref[...] += jnp.dot(weighted, down, preferred_element_type=jnp.float32)
    d_to_end = jnp.concatenate(d_to_end, 1).T            # (heads, Q)
    ddt_ref[0, 0] = d_to_end * decay
    # to_end = exp(cum_end - cum_s) dt_s: -g at s, the sum of g at the chunk's end
    g = d_to_end * to_end
    end = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) == q - 1
    dcum_ref[0, 0] = jnp.where(end, g.sum(1, keepdims=True), 0.0) - g

    @pl.when((block + 1) * heads % per_group == 0)
    def _():
        db_ref[0, 0] = db_acc_ref[...].astype(db_ref.dtype)


# The four kernels' wrappers are jitted, so that a step traces and lowers each kernel
# once and not once a layer.
@jax.jit
def _state_fwd(x, dt, cum, b):
    specs, common, tiles, _ = _specs(x, dt, b)
    bt, chunks, heads, _ = dt.shape
    return pl.pallas_call(
        _state_fwd_kernel,
        in_specs=[specs[k] for k in ("x", "step", "step", "b")], out_specs=specs["state"],
        out_shape=jax.ShapeDtypeStruct((bt, chunks, heads, x.shape[2] // heads, b.shape[3]),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name=f"ssd_state_fwd_{tiles}", **common,
    )(x, dt, cum, b)


@jax.jit
def _state_bwd(residuals, down):
    x, dt, cum, b = residuals
    specs, common, tiles, per_group = _specs(x, dt, b)
    q, n = dt.shape[3], b.shape[3]
    return pl.pallas_call(
        functools.partial(_state_bwd_kernel, per_group=per_group),
        in_specs=[specs[k] for k in ("x", "step", "step", "b", "state")],
        out_specs=[specs[k] for k in ("x", "step", "step", "b")],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v in residuals],
        scratch_shapes=[pltpu.VMEM((q, n), jnp.float32)],
        # a group's blocks of heads, in turn, sum its dB in the scratch
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name=f"ssd_state_bwd_{tiles}", **common,
    )(x, dt, cum, b, down)


@jax.custom_vjp
def ssd_state(x, dt, cum, b):
    """Each chunk's own part of the state at its end, `sum_s exp(cum_end - cum_s) dt_s x_s
    B_s^T`: x (Bt, L, H P) and b (Bt, G, L, N) in the compute dtype, dt and cum (Bt, c, H,
    Q) float32 -> (Bt, c, H, P, N) float32. A pallas kernel each way; the backward keeps
    the inputs alone."""
    return _state_fwd(x, dt, cum, b)


ssd_state.defvjp(lambda *args: (_state_fwd(*args), args), _state_bwd)


@jax.jit
def _chunk_fwd(x, dt, cum, b, c, state):
    specs, common, tiles, _ = _specs(x, dt, b)
    return pl.pallas_call(
        _chunk_fwd_kernel,
        in_specs=[specs[k] for k in ("x", "step", "step", "b", "b", "state")],
        out_specs=specs["x"], out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name=f"ssd_chunk_fwd_{tiles}", **common,
    )(x, dt, cum, b, c, state)


@jax.jit
def _chunk_bwd(residuals, dy):
    x, dt, cum, b, c, state = residuals
    specs, common, tiles, per_group = _specs(x, dt, b)
    q, n = dt.shape[3], state.shape[4]
    return pl.pallas_call(
        functools.partial(_chunk_bwd_kernel, per_group=per_group),
        in_specs=[specs[k] for k in ("x", "step", "step", "b", "b", "state", "x")],
        out_specs=[specs[k] for k in ("x", "step", "step", "b", "b", "state")],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v in residuals],
        scratch_shapes=[pltpu.VMEM((q, q), jnp.float32), pltpu.VMEM((q, n), jnp.float32)],
        # a group's blocks of heads, in turn, sum its dB and dC in the scratch
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name=f"ssd_chunk_bwd_{tiles}", **common,
    )(x, dt, cum, b, c, state, dy)


@jax.custom_vjp
def ssd_chunk(x, dt, cum, b, c, state):
    """Every chunk's output, `y[t] = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s +
    exp(cum_t) C_t . S`, from its own inputs and the state S it starts from: x (Bt, L, H P),
    b and c (Bt, G, L, N) in the compute dtype, dt and cum (Bt, c, H, Q) and the state (Bt,
    c, H, P, N) float32 -> (Bt, L, H P) float32. Forward and backward are pallas kernels that
    hold a chunk's (Q, Q) decay, `C B^T` and mix in VMEM; the backward keeps the inputs
    alone and builds them again."""
    return _chunk_fwd(x, dt, cum, b, c, state)


ssd_chunk.defvjp(lambda *args: (_chunk_fwd(*args), args), _chunk_bwd)


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The recurrence above in chunks of `chunk` positions.

    x (Bt, L, H, P) in the compute dtype; dt (Bt, L, H) float32, the step
    after softplus; a (H,) float32, negative; b and c (Bt, L, G, N).
    Returns (y (Bt, L, H, P) float32 without the `D x` term, the chunks'
    decays `exp(sum of dt a over the chunk)`, (Bt, chunks, H) float32). The
    chunk, or the row where it is shorter than the chunk, tiles the row."""
    bt, length, heads, hp = x.shape
    n = b.shape[-1]
    q = min(chunk, length)
    if length % q:
        raise ValueError(f"{length} positions are no multiple of the chunk {q}")
    chunks = length // q

    # the steps and their running sums within each chunk, (Bt, c, H, Q)
    steps = dt.reshape(bt, chunks, q, heads).swapaxes(2, 3)
    cum = jnp.cumsum(steps * a[:, None], axis=-1)
    kept = jnp.exp(cum[..., -1])                          # (Bt, c, H)
    x = x.reshape(bt, length, heads * hp)
    b, c = (v.astype(x.dtype).swapaxes(1, 2) for v in (b, c))   # (Bt, G, L, N)
    own = ssd_state(x, steps, cum, b)                     # (Bt, c, H, P, N)

    # the state each chunk starts from: S_k = kept_k S_{k-1} + own_k, in float32
    def carry(state, inputs):
        kept_k, own_k = inputs
        return state * kept_k[..., None, None] + own_k, state

    _, before = jax.lax.scan(carry, jnp.zeros((bt, heads, hp, n), jnp.float32),
                             (kept.swapaxes(0, 1), own.swapaxes(0, 1)))
    y = ssd_chunk(x, steps, cum, b, c, before.swapaxes(0, 1))
    return y.reshape(bt, length, heads, hp), kept


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
