"""Bandwidth-critical value gather + interpolation (Pallas TPU).

Computes  out[t] = sum_k w[t,k] * values[idx[t,k]]  — the random-access read
the paper implements as a CUDA gather.

The table stays in HBM (`memory_space=pl.ANY`) and each grid step gathers
the rows of `_TOKENS_PER_STEP` tokens with one async DMA per (token, k)
into a VMEM scratch, then accumulates the weighted rows.  The TPU's DMA
engine moves whole 128-lane tiles: a (1, 64) f32 row, or any single row
of a 1-byte table, is not a slice the chip's compiler accepts.  So the
wrapper views the table as lane-dense 32-bit *groups* of 128 words — each
group holds `128 / words_per_row` consecutive rows — and the kernel DMAs
the group that holds the wanted row.  A lane mask keeps only that row's
words, so each output row accumulates in its own lane segment; the
wrapper folds the segments (and, for 2- and 1-byte tables, the byte
planes unpacked in VMEM) back into (..., m).

The indices and weights ride SMEM blocks of one grid step each, so their
size does not bound the number of tokens a call can take.  Out-of-range
indices are clamped to the table, as `jnp.take` does; a DMA must never
leave the array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lookup
from repro.kernels import interpret_mode, scatter_add

_LANES = 128
_TOKENS_PER_STEP = 8  # tokens per grid step (the output block's sublanes)


def _decode_plane(word, plane: int, dtype) -> jax.Array:
    """fp32 values of byte/half plane `plane` of packed 32-bit words."""
    if dtype == jnp.bfloat16:
        bits = word << 16 if plane == 0 else word & jnp.int32(-65536)
        return pltpu.bitcast(bits, jnp.float32)
    if dtype == jnp.int8:  # sign-extend byte `plane`
        return ((word << (24 - 8 * plane)) >> 24).astype(jnp.float32)
    # float8_e4m3fn: s eeee mmm, bias 7 (the quantizer never stores NaN)
    u = (word >> (8 * plane)) & 0xFF
    sign = (u >> 7) << 31
    e = (u >> 3) & 0xF
    mant = u & 7
    normal = pltpu.bitcast(sign | ((e + 120) << 23) | (mant << 20),
                           jnp.float32)
    sub = mant.astype(jnp.float32) * (2.0 ** -9)
    return jnp.where(e == 0, jnp.where(sign != 0, -sub, sub), normal)


def _kernel(idx_ref, w_ref, table_ref, out_ref, groups, sem, *,
            top_k: int, words_per_row: int, dtype):
    rows_per_group = _LANES // words_per_row
    n = _TOKENS_PER_STEP * top_k

    def start(j, carry):
        g = idx_ref[j // top_k, j % top_k] // rows_per_group
        pltpu.make_async_copy(
            table_ref.at[pl.ds(g, 1)], groups.at[pl.ds(j, 1)], sem.at[0]
        ).start()
        return carry

    def wait(j, carry):
        pltpu.make_async_copy(
            table_ref.at[pl.ds(0, 1)], groups.at[pl.ds(0, 1)], sem.at[0]
        ).wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)

    planes = 4 // jnp.dtype(dtype).itemsize
    segment = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) \
        // words_per_row

    for t in range(_TOKENS_PER_STEP):
        def row(k, acc, t=t):
            # the weight lands only on the lanes of the wanted row
            wv = jnp.where(segment == idx_ref[t, k] % rows_per_group,
                           w_ref[t, k], 0.0)
            group = groups[pl.ds(t * top_k + k, 1), :]
            if planes == 1:
                return (acc[0] + wv * group,)
            return tuple(a + wv * _decode_plane(group, b, dtype)
                         for b, a in enumerate(acc))

        zeros = tuple(jnp.zeros((1, _LANES), jnp.float32)
                      for _ in range(planes))
        acc = jax.lax.fori_loop(0, top_k, row, zeros)
        for b in range(planes):
            out_ref[t:t + 1, b * _LANES:(b + 1) * _LANES] = acc[b]


def _lane_view(values: jax.Array) -> tuple[jax.Array, int]:
    """(N, m) table -> (lane-dense (groups, 128) 32-bit view, words/row)."""
    num_rows, m = values.shape
    itemsize = values.dtype.itemsize
    if values.dtype not in (jnp.float32, jnp.bfloat16, jnp.int8,
                            jnp.float8_e4m3fn):
        raise TypeError(f"unsupported table dtype {values.dtype}")
    if (m * itemsize) % 4 or _LANES % (m * itemsize // 4):
        raise ValueError(
            f"a row of {m} x {values.dtype} must pack into a power-of-two "
            f"number of 32-bit words <= {_LANES}"
        )
    words = m * itemsize // 4
    if itemsize == 4:
        view = values
    else:
        view = jax.lax.bitcast_convert_type(
            values.reshape(num_rows, words, 4 // itemsize), jnp.int32
        )
    rows_per_group = _LANES // words
    pad = -num_rows % rows_per_group
    if pad:
        view = jnp.pad(view, ((0, pad), (0, 0)))
    return view.reshape(-1, _LANES), words


def gather_interp_pallas(
    values: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    *,
    interpret: bool = False,
    name: str = "lram_gather",
) -> jax.Array:
    """sum_k w[..., k] * values[idx[..., k]] -> (..., m) fp32.

    Non-differentiable by itself; `gather_interp_vjp` adds the custom_vjp
    (scatter-add for dvalues, gathered dot for dw).  `name` is the
    kernel's name in the compiled program and in a profile.
    """
    lead = idx.shape[:-1]
    top_k = idx.shape[-1]
    num_rows, m = values.shape
    view, words = _lane_view(values)
    idx_flat = jnp.clip(idx.reshape(-1, top_k).astype(jnp.int32),
                        0, num_rows - 1)
    w_flat = w.reshape(-1, top_k).astype(jnp.float32)
    n = idx_flat.shape[0]
    pad = -n % _TOKENS_PER_STEP
    if pad:
        idx_flat = jnp.pad(idx_flat, ((0, pad), (0, 0)))
        w_flat = jnp.pad(w_flat, ((0, pad), (0, 0)))
    planes = 4 // values.dtype.itemsize

    def smem_block():
        return pl.BlockSpec((_TOKENS_PER_STEP, top_k), lambda i: (i, 0),
                            memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_kernel, top_k=top_k, words_per_row=words,
                          dtype=values.dtype),
        grid=((n + pad) // _TOKENS_PER_STEP,),
        in_specs=[smem_block(), smem_block(),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_TOKENS_PER_STEP, planes * _LANES),
                               lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((_TOKENS_PER_STEP * top_k, _LANES), view.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        out_shape=jax.ShapeDtypeStruct((n + pad, planes * _LANES),
                                       jnp.float32),
        interpret=interpret,
        name=name,
    )(idx_flat, w_flat, view)
    # fold the row segments, then interleave the planes: word i, plane b
    # holds element planes * i + b of the row
    out = out[:n].reshape(n, planes, _LANES // words, words).sum(axis=2)
    return out.transpose(0, 2, 1).reshape(*lead, m)


# ---------------------------------------------------------------------------
# fused dequant variant: rows move HBM->VMEM in their 1-byte form
# ---------------------------------------------------------------------------

def gather_interp_quant_pallas(
    q: jax.Array,
    scale: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    *,
    interpret: bool = False,
    name: str = "lram_gather_quant",
) -> jax.Array:
    """sum_k w[..., k] * scale[i] * q[i := idx[..., k]] -> (..., m).

    Same gather as `gather_interp_pallas` over the quantized payload
    (int8 or float8_e4m3fn): rows move in their 1-byte form and are
    unpacked to fp32 in VMEM.  The per-row scale folds into the weight
    (`w * scale[idx]`) before the kernel, so the accumulation stays fp32.
    """
    idx_c = jnp.clip(idx.astype(jnp.int32), 0, q.shape[0] - 1)
    ws = w.astype(jnp.float32) * jnp.take(
        scale.astype(jnp.float32), idx_c, axis=0
    )
    return gather_interp_pallas(q, idx_c, ws, interpret=interpret,
                                name=name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def gather_interp_quant(q, scale, idx, w, interpret=True):
    """Differentiable wrapper for the fused-dequant Pallas gather.

    A pallas_call that DMAs by hand has no autodiff rule, and a quantized
    table is a frozen store (its training path is the tiered write-back),
    so the only live cotangent is dw — the dequantized-row dot, computed
    with a plain jnp gather in the backward.  Matches the dw contract of
    `repro.kernels.ops.lram_lookup`.
    """
    return gather_interp_quant_pallas(q, scale, idx, w, interpret=interpret)


def _quant_fwd(q, scale, idx, w, interpret):
    out = gather_interp_quant_pallas(q, scale, idx, w, interpret=interpret)
    return out, (q, scale, idx, w)


def _quant_bwd(interpret, res, g):
    q, scale, idx, w = res
    rows = jnp.take(q, idx, axis=0).astype(jnp.float32) \
        * jnp.take(scale, idx, axis=0)[..., None]
    dw = jnp.einsum("...m,...km->...k", g.astype(jnp.float32), rows)
    zero = (np.zeros(q.shape, jax.dtypes.float0)
            if not jnp.issubdtype(q.dtype, jnp.inexact)
            else jnp.zeros(q.shape, q.dtype))
    return (
        zero,
        jnp.zeros(scale.shape, scale.dtype),
        np.zeros(idx.shape, jax.dtypes.float0),
        dw.astype(w.dtype),
    )


gather_interp_quant.defvjp(_quant_fwd, _quant_bwd)


# ---------------------------------------------------------------------------
# differentiable fp32 wrapper (the "pallas" kernel cell of the plan matrix)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gather_interp_vjp(values, idx, w, interpret=True):
    """Differentiable wrapper for the fp32 Pallas gather.

    A pallas_call that DMAs by hand has no autodiff rule, so the backward
    is supplied here with the same contract as `repro.kernels.ops.
    lram_lookup`: d values is the paper's sparse scatter-add over the
    touched rows, d w the gathered-row dot.  This is what lets the dense
    and sharded placements run the Pallas kernel under `jax.grad`.
    """
    return gather_interp_pallas(values, idx, w, interpret=interpret)


def _vjp_fwd(values, idx, w, interpret):
    out = gather_interp_pallas(values, idx, w, interpret=interpret)
    return out, (values, idx, w)


def _vjp_bwd(interpret, res, g):
    values, idx, w = res
    g = g.astype(jnp.float32)
    dvalues = scatter_add.scatter_add_rows(values.shape[0], idx, w, g,
                                           interpret=interpret)
    rows = jnp.take(values, idx, axis=0).astype(jnp.float32)
    dw = jnp.einsum("...m,...km->...k", g, rows)
    return (
        dvalues.astype(values.dtype),
        np.zeros(idx.shape, dtype=jax.dtypes.float0),
        dw.astype(w.dtype),
    )


gather_interp_vjp.defvjp(_vjp_fwd, _vjp_bwd)


# the "pallas" kernel axis of the lookup-plan registry
# (repro.core.lookup): interpret mode is chosen per backend at call time
lookup.register_kernel(
    "pallas", "fp32",
    lambda values, idx, w: gather_interp_vjp(values, idx, w,
                                             interpret_mode()),
)
lookup.register_kernel(
    "pallas", "quant",
    lambda table, idx, w: gather_interp_quant(
        table.q, table.scale, idx, w, interpret_mode()
    ),
)
