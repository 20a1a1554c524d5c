"""The value table's gradient: a sorted, block-owned scatter-add (Pallas TPU).

Computes the dense gradient of a row gather,

    dvalues = zeros(rows, m).at[idx].add(w[..., None] * g[..., None, :]),

for `idx`, `w` of shape (..., k) and `g` of shape (..., m): every looked-up
row receives its weight times its query's cotangent.  XLA's TPU scatter
must allow duplicate ids, so it applies one read-modify-write of a row at a
time.  Here the ids are sorted instead, and each block of `B` output rows is
owned by one run of grid steps that writes it exactly once:

1. sort the flat ids, carrying the weights and the entry's position;
2. find where each block's entries start in the sorted ids (one
   `searchsorted`), and list the (block, chunk of `C` sorted entries)
   pairs the grid visits, a block at a time, at least one per block;
3. gather each entry's cotangent row by its position, in sorted order,
   `(entries, m)`: the only array of updates that exists;
4. each grid step scales its chunk's rows by their weights, builds the
   0/1 matrix of the chunk's entries against its block's rows (an iota
   compare; entries of other blocks match no row) and adds the product
   into the block, which stays in VMEM while consecutive steps visit it.

The 0/1 matrix is exact in bfloat16; the updates are split into three
bfloat16 parts that sum to their float32 value, stacked into one MXU
product accumulated in float32, so the sum keeps float32 accuracy (only the
order of summation differs from XLA's).  A non-finite update makes its
whole block non-finite.

The kernel writes the gradient transposed, `(m, rows)`: the layout XLA
keeps the table in on a TPU (`{0,1}`), so returning its transpose costs no
copy.  Out-of-range ids are dropped and negative ones wrap, as in
`.at[].add`.

On the CPU backend, and for shapes the kernel cannot tile (`m` neither a
divisor nor a multiple of 128, `rows` not a multiple of 128), the same
gradient is XLA's `.at[].add`.  Which path a backward was built with is
counted at trace time (`lram.scatter_add.kernel` / `.fallback`).  Under an
ambient mesh (`repro.distributed.context`) each device sums its share of
the batch and a psum adds the shares.

`gather_rows` is the "reference" fp32 gather of the lookup-plan registry:
the plain `jnp.take` + einsum forward, with this gradient as its backward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import lookup
from repro.distributed import context
from repro.kernels import interpret_mode, ref

_LANES = 128
# the fixed cost of one grid step (pipeline bookkeeping and DMA issue),
# in MXU multiply-adds: it sets the trade between many small tiles and
# a few large ones that multiply mostly zeros
_STEP_MACS = 25 * 2**20


def _pow2(x: float, lo: int, hi: int) -> int:
    return max(lo, min(hi, 2 ** round(math.log2(max(x, 1.0)))))


def tiles(rows: int, m: int, n: int) -> tuple[int, int] | None:
    """(rows a block, entries a chunk) for `n` entries into (rows, m), or
    None where the kernel cannot tile the shape.

    The MXU work is about 3 m (n B + rows C) multiply-adds (each entry meets
    each row of its block; each block meets a chunk of entries more than it
    owns), and the steps number about n / C + rows / B: B and C balance
    the two."""
    if rows % _LANES or not (m % _LANES == 0 or _LANES % m == 0) or n == 0:
        return None
    b = _pow2(math.sqrt(_STEP_MACS * rows / (3 * m * n)), _LANES, 1024)
    while rows % b:
        b //= 2
    c = _pow2(math.sqrt(_STEP_MACS * n / (3 * m * rows)), _LANES, 2048)
    return b, c


def _split3(u: jax.Array) -> jax.Array:
    """float32 (C, m) -> bfloat16 (3C, m): three parts that sum to u."""
    hi = u.astype(jnp.bfloat16)
    r = u - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _kernel(block_ref, chunk_ref, total_ref, ids_ref, w_ref, g_ref, out_ref,
            *, rows_per_block: int):
    i = pl.program_id(0)
    block = block_ref[i]

    @pl.when((i == 0) | (block != block_ref[jnp.maximum(i - 1, 0)]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < total_ref[0])
    def _():
        chunk, m = g_ref.shape
        local = ids_ref[...] - block * rows_per_block          # (1, C)
        rows = jax.lax.broadcasted_iota(jnp.int32,
                                        (rows_per_block, chunk), 0)
        onehot = (rows == local).astype(jnp.float32).astype(jnp.bfloat16)
        # the weights as a column: (1, C) -> (C, 1)
        w = jnp.broadcast_to(w_ref[...], (8, chunk)).T[:, :1]
        out_ref[...] += jax.lax.dot_general(
            _split3(g_ref[...] * w), jnp.concatenate([onehot] * 3, axis=1),
            (((0,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )                                                      # (m, B)


def _work_items(sorted_ids, rows: int, b: int, c: int):
    """The grid's (block, chunk) pairs, block by block, and their count.

    A block visits every chunk that holds one of its entries, and an empty
    block one chunk (it writes zeros).  The list has a static length, at
    least the count; the steps past the count repeat the last pair and do
    nothing."""
    blocks, chunks = rows // b, sorted_ids.shape[0] // c
    bounds = jnp.searchsorted(
        sorted_ids, jnp.arange(blocks + 1, dtype=jnp.int32) * b
    ).astype(jnp.int32)
    lo = bounds[:-1] // c
    n_chunks = jnp.maximum(-(-bounds[1:] // c) - lo, 1)
    ends = jnp.cumsum(n_chunks)
    item = jnp.arange(chunks + blocks, dtype=jnp.int32)
    block = jnp.minimum(jnp.searchsorted(ends, item, side="right"),
                        blocks - 1).astype(jnp.int32)
    offset = jnp.minimum(item - (ends - n_chunks)[block], n_chunks[block] - 1)
    chunk = jnp.minimum(lo[block] + offset, chunks - 1)
    return block, chunk, ends[-1:]


def _sorted_entries(rows: int, idx, w, g, c: int):
    """The entries sorted by row id and padded to whole chunks: ids (ids
    out of range, and the pads, under the key `rows`, which no block owns),
    weights, and each entry's cotangent row (entries, m)."""
    m, k, n = g.shape[-1], idx.shape[-1], idx.size
    ids = idx.reshape(-1).astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + rows, ids)
    ids = jnp.where((ids >= 0) & (ids < rows), ids, rows)
    pad = -n % c
    ids = jnp.pad(ids, (0, pad), constant_values=rows)
    wf = jnp.pad(w.reshape(-1).astype(jnp.float32), (0, pad))
    pos = jnp.arange(n + pad, dtype=jnp.int32)
    ids, wf, pos = jax.lax.sort((ids, wf, pos), num_keys=1, is_stable=False)
    gs = jnp.take(g.reshape(-1, m).astype(jnp.float32), pos // k, axis=0,
                  mode="clip")
    return ids, wf, gs


def _block_sums(rows: int, b: int, c: int, ids, wf, gs, *,
                interpret: bool = False) -> jax.Array:
    """The kernel over sorted entries: (m, rows)."""
    m = gs.shape[1]
    block, chunk, total = _work_items(ids, rows, b, c)
    return pl.pallas_call(
        functools.partial(_kernel, rows_per_block=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(block.shape[0],),
            in_specs=[
                pl.BlockSpec((1, c), lambda i, bl, ch, t: (0, ch[i])),
                pl.BlockSpec((1, c), lambda i, bl, ch, t: (0, ch[i])),
                pl.BlockSpec((c, m), lambda i, bl, ch, t: (ch[i], 0)),
            ],
            out_specs=pl.BlockSpec((m, b), lambda i, bl, ch, t: (0, bl[i])),
        ),
        out_shape=jax.ShapeDtypeStruct((m, rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lram_scatter_add",
    )(block, chunk, total, ids.reshape(1, -1), wf.reshape(1, -1), gs)


def _sorted_sum(rows: int, idx, w, g, interpret: bool) -> jax.Array:
    """The gradient by sort and the block-owned kernel: (rows, m)."""
    b, c = tiles(rows, g.shape[-1], idx.size)
    ids, wf, gs = _sorted_entries(rows, idx, w, g, c)
    return _block_sums(rows, b, c, ids, wf, gs, interpret=interpret).T


def _over_batch(rows: int, idx, w, g, interpret: bool) -> jax.Array:
    """`_sorted_sum` on the ambient mesh: a Mosaic kernel is never
    partitioned by GSPMD, so each device sums its share of the batch (the
    leading axis, over the data-parallel axes, where it divides them) and
    a psum adds the shares.  Inside a shard_map, or with no mesh, the call
    is already local."""
    mesh = context.get_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return _sorted_sum(rows, idx, w, g, interpret)
    batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    shards = math.prod(mesh.shape[a] for a in batch)
    if shards == 1 or idx.shape[0] % shards:
        batch = ()   # every device sums the whole batch, and none adds

    def local(i, ww, gg):
        part = _sorted_sum(rows, i, ww, gg, interpret)
        return jax.lax.psum(part, batch) if batch else part

    spec = P(batch) if batch else P()
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=P(), check_vma=False)(idx, w, g)


def scatter_add_rows(rows: int, idx: jax.Array, w: jax.Array,
                     g: jax.Array, *, interpret: bool | None = None
                     ) -> jax.Array:
    """float32 (rows, m): the sum of w[..., j] * g[...] over the rows
    idx[..., j] — the table gradient of `sum_j w_j * values[idx_j]`.

    `interpret`: None picks by backend (`interpret_mode()`): XLA's
    `.at[].add` on the CPU, where the kernel could only be interpreted,
    and the compiled kernel elsewhere; True runs the kernel interpreted,
    False compiled.  An untileable shape is XLA's either way."""
    m = g.shape[-1]
    if interpret is None and interpret_mode() \
            or tiles(rows, m, idx.size) is None:
        obs.counter("lram.scatter_add.fallback").inc()
        wg = w.astype(jnp.float32)[..., None] \
            * g.astype(jnp.float32)[..., None, :]
        return jnp.zeros((rows, m), jnp.float32).at[idx.reshape(-1)].add(
            wg.reshape(-1, m))
    obs.counter("lram.scatter_add.kernel").inc()
    return _over_batch(rows, idx, w, g, bool(interpret))


# ---------------------------------------------------------------------------
# the "reference" fp32 kernel of the lookup-plan registry (repro.core.lookup)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gather_rows(values: jax.Array, idx: jax.Array, w: jax.Array,
                interpret: bool | None = None) -> jax.Array:
    """sum_k w_k * values[idx_k] -> (..., m): the plain `jnp.take` +
    einsum of `ref.gather_interp_ref`, whose backward builds the table
    gradient with `scatter_add_rows` (`interpret` is its argument) and
    `w`'s by the gathered-row dot."""
    return ref.gather_interp_ref(values, idx, w)


def _gather_rows_fwd(values, idx, w, interpret):
    rows = jnp.take(values, idx, axis=0).astype(jnp.float32)
    out = jnp.einsum("...k,...km->...m", w.astype(jnp.float32), rows)
    return out, (values, rows, idx, w)


def _gather_rows_bwd(interpret, res, g):
    values, rows, idx, w = res
    dvalues = scatter_add_rows(values.shape[0], idx, w, g,
                               interpret=interpret)
    dw = jnp.einsum("...m,...km->...k", g, rows)
    return (dvalues.astype(values.dtype),
            np.zeros(idx.shape, jax.dtypes.float0), dw.astype(w.dtype))


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)

lookup.register_kernel("reference", "fp32",
                       lambda values, idx, w: gather_rows(values, idx, w))
