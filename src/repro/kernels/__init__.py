"""Pallas TPU kernels for the bandwidth-critical lookup hot path.

Public surface:

  * `repro.kernels.e8_lookup`     — query kernel: distance matmul over the
    232 candidates + unrolled top-k (`lram_query_pallas`)
  * `repro.kernels.gather_interp` — DMA row gather + weighted
    interpolation (`gather_interp_pallas`, differentiable
    `gather_interp_vjp`); fused-dequant variants for quantized tables
    (`gather_interp_quant_pallas`, differentiable `gather_interp_quant`)
  * `repro.kernels.tiered_gather` — the same gather through the tiered
    store's shard->slot indirection (`tiered_gather_pallas`, quantized
    `tiered_gather_quant_pallas`, jnp references)
  * `repro.kernels.scatter_add`   — the value table's gradient: sorted,
    block-owned scatter-add (`scatter_add_rows`, kernel `lram_scatter_add`),
    shared by every fp32 gather's backward; `gather_rows`, the jnp gather
    with that backward
  * `repro.kernels.ops`           — `lram_lookup`: query + gather fused
    behind one custom_vjp (sparse scatter-add backward), and the legacy
    `make_interp_impl` callable hook (deprecated)
  * `repro.kernels.ref`           — jnp references for every kernel

`gather_interp`, `scatter_add` (fp32) and `ref` (quantized) register
the "pallas" / "reference" kernel cells of the lookup-plan registry, and `tiered_gather` the indirected
cells (`repro.core.lookup` resolves them lazily).  On the CPU backend
the kernels run in Pallas interpret mode (`interpret_mode`); on every
other backend they compile to Mosaic, so a TPU never interprets.
Placement in the overall system: docs/architecture.md.
"""


def interpret_mode() -> bool:
    """Whether a Pallas kernel call with no explicit `interpret` argument
    runs interpreted: on the CPU backend only."""
    import jax

    return jax.default_backend() == "cpu"
