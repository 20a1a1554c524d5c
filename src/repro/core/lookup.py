"""Composable lookup-backend registry: placement × storage × kernel.

The paper's O(1) lookup has one semantic — ``out = Σₖ wₖ · values[idxₖ]`` —
but many deployment shapes.  Historically each shape was a hand-wired
implementation (an isinstance/string/callable ladder in ``core/lram``), so
the combinations that actually reach "billions of entries" (sharded AND
tiered, sharded AND pallas) were structurally impossible.  This module
replaces that ladder with a **plan**: three orthogonal axes resolved once
at config/init time into a :class:`LookupPlan` that owns table
construction, gather+interp (with its autodiff contract), checkpoint
layout, and capability flags.

Axes:

* **placement** — where the table lives:
  ``dense`` (one device array) | ``tiered`` (host shards + device hot
  cache) | ``sharded`` (rows sharded over the ``model`` mesh axis) |
  ``sharded-tiered`` (each model shard owns a host-offloaded row range
  with its own device hot cache).
* **storage** — how a row is stored: ``fp32`` | ``int8`` | ``fp8``
  (1-byte payload + per-row fp32 scales, ``repro.quant``).
* **kernel** — how the gather executes: ``reference`` (jnp take+einsum)
  | ``pallas`` (DMA gather TPU kernels, interpret mode on CPU).

Backends self-register: ``repro.kernels.ref`` / ``repro.kernels.
gather_interp`` / ``repro.kernels.tiered_gather`` register gather kernels,
``repro.memstore.interp`` registers the ``tiered`` placement, and
``repro.distributed.sharded_lram`` registers ``sharded`` and
``sharded-tiered``.  :func:`resolve` lazy-imports the provider module for
whatever cell a config names, so importing ``repro.core`` stays cheap.

Unsupported cells raise :class:`LookupPlanError` **at resolve time** —
misconfiguration fails while building the layer, not deep inside a jitted
apply.  (The legacy callable ``interp_impl`` hook protocol is gone:
callables bypass the plan's capability flags and cannot compose with
tiering/quantization/growth — register a placement backend instead.)

Beyond the gather itself, the plan carries the capabilities the rest of
the system keys on: the serve engine reads ``supports_prefetch``, the
trainer reads ``table_update``, the checkpoint manager reads
``checkpoint_layout``, the GSPMD partitioner reads ``table_rows_axis``
(`repro.distributed.sharding`), and the memory lifecycle manager
(`repro.memctl`) reads ``supports_growth`` / ``row_stats`` /
``build_empty`` for online capacity growth and live plan-to-plan
migration.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable

PLACEMENTS = ("dense", "tiered", "sharded", "sharded-tiered")
STORAGES = ("fp32", "int8", "fp8")
KERNELS = ("reference", "pallas")

# interp_impl string -> placement (legacy names kept as aliases)
IMPL_PLACEMENT = {
    "reference": "dense",
    "dense": "dense",
    "pallas": "dense",
    "tiered": "tiered",
    "sharded": "sharded",
    "sharded-tiered": "sharded-tiered",
}


class LookupPlanError(ValueError):
    """A (placement, storage, kernel) cell that cannot be built — raised
    when the plan is resolved, with the offending cell in the message."""

    def __init__(self, placement, storage, kernel, reason: str):
        self.cell = (placement, storage, kernel)
        super().__init__(
            f"lookup plan ({placement} × {storage} × {kernel}): {reason}"
        )


@dataclasses.dataclass(frozen=True)
class LookupPlan:
    """A resolved lookup backend: one cell of placement × storage × kernel.

    ``build_table(dense_values)`` turns the init-time fp32 draw into the
    table object that sits at ``params["values"]`` (dense array,
    ``QuantizedTable``, ``TieredValueStore``, ``ShardedTieredStore``);
    every placement starts from the *same* draw, so all plans of one
    config are numerically equivalent at init up to storage rounding.

    ``interp(values, idx, w)`` is the gather+interpolate step, carrying
    the backend's autodiff contract (see ``table_update``).

    Capability flags replace isinstance probing everywhere else:

    * ``supports_prefetch`` — the table exposes ``prefetch_last()`` /
      ``warm()`` handles (serve engine per-tick prefetch).
    * ``table_update`` — how the value table trains: ``autodiff`` (dense
      dL/dvalues via the custom-VJP scatter-add), ``writeback`` (sparse
      SGD applied by the store itself), or ``frozen`` (quantized dense
      tables own no update rule).
    * ``checkpoint_layout`` — ``dense`` (one array leaf) or ``shards``
      (streamed ``shard_NNNNNN.npy`` files, ``repro.checkpoint``).
    * ``requires_mesh`` — the interp shard_maps over the ambient mesh.
    * ``supports_growth`` — `repro.memctl.grow` can enlarge this table
      live (append-only K_0 torus growth; mesh-sharded dense tables
      cannot grow without a relaunch).
    * ``row_stats`` — the table tracks per-shard access counts
      (`row_stats()` on the store), which `repro.memctl.telemetry`
      aggregates into utilisation reports.
    * ``table_rows_axis`` — the mesh axis the table's leading (row) axis
      shards over (``None`` = replicate); `distributed.sharding` emits
      the memory table's pspec from this instead of a path regex.
    * ``build_empty`` — zero-filled table of this plan's layout (store
      placements only): the migration target `repro.memctl.migrate`
      streams shards into.
    * ``supports_overlay`` — the serve engine may fuse a per-tenant
      copy-on-write row overlay (`repro.serving.overlay`) into this
      plan's lookup: overlay rows are stored in the *same* storage kind
      as the base table and resolved host-side into per-slot delta packs
      (`repro.core.overlay`), so the device graph never changes shape
      across attach/detach.  Requires host-readable base rows
      (:func:`read_rows_fp32`); the mesh-sharded dense placement keeps
      this off.
    """

    placement: str
    storage: str
    kernel: str
    build_table: Callable[[Any], Any]
    interp: Callable[[Any, Any, Any], Any]
    supports_prefetch: bool = False
    table_update: str = "autodiff"   # autodiff | writeback | frozen
    checkpoint_layout: str = "dense"  # dense | shards
    requires_mesh: bool = False
    supports_growth: bool = False
    row_stats: bool = False
    table_rows_axis: str | None = None
    build_empty: Callable[[], Any] | None = None
    supports_overlay: bool = False

    @property
    def cell(self) -> tuple[str, str, str]:
        return (self.placement, self.storage, self.kernel)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"LookupPlan({self.placement} × {self.storage} × "
                f"{self.kernel}, update={self.table_update})")


# ---------------------------------------------------------------------------
# registries (populated by provider modules at import)
# ---------------------------------------------------------------------------

# placement -> factory(cfg, storage, kernel) -> LookupPlan
_PLACEMENT_FACTORIES: dict[str, Callable] = {}
_PLACEMENT_PROVIDERS = {
    "dense": "repro.core.lookup",            # registered below
    "tiered": "repro.memstore.interp",
    "sharded": "repro.distributed.sharded_lram",
    "sharded-tiered": "repro.distributed.sharded_lram",
}

# (kernel, storage_class) -> gather callable; the storage_class names a
# calling convention, not a dtype: "fp32" (values, idx, w),
# "quant" (QuantizedTable, idx, w), "tiered[-quant]" (cache-indirected,
# see repro.kernels.tiered_gather)
_KERNEL_IMPLS: dict[tuple[str, str], Callable] = {}
_KERNEL_PROVIDERS = {
    ("reference", "fp32"): "repro.kernels.ref",
    ("reference", "quant"): "repro.kernels.ref",
    ("pallas", "fp32"): "repro.kernels.gather_interp",
    ("pallas", "quant"): "repro.kernels.gather_interp",
    ("pallas", "tiered"): "repro.kernels.tiered_gather",
    ("pallas", "tiered-quant"): "repro.kernels.tiered_gather",
}

# store classes that ride params as leafless pytree nodes (prefetch /
# write-back / shard-streaming checkpoint handles)
_STORE_TYPES: list[type] = []
_STORE_PROVIDERS = ("repro.memstore.store", "repro.distributed.sharded_lram")


def register_placement(name: str, factory: Callable) -> None:
    _PLACEMENT_FACTORIES[name] = factory


def register_kernel(kernel: str, storage_class: str, fn: Callable) -> None:
    _KERNEL_IMPLS[(kernel, storage_class)] = fn


def register_store_type(cls: type) -> None:
    global _store_types_cache
    if cls not in _STORE_TYPES:
        _STORE_TYPES.append(cls)
        _store_types_cache = None


def kernel_gather(kernel: str, storage_class: str) -> Callable:
    """The registered gather for (kernel, storage_class), importing its
    provider module on first use."""
    key = (kernel, storage_class)
    if key not in _KERNEL_IMPLS:
        provider = _KERNEL_PROVIDERS.get(key)
        if provider is None:
            raise KeyError(f"no kernel registered for {key}")
        importlib.import_module(provider)
    return _KERNEL_IMPLS[key]


_store_types_cache: tuple[type, ...] | None = None


def store_types() -> tuple[type, ...]:
    """Every registered offloaded-store class (providers imported).
    Memoized after the providers load: `is_store` sits on per-leaf
    checkpoint walks and per-apply validation."""
    global _store_types_cache
    if _store_types_cache is None:
        for provider in _STORE_PROVIDERS:
            importlib.import_module(provider)
        _store_types_cache = tuple(_STORE_TYPES)
    return _store_types_cache


def is_store(x) -> bool:
    return isinstance(x, store_types())


def find_stores(tree) -> list[tuple[str, Any]]:
    """(path, store) for every distinct offloaded store in a pytree."""
    import jax

    types = store_types()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, types)
    )
    out, seen = [], set()
    for path, leaf in flat:
        if isinstance(leaf, types) and id(leaf) not in seen:
            seen.add(id(leaf))
            name = "/".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in path
            )
            out.append((name, leaf))
    return out


def is_memory_table(x) -> bool:
    """A whole value-table object: a registered offloaded store or a
    dense `QuantizedTable` (treated as one leaf, not its q/scale parts)."""
    from repro.quant import QuantizedTable

    return is_store(x) or isinstance(x, QuantizedTable)


def map_memory_tables(tree, fn: Callable[[Any], Any]):
    """Replace every `lram/values` table leaf of a model-sized pytree with
    `fn(table)` — the shared walker behind `repro.memctl`'s growth and
    migration.  Tables are visited whole (`is_memory_table`), so a
    QuantizedTable maps as one object; works on params and on trees
    mirroring them (optimizer moments)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=is_memory_table
    )
    leaves = []
    for path, leaf in flat:
        name = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        leaves.append(fn(leaf) if name.endswith("lram/values") else leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------

def resolve(cfg, override=None) -> LookupPlan:
    """Resolve a config (plus an optional per-call override) into a plan.

    `override` is ``lram_apply``'s ``interp_impl`` argument: ``None``
    (use ``cfg.interp_impl``) or an impl name string.

    Resolution happens once per (config, impl, ambient mesh) — the result
    is memoized, so ``lram_apply`` can call this on every trace without
    re-walking the registry.
    """
    impl = override if override is not None else cfg.interp_impl
    if not isinstance(impl, str):
        raise LookupPlanError(
            "custom", "?", "?",
            "callable interp_impl hooks were removed — pass an impl name "
            "(reference | pallas | tiered | sharded | sharded-tiered) or "
            "register a placement backend via repro.core.lookup",
        )
    from repro.distributed import context as _ctx

    return _resolve_cached(cfg, impl, _ctx.get_mesh())


@functools.lru_cache(maxsize=None)
def _resolve_cached(cfg, impl: str, mesh) -> LookupPlan:
    placement = IMPL_PLACEMENT.get(impl)
    if placement is None:
        raise LookupPlanError(
            impl, "?", "?",
            f"unknown interp_impl {impl!r}; known: {sorted(IMPL_PLACEMENT)}",
        )
    storage = _resolve_storage(cfg, placement)
    kernel = _resolve_kernel(cfg, placement, impl)
    factory = _placement_factory(placement)
    return factory(cfg, storage, kernel)


def _placement_factory(placement: str) -> Callable:
    if placement not in _PLACEMENT_FACTORIES:
        importlib.import_module(_PLACEMENT_PROVIDERS[placement])
    return _PLACEMENT_FACTORIES[placement]


def _resolve_storage(cfg, placement: str) -> str:
    storage = "fp32" if cfg.table_quant in (None, "none") else cfg.table_quant
    spec = getattr(cfg, "tiered", None)
    if placement in ("tiered", "sharded-tiered") and spec is not None \
            and spec.quant != "none":
        if storage not in ("fp32", spec.quant):
            raise LookupPlanError(
                placement, storage, "?",
                f"LRAMConfig.table_quant={storage!r} conflicts with "
                f"TieredSpec.quant={spec.quant!r}",
            )
        storage = spec.quant
    if storage not in STORAGES:
        raise LookupPlanError(
            placement, storage, "?",
            f"unknown storage {storage!r}; known: {STORAGES}",
        )
    return storage


def _resolve_kernel(cfg, placement: str, impl: str) -> str:
    kernel = getattr(cfg, "lookup_kernel", "auto")
    if kernel == "auto":
        if placement == "dense":
            kernel = "pallas" if impl == "pallas" else "reference"
        elif placement in ("tiered", "sharded-tiered"):
            spec = getattr(cfg, "tiered", None)
            kernel = "pallas" if (spec is not None and spec.use_pallas) \
                else "reference"
        else:
            kernel = "reference"
    if kernel not in KERNELS:
        raise LookupPlanError(
            placement, "?", kernel,
            f"unknown kernel {kernel!r}; known: {KERNELS}",
        )
    return kernel


def model_plans(model_cfg) -> list[LookupPlan]:
    """The resolved lookup plans a model config implies (one per distinct
    LRAM config; [] when the arch has no memory layer).  This is how the
    serve engine and the trainer discover capabilities — plan flags, not
    isinstance checks on params."""
    lram_cfg = getattr(model_cfg, "lram", None)
    if lram_cfg is None or not getattr(model_cfg, "lram_layers", ()):
        return []
    return [resolve(lram_cfg)]


# ---------------------------------------------------------------------------
# the dense placement (lives here: it is the reference semantics)
# ---------------------------------------------------------------------------

def _expect_dense(values, placement, storage, kernel):
    if is_store(values):
        raise LookupPlanError(
            placement, storage, kernel,
            "params['values'] is a tiered store but the plan expects a "
            "dense table — init and apply must use the same interp_impl",
        )


def _dense_factory(cfg, storage: str, kernel: str) -> LookupPlan:
    if storage == "fp32":
        from repro import quant

        gather = kernel_gather(kernel, "fp32")

        def interp(values, idx, w):
            _expect_dense(values, "dense", storage, kernel)
            if isinstance(values, quant.QuantizedTable):
                raise LookupPlanError(
                    "dense", storage, kernel,
                    "params['values'] is a QuantizedTable but the plan "
                    "expects an fp32 table — init and apply must use the "
                    "same table_quant",
                )
            return gather(values, idx, w)

        return LookupPlan(
            placement="dense", storage=storage, kernel=kernel,
            build_table=lambda dense: dense, interp=interp,
            supports_growth=True, supports_overlay=True,
        )

    from repro import quant

    quant.check_kind(storage)
    gather = kernel_gather(kernel, "quant")

    def interp(values, idx, w):
        _expect_dense(values, "dense", storage, kernel)
        if not isinstance(values, quant.QuantizedTable):
            raise LookupPlanError(
                "dense", storage, kernel,
                f"params['values'] must be a QuantizedTable for "
                f"storage={storage!r}; got {type(values).__name__}",
            )
        return gather(values, idx, w)

    return LookupPlan(
        placement="dense", storage=storage, kernel=kernel,
        build_table=lambda dense: quant.QuantizedTable.from_dense(
            dense, storage
        ),
        interp=interp,
        # integer payloads are opaque to autodiff: a dense quantized table
        # is a frozen store (training goes through the tiered write-back)
        table_update="frozen",
        supports_growth=True, supports_overlay=True,
    )


register_placement("dense", _dense_factory)


def read_rows_fp32(table, rows) -> Any:
    """Host-side fp32 read of arbitrary rows from any value-table object
    (dense array, `QuantizedTable`, tiered / sharded-tiered store), with
    the table's storage rounding applied.  The per-tenant overlay layer
    (`repro.serving.overlay`) diffs overlay rows against base rows read
    through this, so a plan only sets ``supports_overlay`` if its table
    kind is handled here.  Mirrors `repro.memctl.migrate._read_rows` but
    takes an arbitrary row-id array instead of a contiguous range."""
    import numpy as np

    rows = np.asarray(rows, np.int64).reshape(-1)
    if is_store(table):
        payload, scales = table._read_rows_raw(rows)
        if scales is None:
            return np.asarray(payload, np.float32)
        from repro import quant

        return quant.dequantize_rows_np(payload, scales)
    from repro import quant

    if isinstance(table, quant.QuantizedTable):
        q = np.asarray(table.q)[rows]
        scale = np.asarray(table.scale, np.float32)[rows]
        return quant.dequantize_rows_np(q, scale)
    return np.asarray(table, np.float32)[rows]


def merged_tiered_spec(cfg, storage: str, kernel: str):
    """The TieredSpec a tiered(-sharded) plan actually builds: the
    config's spec (or defaults) with the resolved storage and kernel axes
    folded in.  Shared by the tiered and sharded-tiered factories."""
    from repro.memstore import TieredSpec

    spec = getattr(cfg, "tiered", None) or TieredSpec()
    quant_kind = "none" if storage == "fp32" else storage
    if spec.quant != quant_kind or spec.use_pallas != (kernel == "pallas"):
        spec = dataclasses.replace(
            spec, quant=quant_kind, use_pallas=(kernel == "pallas")
        )
    return spec
