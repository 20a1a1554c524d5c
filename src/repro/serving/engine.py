"""Continuous-batching serve engine over fixed-shape decode slots.

The engine owns a slotted KV cache (`transformer.init_cache` with the batch
axis as a pool of `slots` sequences) and runs one jitted decode step per
tick, whatever mix of sequences is in flight:

  * **admit** — a ready request is prefilled at batch=1 (prompt padded up
    to a power-of-two bucket so the number of prefill compilations is
    O(log max_prompt), not O(#distinct lengths)) and its sub-cache spliced
    into a free slot (`transformer.write_cache_slot`).  Padded positions
    are harmless by construction: the decode step writes its KV row at the
    current position *before* attending, and the validity mask only ever
    exposes positions <= the slot's true depth, so a stale row is always
    overwritten before it can be read.
  * **step** — one fixed-shape `transformer.decode_step` with a per-slot
    position vector; retired/free slots ride along as maskable garbage
    (token 0 at their frozen position) and their outputs are dropped on
    the host.  No shape ever changes, so the step compiles exactly once.
  * **retire** — a slot whose request hits its generation budget (or the
    cache end) is marked free; the next admission overwrites every cache
    row, so retirement is O(1).

Scheduling modes share this loop and differ only in admission policy:

  * `continuous` — admit into any free slot, every tick.
  * `static`     — the legacy fixed-batch loop: admit only when *all*
    slots are free (gang admission), so a long sequence blocks the whole
    batch — the head-of-line blocking `benchmarks/table8_serving.py`
    quantifies.

Tiered memory integration: the engine asks the model's resolved lookup
plan (`repro.core.lookup.model_plans`) whether the placement
`supports_prefetch`; if so it collects the store handles (tiered or
sharded-tiered) and calls `prefetch_last()` after each tick — every
decode step covers the union of active sequences, so that prefetches
exactly the shards the union touched.  Per-request cache hit-rates are
attributed from per-tick stat deltas (shared-batch attribution: a tick's
hits count toward every request in flight during it).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import lookup
from repro.core import overlay as overlay_ctx
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving.overlay import OverlayManager
from repro.serving.requests import Request, RequestQueue

_STAT_KEYS = ("hits", "misses", "uncached")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine shape: pool size and per-slot sequence budget."""

    slots: int = 4
    max_len: int = 64           # per-slot cache length (prompt + generation)
    mode: str = "continuous"    # continuous | static (gang admission)
    # per-tenant memory overlays (repro.serving.overlay): capacity in
    # overlay rows per slot per lram layer; 0 disables the subsystem
    # entirely (the legacy jitted steps, byte-identical code paths)
    overlay_rows: int = 0
    overlay_write_lr: float = 0.1   # decode-step Hebbian writeback rate

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError("need at least one slot")
        if self.mode not in ("continuous", "static"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.overlay_rows < 0:
            raise ValueError("overlay_rows must be >= 0")


@dataclasses.dataclass
class _Slot:
    """Host-side state of one in-flight sequence."""

    request: Request
    pos: int                    # absolute position of the next decode write
    generated: list[int]
    admit_s: float
    prefill_s: float
    first_logits: np.ndarray    # (V,) logits of the first generated token
    stats: dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(_STAT_KEYS, 0)
    )
    decode_steps: int = 0


def _bucket(n: int, cap: int) -> int:
    """Round a prompt length up to its power-of-two compile bucket."""
    return min(1 << (n - 1).bit_length(), cap)


def _percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


@dataclasses.dataclass
class FinishedRequest:
    """Per-request serving record (the report's `requests` entries)."""

    id: int
    prompt_len: int
    tokens: list[int]
    admit_s: float
    finish_s: float
    prefill_s: float
    decode_steps: int
    cache_hit_rate: float | None
    first_logits: np.ndarray | None = None   # (V,) — equivalence testing
    prompt: np.ndarray | None = None         # (prompt_len,) — likewise

    def summary(self) -> dict[str, Any]:
        return {
            "id": self.id,
            "prompt_len": self.prompt_len,
            "generated": len(self.tokens),
            "admit_s": round(self.admit_s, 4),
            "finish_s": round(self.finish_s, 4),
            "latency_s": round(self.finish_s - self.admit_s, 4),
            "prefill_ms": round(1e3 * self.prefill_s, 3),
            "decode_steps": self.decode_steps,
            "cache_hit_rate": self.cache_hit_rate,
        }


@dataclasses.dataclass
class EngineReport:
    """Aggregate result of one trace replay."""

    mode: str
    wall_s: float
    generated_tokens: int
    step_s: list[float]
    prefill_s: list[float]
    requests: list[FinishedRequest]
    cache: dict[str, Any] | None
    overlay: dict[str, Any] | None = None   # OverlayManager.summary()

    @property
    def tokens_per_sec(self) -> float:
        return self.generated_tokens / self.wall_s if self.wall_s else 0.0

    def p50_ms(self) -> float:
        return 1e3 * _percentile(self.step_s, 50)

    def p99_ms(self) -> float:
        return 1e3 * _percentile(self.step_s, 99)

    def rows(self, prefix: str = "serve") -> list[list[Any]]:
        """Benchmark-harness rows: [name, us_per_call, derived]."""
        med_prefill = 1e6 * _percentile(self.prefill_s, 50)
        med_step = 1e6 * _percentile(self.step_s, 50)
        us_per_tok = (1e6 * self.wall_s / self.generated_tokens
                      if self.generated_tokens else 0.0)
        hit = (f"hit={self.cache['hit_rate']}" if self.cache else "dense")
        rows = [
            [f"{prefix}_prefill", round(med_prefill, 3),
             f"n={len(self.prefill_s)}"],
            [f"{prefix}_decode_step", round(med_step, 3),
             f"p50_ms={self.p50_ms():.3f} p99_ms={self.p99_ms():.3f} {hit}"],
            [f"{prefix}_token", round(us_per_tok, 3),
             f"tokens_per_sec={self.tokens_per_sec:.1f} "
             f"requests={len(self.requests)} mode={self.mode}"],
        ]
        if self.overlay:
            o = self.overlay
            rows.append([
                f"{prefix}_overlay", 0.0,
                f"tenants={o['tenants']} hit_rate={o['hit_rate']} "
                f"bytes_per_tenant={o['bytes_per_tenant']} "
                f"writebacks={o['writebacks']}",
            ])
        return rows

    def summary(self, arch: str) -> dict[str, Any]:
        """The `--json` summary document (schema shared with benchmarks)."""
        return {
            "arch": arch,
            "mode": self.mode,
            "metrics": obs.metrics_doc(),
            "rows": self.rows(),
            "per_step_ms": [round(1e3 * s, 3) for s in self.step_s],
            "decode_median_ms": round(1e3 * _percentile(self.step_s, 50), 2),
            "p50_ms": round(self.p50_ms(), 3),
            "p99_ms": round(self.p99_ms(), 3),
            "tokens_per_sec": round(self.tokens_per_sec, 2),
            "generated_tokens": self.generated_tokens,
            "cache": self.cache,
            "overlay": self.overlay,
            "requests": [r.summary() for r in self.requests],
        }


class ServeEngine:
    """Slot-pool serving engine (see module docstring for the lifecycle).

    `controller` (a `repro.memctl.MemoryController`) hooks in between
    decode ticks: when the memory table outgrows its HBM budget the
    controller migrates it to the tiered store and calls `swap_model`,
    which rebuilds the jitted steps around the new params while the slot
    pool and KV cache carry every in-flight request across the move.
    """

    def __init__(self, params, state, cfg: ModelConfig,
                 engine_cfg: EngineConfig, *, controller=None):
        if cfg.objective != "clm":
            raise ValueError("serving requires a causal-LM arch")
        if cfg.family in ("encdec", "vlm"):
            raise ValueError(
                f"continuous batching supports decoder-only families; "
                f"{cfg.name} is {cfg.family}"
            )
        self.state = state
        self.engine_cfg = engine_cfg
        self.controller = controller
        self.ticks = 0  # decode ticks since construction (policy clock)
        # per-tenant overlays: validated against the lookup plan's
        # capability flag, like prefetch — not isinstance probing
        self.overlays: OverlayManager | None = None
        if engine_cfg.overlay_rows > 0:
            plans = lookup.model_plans(cfg)
            if not plans:
                raise ValueError(
                    f"overlay_rows needs a memory arch; {cfg.name} has no "
                    f"LRAM layer"
                )
            if not plans[0].supports_overlay:
                raise ValueError(
                    f"lookup plan {plans[0]!r} does not support per-tenant "
                    f"overlays"
                )
            self.overlays = OverlayManager(
                num_layers=len(cfg.lram_layers), m=cfg.lram.m,
                storage=plans[0].storage, slots=engine_cfg.slots,
                rows=engine_cfg.overlay_rows,
                write_lr=engine_cfg.overlay_write_lr,
            )
        self._axes = transformer.cache_batch_axes(cfg, engine_cfg.max_len)
        self.cache = transformer.init_cache(
            cfg, engine_cfg.slots, engine_cfg.max_len
        )
        self.swap_model(params, cfg)

    def swap_model(self, params, cfg: ModelConfig | None = None) -> None:
        """(Re)bind the engine's jitted steps to `params` (and optionally a
        new model config — e.g. after a live dense→tiered migration).

        Slot state and the KV cache are untouched: the decode-slot shapes
        depend only on the engine config, so in-flight requests resume on
        the very next tick.  The swapped-in steps compile on first use
        (one-time pause, the cost `benchmarks/table10_lifecycle.py`
        reports as migration pause time)."""
        self.params = params
        if cfg is not None:
            self.cfg = cfg
        cfg = self.cfg
        state = self.state
        # prefetch handles come from the lookup plan's capability flags
        # (tiered and sharded-tiered placements), not from isinstance
        # probing of params
        self.stores = (
            lookup.find_stores(params)
            if any(p.supports_prefetch for p in lookup.model_plans(cfg))
            else []
        )
        # CPU has no buffer donation; donating there only logs warnings
        donate = () if jax.default_backend() == "cpu" else (2,)
        if self.overlays is None:
            self._decode = jax.jit(
                lambda tok, pos, cache: transformer.decode_step(
                    params, state, tok, pos, cache, cfg
                ),
                donate_argnums=donate,
            )
            # jit specializes per tokens shape, so bucketing alone bounds
            # the number of prefill compilations
            self._prefill = jax.jit(
                lambda tokens: transformer.prefill(
                    params, state, {"tokens": tokens}, cfg,
                    self.engine_cfg.max_len
                )
            )
        else:
            # the overlay context wraps the model call *inside* jit: the
            # packs are traced arguments with fixed shapes, so slot
            # attach/detach only mutates host arrays — the decode step
            # still compiles exactly once.  Pack args ride behind the
            # cache, keeping donate_argnums=(2,) valid.
            def _decode_fn(tok, pos, cache, ids, deltas):
                with overlay_ctx.activate(
                    ids, deltas, collect=True
                ) as octx:
                    logits, new_cache = transformer.decode_step(
                        params, state, tok, pos, cache, cfg
                    )
                    access = octx.stacked()
                return logits, new_cache, access

            def _prefill_fn(tokens, ids, deltas):
                with overlay_ctx.activate(ids, deltas):
                    return transformer.prefill(
                        params, state, {"tokens": tokens}, cfg,
                        self.engine_cfg.max_len
                    )

            self._decode = jax.jit(_decode_fn, donate_argnums=donate)
            self._prefill = jax.jit(_prefill_fn)
            self._bind_overlay_reader()
        self._write_slot = jax.jit(
            lambda cache, sub, slot: transformer.write_cache_slot(
                cache, sub, slot, self._axes
            ),
            donate_argnums=() if not donate else (0,),
        )

    def _bind_overlay_reader(self) -> None:
        """Point the overlay manager at the current params' base tables
        (re-bound on every swap_model, so live migrations keep overlay
        deltas consistent with wherever the rows now live)."""
        cfg, params = self.cfg, self.params
        tables = []
        for si, seg in enumerate(transformer.layer_plan(cfg)):
            if seg[0] == "memory" and seg[2] == "lram":
                tables.append(
                    params["segments"][f"seg{si}"]["memffn"]["lram"]["values"]
                )
        host: dict[int, Any] = {}  # device tables snapshot once per swap

        def read(layer: int, rows) -> np.ndarray:
            table = tables[layer]
            rows = np.asarray(rows, np.int64).reshape(-1)
            if lookup.is_store(table):
                return lookup.read_rows_fp32(table, rows)
            cached = host.get(layer)
            if cached is None:
                from repro.quant import QuantizedTable

                if isinstance(table, QuantizedTable):
                    cached = (np.asarray(table.q),
                              np.asarray(table.scale, np.float32))
                else:
                    cached = np.asarray(table, np.float32)
                host[layer] = cached
            if isinstance(cached, tuple):
                from repro import quant

                return quant.dequantize_rows_np(
                    cached[0][rows], cached[1][rows]
                )
            return cached[rows]

        self.overlays.set_base_reader(read)

    # ------------------------------------------------------------ internals

    def _store_stats(self) -> dict[str, int]:
        out = dict.fromkeys(_STAT_KEYS, 0)
        for _, store in self.stores:
            for k in _STAT_KEYS:
                out[k] += store.stats[k]
        return out

    def _admit(self, req: Request, now: float,
               slot_index: int) -> tuple[_Slot, Any]:
        """Prefill one request and splice it into the slotted cache."""
        s = req.prompt_len
        budget = self.engine_cfg.max_len - s
        if budget < 1:
            raise ValueError(
                f"request {req.id}: prompt ({s}) leaves no room to "
                f"generate within max_len={self.engine_cfg.max_len}"
            )
        # attention masks padded positions out (and decode overwrites their
        # KV rows before they can be read), so prompts bucket to powers of
        # two.  Two families must prefill at exact length instead (one
        # compile per distinct length): recurrent state integrates every
        # position, and an SWA ring buffer keeps the *last* window positions
        # of the padded prompt — all valid the moment the ring is full, so
        # pad rows there are not maskable either.
        if self.cfg.family in ("ssm", "hybrid") or self.cfg.attention == "swa":
            bucket = s
        else:
            bucket = _bucket(s, self.engine_cfg.max_len)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :s] = req.prompt
        t0 = time.perf_counter()
        with obs.span("serve.prefill", request=req.id, prompt_len=s,
                      bucket=bucket):
            if self.overlays is None:
                logits, sub_cache = self._prefill(jnp.asarray(tokens))
            else:
                # bind the request's tenant before prefill so the prompt
                # already reads through the tenant's overlay rows; the
                # batch=1 pack slice has a constant shape across slots
                self.overlays.attach(slot_index, req.tenant_id,
                                     tick=self.ticks)
                b = slot_index
                logits, sub_cache = self._prefill(
                    jnp.asarray(tokens),
                    jnp.asarray(self.overlays.ids[:, b:b + 1]),
                    jnp.asarray(self.overlays.deltas[:, b:b + 1]),
                )
            first_logits = np.asarray(logits[0, s - 1])
        prefill_s = time.perf_counter() - t0
        obs.counter("serve.admitted").inc()
        obs.histogram("serve.prefill_s").observe(prefill_s)
        first_tok = int(np.argmax(first_logits))
        return _Slot(
            request=req, pos=s, generated=[first_tok], admit_s=now,
            prefill_s=prefill_s, first_logits=first_logits,
        ), sub_cache

    def _finish(self, slot: _Slot, now: float) -> FinishedRequest:
        st = slot.stats
        total = sum(st.values())
        obs.counter("serve.retired").inc()
        obs.histogram("serve.request_latency_s").observe(now - slot.admit_s)
        return FinishedRequest(
            id=slot.request.id,
            prompt_len=slot.request.prompt_len,
            tokens=slot.generated,
            admit_s=slot.admit_s,
            finish_s=now,
            prefill_s=slot.prefill_s,
            decode_steps=slot.decode_steps,
            cache_hit_rate=(round(st["hits"] / total, 4)
                            if self.stores and total else
                            (0.0 if self.stores else None)),
            first_logits=slot.first_logits,
            prompt=slot.request.prompt,
        )

    def _done(self, slot: _Slot) -> bool:
        return (len(slot.generated) >= slot.request.max_new_tokens
                or slot.pos >= self.engine_cfg.max_len)

    # ------------------------------------------------------------- run loop

    def run(self, requests: list[Request]) -> EngineReport:
        """Replay a request trace to completion and report.

        The whole replay runs under a `serve.run` span (marked for
        `jax.profiler` capture when `--profile-dir` armed the tracer);
        each admission opens `serve.admit` > `serve.prefill` and each
        pool-wide step a `serve.decode_tick` span, so an exported trace
        shows per-tick wall time with the store fills/hits that tick
        caused attached as counter deltas."""
        with obs.span("serve.run", profile=True,
                      mode=self.engine_cfg.mode, requests=len(requests)):
            return self._run(requests)

    def _run(self, requests: list[Request]) -> EngineReport:
        B = self.engine_cfg.slots
        static = self.engine_cfg.mode == "static"
        queue = RequestQueue(requests)
        for _, store in self.stores:
            store.warm()
            store.reset_stats()
        slots: list[_Slot | None] = [None] * B
        tok_buf = np.zeros((B, 1), np.int32)
        pos_buf = np.zeros((B,), np.int32)
        step_s: list[float] = []
        prefill_s: list[float] = []
        finished: list[FinishedRequest] = []
        generated = 0
        t0 = time.perf_counter()
        now = 0.0
        prev_stats = self._store_stats()

        while True:
            now = time.perf_counter() - t0
            # -- admission (static mode gates on a fully drained pool)
            if not static or all(sl is None for sl in slots):
                for b in range(B):
                    if slots[b] is not None:
                        continue
                    req = queue.pop_ready(now)
                    if req is None:
                        break
                    with obs.span("serve.admit", request=req.id,
                                  slot=b, tick=self.ticks):
                        slot, sub_cache = self._admit(req, now, b)
                        self.cache = self._write_slot(
                            self.cache, sub_cache, jnp.int32(b)
                        )
                    prefill_s.append(slot.prefill_s)
                    generated += 1  # first token comes from the prefill
                    # prefill stat delta belongs to the admitted request
                    cur = self._store_stats()
                    for k in _STAT_KEYS:
                        slot.stats[k] += cur[k] - prev_stats[k]
                    prev_stats = cur
                    now = time.perf_counter() - t0
                    if self._done(slot):  # 1-token budget: no decode steps
                        finished.append(self._finish(slot, now))
                        if self.overlays is not None:
                            self.overlays.detach(b)
                        continue
                    slots[b] = slot
                    tok_buf[b, 0] = slot.generated[-1]
                    pos_buf[b] = slot.pos

            active = [b for b in range(B) if slots[b] is not None]
            if not active:
                nxt = queue.next_arrival()
                if nxt is None:
                    break  # drained
                time.sleep(max(0.0, nxt - (time.perf_counter() - t0)))
                continue

            # -- one fixed-shape decode tick over the whole pool
            t_step = time.perf_counter()
            with obs.span("serve.decode_tick", tick=self.ticks,
                          active=len(active)):
                if self.overlays is None:
                    logits, self.cache = self._decode(
                        jnp.asarray(tok_buf), jnp.asarray(pos_buf),
                        self.cache
                    )
                    access = None
                else:
                    logits, self.cache, access = self._decode(
                        jnp.asarray(tok_buf), jnp.asarray(pos_buf),
                        self.cache,
                        jnp.asarray(self.overlays.ids),
                        jnp.asarray(self.overlays.deltas),
                    )
                next_tok = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            dt_step = time.perf_counter() - t_step
            step_s.append(dt_step)
            obs.histogram("serve.decode_step_s").observe(dt_step)
            obs.counter("serve.tokens").inc(len(active))
            self.ticks += 1

            # decode-step writeback: fold this tick's lattice accesses
            # into each active slot's tenant overlay (the packs refresh
            # in place, taking effect from the next tick)
            if access is not None:
                idx_a, w_a, y_a = (np.asarray(a) for a in access)
                for b in active:
                    self.overlays.writeback(
                        b, idx_a[:, b, 0], w_a[:, b, 0], y_a[:, b, 0],
                        tick=self.ticks,
                    )
                obs.counter("serve.overlay_writebacks").inc(len(active))

            # per-request attribution of this tick's cache-stat deltas
            if self.stores:
                cur = self._store_stats()
                for b in active:
                    for k in _STAT_KEYS:
                        slots[b].stats[k] += cur[k] - prev_stats[k]
                prev_stats = cur
                # prefetch the union of active sequences' accesses so the
                # fill overlaps the next tick's dense compute
                for _, store in self.stores:
                    store.prefetch_last()

            # lifecycle hook: the controller may swap the model between
            # ticks (e.g. spill a dense table that outgrew HBM to the
            # tiered store); in-flight slots ride through untouched
            if self.controller is not None and self.controller.on_tick(self):
                prev_stats = self._store_stats()

            now = time.perf_counter() - t0
            for b in active:
                sl = slots[b]
                sl.generated.append(int(next_tok[b]))
                sl.pos += 1
                sl.decode_steps += 1
                generated += 1
                tok_buf[b, 0] = int(next_tok[b])
                pos_buf[b] = sl.pos
                if self._done(sl):
                    with obs.span("serve.retire", request=sl.request.id,
                                  slot=b, tick=self.ticks):
                        finished.append(self._finish(sl, now))
                        slots[b] = None
                        if self.overlays is not None:
                            # retire frees the overlay
                            self.overlays.detach(b)

        wall = time.perf_counter() - t0
        cache_summary = None
        if self.stores:
            agg = {k: 0 for k in
                   ("hits", "misses", "uncached", "fills", "evictions")}
            for _, store in self.stores:
                for k in agg:
                    agg[k] += store.stats[k]
            cache_summary = {
                "hit_rate": round(float(np.mean(
                    [s.hit_rate() for _, s in self.stores]
                )), 4),
                **agg,
            }
        finished.sort(key=lambda r: r.id)
        return EngineReport(
            mode=self.engine_cfg.mode,
            wall_s=wall,
            generated_tokens=generated,
            step_s=step_s,
            prefill_s=prefill_s,
            requests=finished,
            cache=cache_summary,
            overlay=(self.overlays.summary()
                     if self.overlays is not None else None),
        )


def serve_requests(params, state, cfg: ModelConfig, requests: list[Request],
                   *, slots: int = 4, max_len: int | None = None,
                   mode: str = "continuous") -> EngineReport:
    """One-shot convenience: build an engine sized for `requests`, run it."""
    if max_len is None:
        max_len = max(r.prompt_len + r.max_new_tokens for r in requests)
    engine = ServeEngine(
        params, state, cfg,
        EngineConfig(slots=slots, max_len=max_len, mode=mode),
    )
    return engine.run(requests)
