"""Adam with per-group learning rates (built from scratch — no optax here).

The paper trains "normal" parameters at 1e-4 and memory-layer values at 1e-3
"to compensate for sparse access" (§3.2).  Param groups are selected by
path substring match on the flattened tree (the LRAM/PKM value tables live
under ".../values").  Global-norm clipping and the usual schedules included.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    lr: float = 1e-4
    memory_lr_mult: float = 10.0   # paper: 1e-3 for memory values
    memory_path: str = "values"
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    schedule: str = "constant"     # constant | cosine | linear
    warmup_steps: int = 0
    total_steps: int = 100_000


def schedule_lr(cfg: OptimConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    lr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.warmup_steps > 0:
        lr = lr * jnp.minimum(1.0, (step + 1) / cfg.warmup_steps)
    if cfg.schedule == "cosine":
        frac = jnp.clip(step / max(1, cfg.total_steps), 0.0, 1.0)
        lr = lr * 0.5 * (1.0 + jnp.cos(np.pi * frac))
    elif cfg.schedule == "linear":
        frac = jnp.clip(step / max(1, cfg.total_steps), 0.0, 1.0)
        lr = lr * (1.0 - frac)
    return lr


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )


def _lr_mult_tree(params, cfg: OptimConfig):
    """Per-leaf multiplier: memory value tables get memory_lr_mult."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    mults = []
    for path, _ in flat:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        mults.append(
            cfg.memory_lr_mult if cfg.memory_path in name else 1.0
        )
    return jax.tree_util.tree_unflatten(treedef, mults)


def adam_init(params) -> dict[str, Any]:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def adam_update(grads, opt_state, params, cfg: OptimConfig):
    """Returns (new_params, new_opt_state, stats).  Runs under the named
    scope `optim`, its global-norm clip under `optim/clip`."""
    with jax.named_scope("optim"):
        return _adam_update(grads, opt_state, params, cfg)


def _adam_update(grads, opt_state, params, cfg: OptimConfig):
    step = opt_state["step"] + 1
    with jax.named_scope("clip"):
        gnorm = global_norm(grads)
        if cfg.grad_clip > 0:
            scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)

    lr = schedule_lr(cfg, step)
    mults = _lr_mult_tree(params, cfg)
    t = step.astype(jnp.float32)
    bc1 = 1.0 - cfg.b1**t
    bc2 = 1.0 - cfg.b2**t

    def upd(g, m, v, p, mult):
        g32 = g.astype(jnp.float32)
        m_new = cfg.b1 * m + (1 - cfg.b1) * g32
        v_new = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g32)
        mhat = m_new / bc1
        vhat = v_new / bc2
        delta = lr * mult * mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay > 0:
            delta = delta + lr * mult * cfg.weight_decay * p.astype(
                jnp.float32
            )
        return (p.astype(jnp.float32) - delta).astype(p.dtype), m_new, v_new

    out = jax.tree.map(
        upd, grads, opt_state["mu"], opt_state["nu"], params, mults
    )
    new_params = jax.tree.map(
        lambda x: x[0], out, is_leaf=lambda x: isinstance(x, tuple)
    )
    new_mu = jax.tree.map(
        lambda x: x[1], out, is_leaf=lambda x: isinstance(x, tuple)
    )
    new_nu = jax.tree.map(
        lambda x: x[2], out, is_leaf=lambda x: isinstance(x, tuple)
    )
    new_state = {"mu": new_mu, "nu": new_nu, "step": step}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
