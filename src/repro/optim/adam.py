"""Adam optimizer (paper Table II) as pure pytree transforms.

State dtype is configurable: fp32 (default) or bf16 for the 100B+ assigned
architectures where optimizer memory dominates the HBM budget (see
docs/EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig


@dataclass
class AdamState:
    step: jnp.ndarray       # int32 scalar
    m: Any                  # pytree like params
    v: Any


# keyed registration so checkpoint manifests name leaves ".step"/".m"/".v"
# instead of flattened indices (see checkpoint/checkpoint.py, docs/API.md)
jax.tree_util.register_pytree_with_keys(
    AdamState,
    lambda s: (((jax.tree_util.GetAttrKey("step"), s.step),
                (jax.tree_util.GetAttrKey("m"), s.m),
                (jax.tree_util.GetAttrKey("v"), s.v)), None),
    lambda _, c: AdamState(*c),
    flatten_func=lambda s: ((s.step, s.m, s.v), None),
)


def adam_init(params: Any, cfg: OptimizerConfig) -> AdamState:
    z = lambda p: jnp.zeros(p.shape, cfg.state_dtype)
    return AdamState(step=jnp.zeros((), jnp.int32),
                     m=jax.tree.map(z, params), v=jax.tree.map(z, params))


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


@jax.named_scope("adam")
def adam_update(params: Any, grads: Any, state: AdamState,
                cfg: OptimizerConfig, lr: jnp.ndarray,
                lr_scale_tree: Optional[Any] = None):
    """One Adam step.  ``lr_scale_tree`` (optional, same structure as params
    or a prefix) multiplies the per-leaf learning rate — used by the
    Sequential strategy's server-LR divisor and by per-layer SplitEE scaling.
    Returns (new_params, new_state)."""
    step = state.step + 1
    if cfg.grad_clip > 0:
        gn = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / (gn + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, s=None):
        gf = g.astype(jnp.float32)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(gf)
        update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + cfg.eps)
        if cfg.weight_decay > 0:
            update = update + cfg.weight_decay * p.astype(jnp.float32)
        eff_lr = lr if s is None else lr * s
        p_new = p.astype(jnp.float32) - eff_lr * update
        return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    if lr_scale_tree is None:
        out = jax.tree.map(upd, params, grads, state.m, state.v)
    else:
        out = jax.tree.map(upd, params, grads, state.m, state.v, lr_scale_tree)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
    return new_p, AdamState(step=step, m=new_m, v=new_v)
