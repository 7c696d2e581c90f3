"""Shared training-step builders for every engine.

The paper-faithful per-client training loop lives in
``repro.api.reference_engine.ReferenceEngine`` as a pure
``TrainState -> TrainState`` executor behind the :class:`repro.api.TrainSession`
facade; this module keeps what all engines share:

  * :func:`make_client_step` / :func:`make_server_step` — pure functions of
    ``(pytrees, batch, lr)`` closed over the model/optimizer config only.
    The reference engine jits them one client at a time (the paper-faithful
    oracle); the fused and spmd engines compose the same functions into the
    cohort step (``core.spmd.make_cohort_train_step``) that runs vmapped
    over stacked client cohorts, so every engine runs numerically identical
    math in ``eq1`` grad mode.
  * :class:`RoundMetrics` — the per-round metric record.

Each side's loss and gradient run under ``jax.named_scope("client")`` /
``("server")``, so every engine's device ops carry the side in their HLO
``op_name`` (backward ops as ``transpose(jvp(client))``); Adam and Eq. (1)
carry ``adam`` and ``eq1`` (docs/ENGINES.md, "Reading a run").

Gradients never flow from server to client (``h_i`` enters the server step
as data), and every model is initialized from the same random seed via the
adapters in ``core/splitee.py`` (paper §III-B).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig
from repro.core.losses import softmax_cross_entropy
from repro.optim import adam_update


@dataclass
class RoundMetrics:
    round: int
    client_loss: float
    server_loss: float
    #: slots that contributed to this round's aggregation under a client
    #: population (-1 = fixed cohort, every client always participates)
    active_clients: int = -1
    #: assigned clients masked out of this round for exceeding their step
    #: budget / deadline (population sessions only)
    stragglers: int = 0


def masked_update(mask, new_tree, old_tree):
    """Participation-gated state update: where ``mask`` (a traced 0/1
    scalar) is set, take the stepped value, else keep the old one — leaf
    by leaf, with fixed shapes, so a varying active set never recompiles.
    Gating the *update* (not just the loss) is load-bearing: a zeroed loss
    still has zero gradients flow through Adam, whose moment decay and
    step count would move an inactive client's parameters."""
    return jax.tree.map(lambda n, o: jnp.where(mask > 0, n, o),
                        new_tree, old_tree)


# ---------------------------------------------------------------------------
# Shared step-builders
# ---------------------------------------------------------------------------


def client_loss_fn(model) -> Callable:
    """The client-side training loss: the adapter's ``client_loss`` hook
    when it defines one (``(trainable, state, x, y) -> (loss, (h,
    new_state))`` — BackboneSplitModel adds its MoE load-balancing aux loss
    there, weighted per the config), else the protocol default: exit-head
    cross-entropy.  Evaluation never uses the hook — aux losses are a
    training regularizer only."""
    custom = getattr(model, "client_loss", None)
    if custom is not None:
        return custom

    def loss_fn(trainable, state, x, y):
        h, logits, new_state = model.client_forward(trainable, state, x,
                                                    train=True)
        return softmax_cross_entropy(logits, y), (h, new_state)

    return loss_fn


def server_loss_fn(model, li: int) -> Callable:
    """The server-side training loss: the adapter's ``server_loss`` hook
    (``(trainable, state, h, li, y) -> (loss, new_state)``, closed over
    ``li`` here) when defined, else final-head cross-entropy."""
    custom = getattr(model, "server_loss", None)
    if custom is not None:
        def loss_fn(trainable, state, h, y):
            return custom(trainable, state, h, li, y)
        return loss_fn

    def loss_fn(trainable, state, h, y):
        logits, new_state = model.server_forward(trainable, state, h, li,
                                                 train=True)
        return softmax_cross_entropy(logits, y), new_state

    return loss_fn


def make_client_step(model, opt_cfg: OptimizerConfig) -> Callable:
    """(trainable, state, opt, x, y, lr) ->
    (trainable, state, opt, h, loss) — Alg. 1/2 lines 6-11."""
    loss_fn = client_loss_fn(model)

    def step(trainable, state, opt, x, y, lr):
        with jax.named_scope("client"):
            (loss, (h, new_state)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(trainable, state, x, y)
        trainable, opt = adam_update(trainable, grads, opt, opt_cfg, lr)
        return trainable, new_state, opt, h, loss

    return step


def make_server_step(model, opt_cfg: OptimizerConfig, li: int) -> Callable:
    """(trainable, state, opt, h, y, lr) ->
    (trainable, state, opt, loss) — Alg. 1/2 lines 12-16; ``h`` enters as
    data, so no gradient ever flows back to the client."""
    loss_fn = server_loss_fn(model, li)

    def step(trainable, state, opt, h, y, lr):
        with jax.named_scope("server"):
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(trainable, state, h, y)
        trainable, opt = adam_update(trainable, grads, opt, opt_cfg, lr)
        return trainable, new_state, opt, loss

    return step
