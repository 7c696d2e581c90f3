"""``BackboneSplitModel`` — the production backbones behind the
``SplitModel`` protocol.

The ``configs/`` zoo (GLM-4, DeepSeek-V3, Qwen3-MoE, RWKV6, Whisper, …)
describes deep decoder backbones that, until this adapter, could only run
the monolithic fused-SPMD step (core/spmd.py).  This module partitions an
``init_backbone`` parameter tree into the paper's split-learning shape so
any registered engine (``reference``/``fused``/``spmd``) trains them
through :class:`repro.api.TrainSession`:

  * cut layers are the config's ``exit_layers`` — the segment boundaries of
    ``build_plan`` — so a client with cut layer ``l_i = exit_layers[b]``
    holds segments ``0..b`` (layers 1..l_i) plus exit head ``b`` (the
    paper's client output layer), and its server holds segments ``b+1..``
    plus the LM head;
  * server trainables are keyed ``seg{si}``/``head``: segment granularity
    *is* layer granularity at the cut points, so Eq. (1) cross-layer
    aggregation matches common trunks by key exactly as the ``layer{l}``
    keying does for the ResNet/MLP adapters;
  * clients sharing a cut layer have identical pytree structure and
    identical seed-derived values (paper §III-B), so cohorts stack along a
    lane axis (``_StackMixin``) and the fused/spmd engines vmap them
    unchanged.

The task is sequence classification over the synthetic token pipeline
(``data.synthetic.SyntheticSeqClsDataset``): ``x`` is ``(B, T)`` int32
tokens, labels are class ids below the vocab size, and both the exit head
and the LM head are scored at the last position, giving ``(B, V)`` logits —
the same ``(h, logits)`` contract the engines and the ``SplitEvaluator``
already consume.

Scope notes:

  * audio configs (Whisper) cross-attend over the stubbed encoder states —
    the adapter feeds the documented zeros stub through each side's own
    frontend projector; VLM configs train token-only (the vision frontend
    stays out of the trainables);
  * Zamba2's globally-shared attention block is duplicated per side: the
    client family and the server family each train their own copy (they
    start identical; the server copies are Eq.(1)-aggregated like any
    shared key).  This is the split-learning analogue of the 1/N
    participation approximation core/spmd.py documents;
  * MoE router load-balance aux losses ride the optional
    ``client_loss`` / ``server_loss`` hooks (``core.strategies``): each
    family's training loss adds the aux total of its *own* segments
    (weighted by the config's ``router_aux_weight``, applied inside
    ``models.moe.route``), so routers on both sides of the cut stay
    load-balanced while evaluation logits remain aux-free.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core.losses import softmax_cross_entropy
from repro.core.splitee import _StackMixin
from repro.models import frontend as frontend_mod
from repro.models import heads as heads_mod
from repro.models.backbone import _run_forward, build_plan, init_backbone
from repro.models.common import embed


@dataclass
class BackboneSplitModel(_StackMixin):
    """Split a ``configs/`` backbone at any of its ``exit_layers``."""

    cfg: ModelConfig
    seed: int = 0

    def __post_init__(self):
        if not self.cfg.exit_layers:
            raise ValueError(
                f"{self.cfg.name}: BackboneSplitModel needs exit_layers — "
                f"cut layers must sit at exit-head boundaries")
        self.plan = build_plan(self.cfg)
        self.full_params = init_backbone(jax.random.PRNGKey(self.seed),
                                         self.cfg)
        self._exits = tuple(sorted(self.cfg.exit_layers))
        self._boundary = {li: b for b, li in enumerate(self._exits)}

    # -------------------------------------------------------------- identity
    @property
    def name(self) -> str:
        """Recorded in checkpoint manifests for resume validation."""
        return self.cfg.name

    @property
    def num_layers(self) -> int:
        return self.cfg.num_layers

    @property
    def cut_layers(self) -> Tuple[int, ...]:
        """The valid cut layers (= sorted exit layers)."""
        return self._exits

    def _boundary_of(self, li: int) -> int:
        try:
            return self._boundary[li]
        except KeyError:
            raise ValueError(
                f"{self.cfg.name}: cut layer {li} is not an exit boundary; "
                f"valid cut layers are {self._exits}") from None

    # ------------------------------------------------------------ partitions
    def _side_extras(self) -> Dict[str, Any]:
        """Params both sides need a copy of: the shared attention block
        (Zamba2) and, for cross-attending archs, the enc projector."""
        extras: Dict[str, Any] = {}
        if "shared_attn" in self.full_params:
            extras["shared_attn"] = self.full_params["shared_attn"]
        if self.cfg.cross_attention and "frontend" in self.full_params:
            extras["frontend"] = self.full_params["frontend"]
        return extras

    def make_client(self, li: int) -> Dict[str, Any]:
        b = self._boundary_of(li)
        trainable: Dict[str, Any] = {
            "embed": self.full_params["embed"],
            "segments": [self.full_params["segments"][si]
                         for si in range(b + 1)],
            "out": self.full_params["exit_heads"][b],
        }
        trainable.update(self._side_extras())
        return {"trainable": trainable, "state": {}}

    def make_server(self, li: int) -> Dict[str, Any]:
        b = self._boundary_of(li)
        trainable: Dict[str, Any] = {
            f"seg{si}": self.full_params["segments"][si]
            for si in range(b + 1, len(self.plan))
        }
        trainable["head"] = self.full_params["head"]
        trainable.update(self._side_extras())
        return {"trainable": trainable, "state": {}}

    # --------------------------------------------------------------- forward
    def _enc_for(self, trainable: Dict[str, Any], B: int):
        """The stubbed, projected encoder states for cross-attention archs
        (zeros — the documented frontend carve-out), else None."""
        if not self.cfg.cross_attention:
            return None
        raw = jnp.zeros((B, self.cfg.cross_source_len,
                         frontend_mod.WHISPER_FRAME_DIM), self.cfg.dtype)
        return frontend_mod.project(trainable["frontend"], raw).astype(
            self.cfg.dtype)

    def _apply_segment(self, seg_params, si: int, x, positions, enc,
                       shared_p):
        """Run one segment; returns ``(x, aux)`` where ``aux`` totals the
        segment's MoE load-balance losses (0.0 for dense segments)."""
        aux = jnp.zeros((), jnp.float32)
        for ri, run in enumerate(self.plan[si]):
            x, _, a = _run_forward(run, seg_params[ri], shared_p, x,
                                   positions, self.cfg, None, None, enc,
                                   False)
            aux = aux + a
        return x, aux

    def _client_run(self, trainable, x):
        """(h, last-position exit logits, aux total over client segments)."""
        h = embed(trainable["embed"], x).astype(self.cfg.dtype)
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        enc = self._enc_for(trainable, h.shape[0])
        shared_p = trainable.get("shared_attn")
        aux = jnp.zeros((), jnp.float32)
        with jax.named_scope("segment"):
            for si in range(len(trainable["segments"])):
                h, a = self._apply_segment(trainable["segments"][si], si, h,
                                           positions, enc, shared_p)
                aux = aux + a
        with jax.named_scope("exit_head"):
            logits = heads_mod.exit_head(trainable["out"], h, self.cfg)
        return h, logits[:, -1, :], aux

    def _server_run(self, trainable, h, li: int):
        """(last-position head logits, aux total over server segments)."""
        b = self._boundary_of(li)
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        enc = self._enc_for(trainable, h.shape[0])
        shared_p = trainable.get("shared_attn")
        h = h.astype(self.cfg.dtype)
        aux = jnp.zeros((), jnp.float32)
        with jax.named_scope("segment"):
            for si in range(b + 1, len(self.plan)):
                h, a = self._apply_segment(trainable[f"seg{si}"], si, h,
                                           positions, enc, shared_p)
                aux = aux + a
        with jax.named_scope("head"):
            logits = heads_mod.lm_head(trainable["head"], h, self.cfg)
        return logits[:, -1, :], aux

    def client_forward(self, trainable, state, x, train: bool
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
        h, logits, _ = self._client_run(trainable, x)
        return h, logits, state

    def server_forward(self, trainable, state, h, li: int, train: bool
                       ) -> Tuple[jnp.ndarray, Any]:
        logits, _ = self._server_run(trainable, h, li)
        return logits, state

    # ------------------------------------------------------- training losses
    def client_loss(self, trainable, state, x, y
                    ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, Any]]:
        """The ``core.strategies`` client-loss hook: exit-head CE plus the
        client segments' MoE load-balance aux total (config-weighted inside
        the router), so client-side routers train balanced."""
        h, logits, aux = self._client_run(trainable, x)
        return softmax_cross_entropy(logits, y) + aux, (h, state)

    def server_loss(self, trainable, state, h, li: int, y
                    ) -> Tuple[jnp.ndarray, Any]:
        """The server-loss hook: final-head CE plus the server segments'
        aux total (mirrors ``core.spmd.hetero_losses`` adding
        ``out.aux_loss`` to the monolithic server loss)."""
        logits, aux = self._server_run(trainable, h, li)
        return softmax_cross_entropy(logits, y) + aux, state
