"""Split-model adapters: partition a layered network into a client-side net
(layers 1..l_i + client output layer) and a server-side net (layers l_i+1..L
+ server head), with all models initialized from the same random seed (paper
§III-B: "Initialize all networks from the same random seed").

Adapters implement the ``repro.api.protocol.SplitModel`` protocol consumed
by every registered training engine (enforced by the conformance test in
tests/test_session.py):

    make_client(l_i)  -> client pytree  {"trainable": {...}, "state": {...}}
    make_server(l_i)  -> server pytree  {"trainable": {layerK.., head}, "state"}
    client_forward(client, x, train)  -> (h, client_logits, new_state)
    server_forward(server, h, l_i, train) -> (server_logits, new_state)

``trainable`` holds everything the optimizer updates; ``state`` carries
non-differentiated statistics (BatchNorm running stats).  Server trainables
are keyed ``layer{l}``/``head`` so Eq. (1) aggregation matches layers by name.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import HeteroProfile
from repro.models import resnet as rn
from repro.models.common import fan_in_init, zeros


# ---------------------------------------------------------------------------
# Cohort stacking — shared by the fused/spmd engines (repro.api)
# ---------------------------------------------------------------------------


def stack_pytrees(trees: Sequence[Any]) -> Any:
    """Stack same-structure pytrees along a new leading "lane" axis.  Clients
    that share a split layer have identical tree structure, so a cohort of k
    clients becomes one pytree with [k, ...] leaves, ready for ``jax.vmap``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_pytrees(stacked: Any, n: int) -> list:
    """Inverse of :func:`stack_pytrees`: split the leading lane axis back into
    ``n`` per-client pytrees (device-resident slices, no host copy)."""
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


class _StackMixin:
    """Adapter-level cohort helpers: every split model can stack a cohort of
    same-shaped per-client pytrees for vmap and unstack them afterwards."""

    def stack_clients(self, trees: Sequence[Any]) -> Any:
        return stack_pytrees(trees)

    def unstack(self, stacked: Any, n: int) -> list:
        return unstack_pytrees(stacked, n)


# ---------------------------------------------------------------------------
# ResNet adapter (the paper's experimental model)
# ---------------------------------------------------------------------------


@dataclass
class ResNetSplitModel(_StackMixin):
    cfg: rn.ResNetConfig
    seed: int = 0

    def __post_init__(self):
        rng = jax.random.PRNGKey(self.seed)
        self.full_params, self.full_state = rn.init_resnet(rng, self.cfg)

    @property
    def num_layers(self) -> int:
        return self.cfg.num_layers

    def make_client(self, li: int) -> Dict[str, Any]:
        params = {f"layer{k}": self.full_params[f"layer{k}"]
                  for k in range(1, li + 1)}
        state = {f"layer{k}": self.full_state[f"layer{k}"]
                 for k in range(1, li + 1)}
        # client output layer: same seed for every client with the same l_i
        head = rn.init_client_head(jax.random.PRNGKey(self.seed + 1000 + li),
                                   self.cfg, li)
        return {"trainable": {"layers": params, "out": head}, "state": state}

    def make_server(self, li: int) -> Dict[str, Any]:
        params = {f"layer{k}": self.full_params[f"layer{k}"]
                  for k in range(li + 1, self.num_layers + 1)}
        params["head"] = self.full_params["head"]
        state = {f"layer{k}": self.full_state[f"layer{k}"]
                 for k in range(li + 1, self.num_layers + 1)}
        return {"trainable": params, "state": state}

    def client_forward(self, trainable, state, x, train: bool
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
        with jax.named_scope("segment"):
            h, new_state = rn.resnet_features(
                trainable["layers"], state, x, self.cfg,
                end_layer=len(trainable["layers"]), train=train)
        with jax.named_scope("exit_head"):
            logits = rn.client_head_forward(trainable["out"], h)
        return h, logits, new_state

    def server_forward(self, trainable, state, h, li: int, train: bool
                       ) -> Tuple[jnp.ndarray, Any]:
        with jax.named_scope("segment"):
            feats, new_state = rn.resnet_features(trainable, state, h,
                                                  self.cfg, start_layer=li,
                                                  train=train)
        with jax.named_scope("head"):
            logits = rn.head_forward(trainable["head"], feats)
        return logits, new_state


# ---------------------------------------------------------------------------
# Tiny MLP adapter (fast property tests / CI)
# ---------------------------------------------------------------------------


@dataclass
class MLPSplitModel(_StackMixin):
    """L-layer MLP on flat inputs; layer l is keyed ``layer{l}`` so the same
    strategy/aggregation machinery applies.  Used by tests and quick demos."""

    in_dim: int
    hidden: int
    num_classes: int
    num_layers: int = 6
    seed: int = 0
    dtype: Any = jnp.float32

    def __post_init__(self):
        rng = jax.random.PRNGKey(self.seed)
        ks = jax.random.split(rng, self.num_layers + 1)
        self.full_params = {}
        d_in = self.in_dim
        for l in range(1, self.num_layers + 1):
            self.full_params[f"layer{l}"] = {
                "w": fan_in_init(ks[l - 1], (d_in, self.hidden), self.dtype),
                "b": zeros((self.hidden,), self.dtype)}
            d_in = self.hidden
        self.full_params["head"] = {
            "w": fan_in_init(ks[-1], (self.hidden, self.num_classes), self.dtype),
            "b": zeros((self.num_classes,), self.dtype)}

    def make_client(self, li: int) -> Dict[str, Any]:
        layers = {f"layer{k}": self.full_params[f"layer{k}"]
                  for k in range(1, li + 1)}
        hrng = jax.random.PRNGKey(self.seed + 1000 + li)
        out = {"w": fan_in_init(hrng, (self.hidden, self.num_classes), self.dtype),
               "b": zeros((self.num_classes,), self.dtype)}
        return {"trainable": {"layers": layers, "out": out}, "state": {}}

    def make_server(self, li: int) -> Dict[str, Any]:
        params = {f"layer{k}": self.full_params[f"layer{k}"]
                  for k in range(li + 1, self.num_layers + 1)}
        params["head"] = self.full_params["head"]
        return {"trainable": params, "state": {}}

    def _apply_layers(self, layers: Dict[str, dict], h, keys):
        for k in keys:
            p = layers[k]
            h = jax.nn.relu(h @ p["w"] + p["b"])
        return h

    def client_forward(self, trainable, state, x, train: bool):
        h = x.reshape(x.shape[0], -1)
        keys = sorted(trainable["layers"], key=lambda s: int(s[5:]))
        h = self._apply_layers(trainable["layers"], h, keys)
        logits = h @ trainable["out"]["w"] + trainable["out"]["b"]
        return h, logits, state

    def server_forward(self, trainable, state, h, li: int, train: bool):
        keys = [f"layer{k}" for k in range(li + 1, self.num_layers + 1)]
        h = self._apply_layers(trainable, h, keys)
        logits = h @ trainable["head"]["w"] + trainable["head"]["b"]
        return logits, state
