"""Reference engine: the paper-faithful per-client loop as a pure
``TrainState -> TrainState`` executor.

Literally Alg. 1 / Alg. 2: per round, each client runs E local minibatch
steps (client-side loss on its exit head) and the server performs one update
per transmitted minibatch — the shared server under Sequential (server LR
divided by N, paper Table II), per-client servers under Averaging /
distributed, with Eq. (1) cross-layer aggregation on Averaging boundaries.
Gradients never flow from server to client (``h`` enters the server step
through ``stop_gradient``).

One jitted client step and one jitted server step per split layer, a
``float(loss)`` host sync per minibatch: slow but literal — every behavioral
question about other engines is settled against this one.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.engines import Engine, SessionContext, register_engine
from repro.api.state import TrainState
from repro.core.aggregation import cross_layer_aggregate
from repro.core.strategies import (RoundMetrics, make_client_step,
                                   make_server_step)


@register_engine("reference")
class ReferenceEngine(Engine):

    def __init__(self, ctx: SessionContext):
        super().__init__(ctx)
        self._cstep: Dict[int, Callable] = {}
        self._sstep: Dict[int, Callable] = {}

    @classmethod
    def supports(cls, ctx: SessionContext):
        if ctx.strategy not in ("sequential", "averaging", "distributed"):
            return f"unknown strategy {ctx.strategy!r}"
        if ctx.grad_mode != "eq1":
            return (f"the reference engine implements the paper-faithful "
                    f"'eq1' gradient routing only, not {ctx.grad_mode!r} — "
                    f"use the fused or spmd engine for 'sum'")
        if ctx.population is not None:
            return ("the client-population simulation runs in-graph over "
                    "masked cohort lanes (docs/ENGINES.md 'Client "
                    "populations'); use the fused or spmd engine")
        return None

    # ------------------------------------------------------------------ jit
    def _client_step(self) -> Callable:
        # the client step is li-independent (the trainable's own layer keys
        # determine depth), so one jitted step serves every cohort
        if 0 not in self._cstep:
            self._cstep[0] = jax.jit(make_client_step(self.ctx.model,
                                                      self.ctx.opt_cfg))
        return self._cstep[0]

    def _server_step(self, li: int) -> Callable:
        if li not in self._sstep:
            self._sstep[li] = jax.jit(make_server_step(self.ctx.model,
                                                       self.ctx.opt_cfg, li))
        return self._sstep[li]

    # ------------------------------------------------------------ training
    def _run(self, state: TrainState, rounds: int, local_epochs: int = 1,
             log_every: int = 0, chunk_rounds: int = 0
             ) -> Tuple[TrainState, List]:
        """``chunk_rounds`` is accepted for engine-interface uniformity and
        ignored — the reference engine is round-by-round by construction."""
        ctx = self.ctx
        ctx.data.align(state.batches_drawn)
        clients, copts = list(state.clients), list(state.client_opts)
        servers, sopts = list(state.servers), list(state.server_opts)
        t0 = int(state.round)
        metrics: List[RoundMetrics] = []

        for r in range(rounds):
            t = t0 + r
            with self.span("repro.ref.schedule"):
                lr = ctx.schedule(t)
                lr_server = lr / ctx.server_lr_div
            closses, slosses = [], []

            for i, li in enumerate(ctx.profile.split_layers):
                cstep = self._client_step()
                sstep = self._server_step(li)
                sidx = 0 if ctx.strategy == "sequential" else i
                client, copt = clients[i], copts[i]
                server, sopt = servers[sidx], sopts[sidx]

                for _ in range(local_epochs):
                    with self.span("repro.ref.input"):
                        x, y = ctx.data.draw(i)
                        x, y = jnp.asarray(x), jnp.asarray(y)
                    # client-side training (Alg. 1/2 lines 6-11)
                    with self.span("repro.ref.client_step"):
                        tr, st, copt, h, closs = cstep(client["trainable"],
                                                       client["state"], copt,
                                                       x, y, lr)
                    client = {"trainable": tr, "state": st}
                    # server-side training on h_i (lines 12-16)
                    with self.span("repro.ref.server_step"):
                        h = jax.lax.stop_gradient(h)
                        str_, sst, sopt, sloss = sstep(server["trainable"],
                                                       server["state"], sopt,
                                                       h, y, lr_server)
                    server = {"trainable": str_, "state": sst}
                    with self.span("repro.ref.loss_sync"):
                        closses.append(float(closs))
                        slosses.append(float(sloss))

                clients[i], copts[i] = client, copt
                servers[sidx], sopts[sidx] = server, sopt

            # cross-layer aggregation (Alg. 2 lines 20-30)
            if (ctx.strategy == "averaging"
                    and (t + 1) % ctx.cfg.aggregate_every == 0):
                splits = list(ctx.profile.split_layers)
                with self.span("repro.ref.eq1"):
                    trainables = cross_layer_aggregate(
                        [s["trainable"] for s in servers], splits)
                    states = cross_layer_aggregate(
                        [s["state"] for s in servers], splits,
                        extra_shared_keys=())
                servers = [{"trainable": tr, "state": st}
                           for tr, st in zip(trainables, states)]

            m = RoundMetrics(t, float(np.mean(closses)),
                             float(np.mean(slosses)))
            metrics.append(m)
            if log_every and (t % log_every == 0):
                print(f"round {t:4d}  client_loss {m.client_loss:.4f}  "
                      f"server_loss {m.server_loss:.4f}")

        new_state = state.replace(
            clients=tuple(clients), client_opts=tuple(copts),
            servers=tuple(servers), server_opts=tuple(sopts),
            round=jnp.asarray(t0 + rounds, jnp.int32),
            batches_drawn=state.batches_drawn
            + jnp.asarray(rounds * local_epochs, jnp.int32))
        return new_state, metrics
