"""SPMD engine: the fused round body staged under jit with recipe-driven
mesh shardings, as a pure ``TrainState -> TrainState`` executor (see
docs/ENGINES.md).

This is the scaling story for the Averaging/distributed strategies: the
chunk function the fused engine scans on one device is compiled with
explicit `jax.sharding.NamedSharding` constraints from a
``launch.shardings.ShardingRecipe`` — the SAME recipe machinery the offline
dry-run uses, so there is one sharding rule set in the repo, not two:

  * the **cohort carry** (stacked clients/servers, Adam moments, BN stats,
    every leaf ``[E, ...]``) is placed by
    ``launch.shardings.train_state_specs``: the lane dim shards over the
    mesh's ``"lanes"`` axis, remaining dims get the recipe's FSDP/TP rules
    (Adam moments mirroring their params), tiny leaves replicate;
  * the **pre-staged batches** (``[rounds, k, E, B, ...]`` per cohort)
    shard their lane dim over ``"lanes"`` and their per-lane batch dim
    ``B`` over the mesh's batch axes (``("pod", "data")`` where present),
    so each device receives only its lanes' slices;
  * XLA's SPMD partitioner inserts the per-minibatch gradient
    ``all-reduce`` over the batch axes, the FSDP ``all-gather`` /
    ``reduce-scatter`` around sharded params, and the cross-lane
    collectives for the in-graph Eq. (1) aggregation
    (``core.aggregation.stacked_cross_layer_aggregate`` sums over the lane
    dim, which is exactly a reduce over the ``"lanes"`` axis).

The math is byte-for-byte the fused engine's (the same
``core.spmd.make_cohort_train_step`` under the same scanned round body), so
spmd ``eq1`` is cross-checkable against the reference engine to float32
reduction tolerance — including ``aggregate_every`` boundaries, cross-recipe
checkpoint resume (states are saved as host arrays and re-placed through
whatever recipe the restoring session runs), and spmd<->fused hand-offs
(tests/test_spmd_engine.py).

Meshes: pass one explicitly (``TrainSession(..., mesh=...)`` — e.g.
``launch.mesh.make_production_mesh(lanes=4)`` or
``launch.mesh.make_host_mesh((2, 2, 1), ("lanes", "data", "model"))``) or
let the engine build the default data-parallel mesh over every visible
device.  Recipes: ``TrainSession(..., recipe=...)`` — a name from
``launch.shardings.NAMED_RECIPES`` (``"greedy"`` default, ``"megatron"``,
``"fsdp-off"``, ``"replicate"``, ...) or a ``ShardingRecipe`` instance.  On
a CPU container, expose fake devices first:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

**Multi-host**: after ``jax.distributed.initialize`` (see
``launch.distributed`` / ``launch.train --distributed``) ``jax.devices()``
is the *global* device list, so the default data mesh — and any
``launch.mesh`` helper — spans every process.  Every process draws the
identical seeded batch stream (the data layer is deterministic, so no
cross-host data exchange is needed) and the engine assembles global
arrays from the host-replicated staging buffers via
``jax.make_array_from_process_local_data``: each process extracts and
uploads only the shard rows its local devices own.  The carry is placed
the same way, and fetched back through a replicating reshard so the
returned ``TrainState`` holds host arrays on every process
(tests/test_distributed.py asserts 2-process ≡ 1-process parity).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.engines import (SessionContext, cohort_layout,
                               register_engine)
from repro.api.fused_engine import FusedEngine
from repro.core.splitee import stack_pytrees
from repro.data.pipeline import effective_batch_size
from repro.launch.mesh import (axis_sizes, batch_axes, lane_axis,
                               make_host_mesh)
from repro.launch.shardings import (resolve_recipe, stage_batch_spec,
                                    to_named, train_state_specs)
from repro.optim import adam_init


def default_data_mesh():
    """A 1-D data-parallel mesh over every visible device (the host-CPU
    test topology and the single-process accelerator default).  Production
    launches pass ``launch.mesh.make_production_mesh()`` instead."""
    return make_host_mesh((len(jax.devices()), 1))


def resolve_mesh(ctx: SessionContext):
    """The mesh this session's spmd engine runs on: the explicit
    ``ctx.mesh`` when one was supplied, else the default data mesh."""
    return ctx.mesh if ctx.mesh is not None else default_data_mesh()


def data_parallelism(mesh) -> int:
    """Total batch-axis parallelism of ``mesh`` (product of the ``pod`` and
    ``data`` axis sizes present)."""
    sizes = axis_sizes(mesh)
    return int(np.prod([sizes[a] for a in batch_axes(mesh)]))


def abstract_cohort_carry(model, split_layers, opt_cfg):
    """The engines' cohort scan carry as a ``jax.eval_shape`` pytree:
    ``{li: (client, client_opt, server, server_opt)}`` with every leaf
    stacked along a leading lane dim.  ``model`` may be a ``SplitModel``
    adapter or a zero-arg factory returning one — the factory runs under
    abstract evaluation, so no parameters materialize (the recipe
    conformance tests build full-arch carries this way)."""
    lis, lanes = cohort_layout(split_layers)

    def build():
        m = model() if callable(model) else model
        carry = {}
        for li in lis:
            cs = [m.make_client(li) for _ in lanes[li]]
            ss = [m.make_server(li) for _ in lanes[li]]
            carry[li] = (
                m.stack_clients(cs),
                stack_pytrees([adam_init(c["trainable"], opt_cfg)
                               for c in cs]),
                m.stack_clients(ss),
                stack_pytrees([adam_init(s["trainable"], opt_cfg)
                               for s in ss]),
            )
        return carry

    return jax.eval_shape(build)


def _model_num_experts(model) -> int:
    """Expert count for the recipe's expert-parallel rules, when the
    adapter wraps a MoE backbone config."""
    cfg = getattr(model, "cfg", None)
    moe = getattr(cfg, "moe", None)
    return int(moe.num_experts) if moe is not None else -1


@register_engine("spmd")
class SpmdEngine(FusedEngine):
    """Recipe-driven mesh-sharded execution of the fused scan+vmap round
    body."""

    def __init__(self, ctx: SessionContext):
        super().__init__(ctx)
        self.mesh = resolve_mesh(ctx)
        self.recipe = resolve_recipe(ctx.recipe)
        self._replicated = NamedSharding(self.mesh, P())

        # recipe shardings for the carry, from its abstract shapes (built
        # once — the carry structure is fixed by the immutable context)
        carry = abstract_cohort_carry(ctx.model, ctx.profile.split_layers,
                                      ctx.opt_cfg)
        self._carry_specs = train_state_specs(
            self.recipe, self.mesh, carry,
            num_experts=_model_num_experts(ctx.model))
        self._carry_shardings = to_named(self._carry_specs, self.mesh)

        # per-cohort staged-batch shardings ([rounds, k, E, B, ...]); the
        # population masks ([rounds, k]) reuse the spec's lane placement
        self._batch_shardings: Dict[int, NamedSharding] = {}
        self._mask_shardings: Dict[int, NamedSharding] = {}
        for li in self._cohort_lis:
            i0 = self._lanes[li][0]
            eb = effective_batch_size(len(ctx.client_data[i0][0]),
                                      ctx.batch_size)
            spec = stage_batch_spec(self.recipe, self.mesh,
                                    self._counts[li], eb)
            self._batch_shardings[li] = NamedSharding(self.mesh, spec)
            self._mask_shardings[li] = NamedSharding(self.mesh,
                                                     P(None, spec[2]))

    @classmethod
    def supports(cls, ctx: SessionContext) -> Optional[str]:
        reason = super().supports(ctx)           # strategy + ragged cohorts
        if reason:
            return reason
        if ctx.mesh is None and len(jax.devices()) < 2:
            return ("needs a mesh (TrainSession(..., mesh=...)) or >1 "
                    "visible device (e.g. XLA_FLAGS=--xla_force_host_"
                    "platform_device_count=4); only 1 device visible")
        mesh = resolve_mesh(ctx)
        recipe = resolve_recipe(ctx.recipe)
        sizes = axis_sizes(mesh)
        dp = data_parallelism(mesh)
        lax_name = lane_axis(mesh)
        lane_sz = (sizes.get(lax_name, 1)
                   if lax_name and recipe.shard_lanes else 1)
        if dp < 2 and lane_sz < 2:
            if lax_name and sizes.get(lax_name, 1) > 1:
                return (f"mesh {sizes} only has parallelism on its lanes "
                        f"axis, which recipe {ctx.recipe_name!r} disables "
                        f"(shard_lanes=False); pick a lane-sharding recipe "
                        f"or a mesh with batch-axis parallelism")
            return (f"mesh {sizes} has no parallelism on its batch axes "
                    f"{batch_axes(mesh)} or a lanes axis")
        for i, (xd, _) in enumerate(ctx.client_data):
            eb = effective_batch_size(len(xd), ctx.batch_size)
            if dp > 1 and eb % dp != 0:
                return (f"client {i}'s effective batch size {eb} does not "
                        f"divide over the data-parallel size {dp}; adjust "
                        f"batch_size or the mesh")
        if lane_sz > 1:
            _, lanes = cohort_layout(ctx.profile.split_layers)
            counts = {li: len(v) for li, v in lanes.items()}
            if not any(c % lane_sz == 0 for c in counts.values()):
                return (f"the mesh's {lane_sz}-way lanes axis divides no "
                        f"cohort's lane count {counts}; equalize cohort "
                        f"sizes, shrink the lanes axis, or use a mesh "
                        f"without one")
        return None

    # ------------------------------------------------------------- staging
    def _compile_chunk(self, chunk: Callable) -> Callable:
        """Jit the scanned round body with the recipe's shardings: the
        carry (params / moments / BN stats) placed per-leaf by
        ``train_state_specs``, staged batch tensors per-cohort by
        ``stage_batch_spec``, per-round losses replicated.  Population
        chunks take the extra per-cohort ``[rounds, k]`` mask input, its
        lane dim sharded like the batches'.  The carry is still donated,
        so long chunks run in place."""
        bsh = dict(self._batch_shardings)
        in_sh = (self._carry_shardings, self._replicated, bsh, dict(bsh))
        if self.ctx.population is not None:
            in_sh += (dict(self._mask_shardings),)
        return jax.jit(chunk,
                       in_shardings=in_sh,
                       out_shardings=(self._carry_shardings,
                                      (self._replicated, self._replicated)),
                       donate_argnums=(0,))

    def _put_mask(self, arr, li: int):
        return self._put_global(arr, self._mask_shardings[li])

    def _put_global(self, arr, sharding: NamedSharding):
        """Host array -> a (possibly process-spanning) ``sharding``.

        Single-process: a plain ``device_put``.  Multi-process: every
        process holds the identical full host copy (the data layer's
        seeded draws and the host-side carry stacking are deterministic),
        so ``jax.make_array_from_process_local_data`` with
        ``global_shape == arr.shape`` lets each process extract and
        upload exactly the shard rows its local devices own — no
        cross-host data exchange."""
        if jax.process_count() == 1:
            return jax.device_put(arr, sharding)
        arr = np.asarray(arr)
        return jax.make_array_from_process_local_data(
            sharding, arr, global_shape=arr.shape)

    def _put_batch(self, arr, li: int):
        """Host-staged batch numpy -> its cohort's sharding directly, so
        each device receives only its lanes' and batch rows' slices (never
        materializing the whole chunk on one device)."""
        return self._put_global(arr, self._batch_shardings[li])

    def _put_ts(self, t: int, n: int):
        ts = np.arange(t, t + n, dtype=np.int32)
        return self._put_global(ts, self._replicated)

    def _stack_carry(self, clients, copts, servers, sopts):
        """Place the stacked carry into its recipe shardings up front
        (avoids an implicit single-device -> sharded reshard inside the
        jit and keeps donation effective).  Multi-process runs place each
        leaf from its host-replicated copy, like the batches."""
        carry = super()._stack_carry(clients, copts, servers, sopts)
        if jax.process_count() == 1:
            return jax.device_put(carry, self._carry_shardings)
        return jax.tree.map(self._put_global, carry, self._carry_shardings)

    def _fetch_carry(self, carry):
        """Multi-process carries have non-addressable shards, so the
        run-final carry is resharded to fully-replicated (an in-graph
        cross-host all-gather) and pulled to host numpy — reading one
        addressable shard of a replicated array is the whole value —
        before the engine unstacks per-client states.  Single-process
        carries are already fully addressable: no copy."""
        if jax.process_count() == 1:
            return carry
        replicate = jax.jit(
            lambda c: c,
            out_shardings=jax.tree.map(lambda _: self._replicated, carry))
        return jax.tree.map(lambda a: np.asarray(a.addressable_data(0)),
                            replicate(carry))

    def _host_losses(self, closs, sloss):
        if jax.process_count() == 1:
            return super()._host_losses(closs, sloss)
        return (np.asarray(closs.addressable_data(0)),
                np.asarray(sloss.addressable_data(0)))
