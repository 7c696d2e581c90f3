"""Cross-layer aggregation — paper Eq. (1).

For every layer ``l`` of the full network, the participation set
``C_l = {i | l_i < l}`` (clients whose *server-side* model contains layer l)
averages its parameters; the mean is broadcast back to every member.  Models
are dicts keyed by layer name (``layer4``, ``head``, ...) so "common layers"
are identified by key across heterogeneous server models.

Three implementations:
  * ``cross_layer_aggregate``      — literal per-client loop (the reference,
    used by the paper-faithful Averaging strategy and by the test oracle).
  * ``masked_mean_over_axis``      — the SPMD collective form: a weighted
    ``psum`` over a mesh axis with per-layer participation masks, used by the
    production fused step (see core/spmd.py and docs/DESIGN.md §2).
  * ``stacked_cross_layer_aggregate`` — the in-graph form over
    cohort-stacked server models, traceable inside ``lax.scan``; the fused
    engine (repro.api.fused_engine) applies it under a ``lax.cond`` on the traced
    ``aggregate_every`` boundary predicate so aggregation never forces a
    host sync.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp


def _mean_trees(trees: Sequence[Any]) -> Any:
    n = float(len(trees))
    return jax.tree.map(lambda *xs: sum(x.astype(jnp.float32) for x in xs)
                        .astype(xs[0].dtype) / n, *trees)


@jax.named_scope("eq1")
def cross_layer_aggregate(server_models: Sequence[Dict[str, Any]],
                          split_layers: Sequence[int],
                          extra_shared_keys: Sequence[str] = ("head",),
                          ) -> List[Dict[str, Any]]:
    """Aggregate client-specific server models (Alg. 2 lines 20-30).

    server_models[i] is a dict whose keys are the layers client i's server
    model contains: ``layer{l}`` for l in (l_i, L] (1-indexed, paper naming)
    plus the keys in ``extra_shared_keys`` which every server model has.
    Returns NEW server models with common layers replaced by the mean.
    """
    assert len(server_models) == len(split_layers)
    out = [dict(m) for m in server_models]

    all_keys = set()
    for m in server_models:
        all_keys |= set(m.keys())

    for key in sorted(all_keys):
        members = [i for i, m in enumerate(server_models) if key in m]
        if len(members) <= 1:
            continue
        mean = _mean_trees([server_models[i][key] for i in members])
        for i in members:
            out[i][key] = mean
    return out


@jax.named_scope("eq1")
def stacked_cross_layer_aggregate(stacked: Dict[int, Dict[str, Any]],
                                  counts: Dict[int, int]
                                  ) -> Dict[int, Dict[str, Any]]:
    """Eq. (1) over cohort-stacked server models, inside the compiled graph.

    ``stacked[li]`` is the server model of the cohort with split layer ``li``,
    keyed by layer name, every leaf carrying a leading lane axis of size
    ``counts[li]`` (one lane per client).  For each layer key the mean is
    taken over *all* lanes of *all* cohorts containing that key — the same
    participation set C_l as :func:`cross_layer_aggregate` — and broadcast
    back to every member lane.  Keys held by a single client pass through
    unchanged.  Callers gate ``aggregate_every`` boundaries around this
    (e.g. ``lax.cond`` in repro.api.fused_engine) so no host round-trip is
    needed.  Under the spmd engine's recipe shardings the lane-dim
    ``jnp.sum`` is a reduce over the mesh's ``"lanes"`` axis and the
    broadcast re-materializes each lane's shard — XLA's partitioner emits
    the collectives; the math is identical to the single-device form.
    """
    out = {li: dict(m) for li, m in stacked.items()}
    all_keys = set()
    for m in stacked.values():
        all_keys |= set(m.keys())

    for key in sorted(all_keys):
        members = [li for li, m in stacked.items() if key in m]
        total = sum(counts[li] for li in members)
        if total <= 1:
            continue
        # lane-sum within each member cohort, then mean across cohorts
        sums = [jax.tree.map(lambda x: jnp.sum(x.astype(jnp.float32), axis=0),
                             stacked[li][key]) for li in members]
        mean = jax.tree.map(lambda *xs: sum(xs) / float(total), *sums)
        for li in members:
            out[li][key] = jax.tree.map(
                lambda old, m_: jnp.broadcast_to(
                    m_.astype(old.dtype)[None], old.shape),
                stacked[li][key], mean)
    return out


@jax.named_scope("eq1")
def masked_stacked_cross_layer_aggregate(stacked: Dict[int, Dict[str, Any]],
                                         masks: Dict[int, Any],
                                         counts: Dict[int, int]
                                         ) -> Dict[int, Dict[str, Any]]:
    """:func:`stacked_cross_layer_aggregate` restricted to a traced
    participation set (the client-population engines, docs/ENGINES.md).

    ``masks[li]`` is the cohort's ``[counts[li]]`` 0/1 lane mask for the
    aggregation boundary's round.  For each shared layer key the mean runs
    over *active* lanes only — masked clients are excluded from both the
    numerator and the denominator — and is broadcast back to **every**
    member lane, active or not: a client rejoining after churn resumes
    from the aggregate, the FedAvg synchronization semantics.  When no
    member of a key is active its parameters pass through unchanged
    (guarded ``where`` on the traced denominator).  With all-ones masks
    this reduces bit-for-bit to the unmasked form: ``x * 1.0`` is exact
    and the denominators agree, so full-participation population runs
    match fixed-cohort runs (the participation-parity CI gate).

    Static structure (which cohorts hold which key) still comes from
    ``counts``; only the *membership within* that structure is traced —
    shapes never depend on the mask, so churn never recompiles."""
    out = {li: dict(m) for li, m in stacked.items()}
    all_keys = set()
    for m in stacked.values():
        all_keys |= set(m.keys())

    def bcast(mask, leaf):
        return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))

    for key in sorted(all_keys):
        members = [li for li, m in stacked.items() if key in m]
        total = sum(counts[li] for li in members)
        if total <= 1:
            continue
        den = sum(jnp.sum(masks[li]) for li in members)
        sums = [jax.tree.map(
            lambda x: jnp.sum(x.astype(jnp.float32)
                              * bcast(masks[li], x), axis=0),
            stacked[li][key]) for li in members]
        mean = jax.tree.map(lambda *xs: sum(xs) / jnp.maximum(den, 1.0),
                            *sums)
        for li in members:
            out[li][key] = jax.tree.map(
                lambda old, m_: jnp.where(
                    den > 0,
                    jnp.broadcast_to(m_.astype(old.dtype)[None], old.shape),
                    old),
                stacked[li][key], mean)
    return out


def participation_counts(split_layers: Sequence[int], num_layers: int):
    """For each 0-indexed layer l: (#clients with l client-side,
    #clients with l server-side).  Client i holds layers [0, l_i)."""
    n_client = [sum(1 for s in split_layers if l < s) for l in range(num_layers)]
    n_server = [len(split_layers) - c for c in n_client]
    return n_client, n_server


def masked_mean_over_axis(value: jnp.ndarray, participate: jnp.ndarray,
                          axis_name: str) -> jnp.ndarray:
    """SPMD Eq. (1): mean of ``value`` over the mesh axis restricted to
    shards where ``participate`` (0/1 scalar) is set.  The mean is broadcast
    back to the members of C_l only (paper Alg. 2 line 25); non-members keep
    their value unchanged."""
    num = jax.lax.psum(value * participate, axis_name)
    den = jax.lax.psum(participate, axis_name)
    mean = num / jnp.maximum(den, 1.0)
    return jnp.where((participate > 0) & (den > 0), mean, value)
