"""Equivalence contract between the fused (scan+vmap) engine and the
paper-faithful reference engine, plus adaptive-inference threshold edges
shared by both engines.  See docs/ENGINES.md."""
import jax
import numpy as np
import pytest

from repro.api import TrainSession
from repro.config import HeteroProfile, OptimizerConfig, SplitEEConfig
from repro.core.splitee import (MLPSplitModel, ResNetSplitModel, stack_pytrees,
                                 unstack_pytrees)
from repro.models.resnet import ResNetConfig

TOL = 1e-5


def _blob_data(n, d, classes, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, d)) * 2.0
    y = rng.integers(0, classes, n).astype(np.int32)
    x = (centers[y] + rng.normal(size=(n, d))).astype(np.float32)
    return x, y


def _make(engine, strategy, splits=(1, 2, 2, 3), aggregate_every=1,
          grad_mode="eq1"):
    x, y = _blob_data(600, 16, 3)
    n = len(splits)
    model = MLPSplitModel(in_dim=16, hidden=32, num_classes=3, num_layers=4,
                          seed=0)
    parts = [(x[i::n], y[i::n]) for i in range(n)]
    tr = TrainSession.from_config(
        model,
        SplitEEConfig(profile=HeteroProfile(tuple(splits)),
                      strategy=strategy, aggregate_every=aggregate_every),
        OptimizerConfig(lr=3e-3, total_steps=50),
        parts, batch_size=64, engine=engine, grad_mode=grad_mode)
    return tr, (x, y)


def _assert_trees_close(a, b, msg=""):
    jax.tree.map(lambda u, v: np.testing.assert_allclose(
        np.asarray(u), np.asarray(v), atol=TOL, err_msg=msg), a, b)


def _assert_engines_match(ref, fus):
    assert len(ref.history) == len(fus.history)
    for a, b in zip(ref.history, fus.history):
        assert a.round == b.round
        assert abs(a.client_loss - b.client_loss) < TOL
        assert abs(a.server_loss - b.server_loss) < TOL
    for i in range(ref.ctx.N):
        _assert_trees_close(ref.state.clients[i]["trainable"],
                            fus.state.clients[i]["trainable"], f"client {i}")
        _assert_trees_close(ref.state.servers[i]["trainable"],
                            fus.state.servers[i]["trainable"], f"server {i}")
        _assert_trees_close((ref.state.client_opts[i].m,
                             ref.state.client_opts[i].v),
                            (fus.state.client_opts[i].m,
                             fus.state.client_opts[i].v),
                            f"client opt {i}")


# ---------------------------------------------------------------------------
# numerical equivalence to the reference engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["averaging", "distributed"])
def test_fused_matches_reference(strategy):
    """≥3 rounds with E=2 local epochs: params, opt state and per-round
    metrics agree with the per-client reference to ~1e-5."""
    ref, _ = _make("reference", strategy)
    fus, _ = _make("fused", strategy)
    ref.train(4, local_epochs=2)
    fus.train(4, local_epochs=2)
    _assert_engines_match(ref, fus)


def test_fused_matches_reference_aggregate_every_2():
    """aggregate_every=2: rounds 0/2 skip Eq. (1), rounds 1/3 apply it — the
    in-graph masked aggregation must hit exactly the reference boundaries."""
    ref, _ = _make("reference", "averaging", aggregate_every=2)
    fus, _ = _make("fused", "averaging", aggregate_every=2)
    ref.train(4)
    fus.train(4)
    _assert_engines_match(ref, fus)
    # boundary really aggregated: deepest common layers identical
    for key in ("layer4", "head"):
        w0 = np.asarray(fus.state.servers[0]["trainable"][key]["w"])
        for s in fus.state.servers[1:]:
            np.testing.assert_allclose(w0, np.asarray(s["trainable"][key]["w"]),
                                       atol=1e-6)


def test_fused_chunked_matches_single_chunk():
    """Chunking the scan (chunk_rounds) must not change the trajectory."""
    one, _ = _make("fused", "averaging", aggregate_every=2)
    many, _ = _make("fused", "averaging", aggregate_every=2)
    one.train(6)
    many.train(6, chunk_rounds=2)
    _assert_engines_match(one, many)


def test_auto_chunk_rounds_respects_stage_budget():
    """chunk_rounds=0 is a *budgeted* default, not stage-everything: when
    the whole run's pre-staged tensors would blow the budget, the engine
    picks the largest chunk that fits (floor 1) — without changing the
    trajectory."""
    one, _ = _make("fused", "averaging", aggregate_every=2)
    auto, _ = _make("fused", "averaging", aggregate_every=2)
    eng = auto.engine
    per_round = eng._round_stage_bytes(local_epochs=1)
    # 4 clients x 64x16 f32 x + 64 i32 y = 4 * (64*16*4 + 64*4)
    assert per_round == 4 * (64 * 16 * 4 + 64 * 4)
    # a budget of ~2.5 rounds -> chunks of 2; floor at 1 when even one
    # round exceeds the budget; whole run when it fits
    eng.stage_budget_bytes = int(2.5 * per_round)
    assert eng._auto_chunk_rounds(6, 1) == 2
    assert eng._auto_chunk_rounds(1, 1) == 1
    eng.stage_budget_bytes = per_round - 1
    assert eng._auto_chunk_rounds(6, 1) == 1
    eng.stage_budget_bytes = 100 * per_round
    assert eng._auto_chunk_rounds(6, 1) == 6
    assert eng._auto_chunk_rounds(6, 2) == 6   # 2x data still fits
    # trained under the tight budget, the trajectory is unchanged
    eng.stage_budget_bytes = int(2.5 * per_round)
    one.train(6)
    auto.train(6)                              # chunk_rounds=0 -> auto
    _assert_engines_match(one, auto)


def test_fused_sum_grad_mode_matches_eq1():
    """The split-boundary stop_gradient decouples the client/server
    parameter families, so the 'sum' mode's single fused backward computes
    the same gradients as the two-pass 'eq1' routing on the split-net
    adapters (the modes differ only in how the backward is staged)."""
    eq1, _ = _make("fused", "averaging")
    summ, _ = _make("fused", "averaging", grad_mode="sum")
    eq1.train(3, local_epochs=2)
    summ.train(3, local_epochs=2)
    _assert_engines_match(eq1, summ)


def test_reference_rejects_sum_grad_mode():
    with pytest.raises(ValueError, match="eq1"):
        _make("reference", "averaging", grad_mode="sum")


def test_fused_rejects_sequential():
    with pytest.raises(ValueError, match="[Ss]equential"):
        _make("fused", "sequential")


def test_fused_rejects_ragged_cohort_batches():
    """Two clients share a cut layer but batch_iterator clamps one shard
    below batch_size — lanes can't stack, so construction must fail loudly
    (the reference engine still handles this profile)."""
    x, y = _blob_data(200, 16, 3)
    model = MLPSplitModel(in_dim=16, hidden=32, num_classes=3, num_layers=4)
    parts = [(x[:100], y[:100]), (x[100:140], y[100:140])]   # 100 vs 40
    cfg = SplitEEConfig(profile=HeteroProfile((2, 2)), strategy="averaging")
    with pytest.raises(ValueError, match="batch"):
        TrainSession.from_config(model, cfg, OptimizerConfig(), parts,
                                 batch_size=64, engine="fused")
    TrainSession.from_config(model, cfg, OptimizerConfig(), parts,
                             batch_size=64,
                             engine="reference").train(1)    # oracle is fine


def test_stack_unstack_roundtrip():
    model = MLPSplitModel(in_dim=8, hidden=16, num_classes=3, num_layers=4)
    clients = [model.make_client(2) for _ in range(3)]
    stacked = model.stack_clients(clients)
    w = stacked["trainable"]["layers"]["layer1"]["w"]
    assert w.shape[0] == 3
    back = model.unstack(stacked, 3)
    for a, b in zip(clients, back):
        _assert_trees_close(a, b)
    # module-level helpers agree with the adapter methods
    _assert_trees_close(stack_pytrees(clients), stacked)
    for a, b in zip(unstack_pytrees(stacked, 3), back):
        _assert_trees_close(a, b)


# ---------------------------------------------------------------------------
# the carry swap: one compiled program each way, bit-identical to the eager
# per-leaf composition
# ---------------------------------------------------------------------------


def _trained_session(kind):
    """A fused session one round in, so BatchNorm statistics and Adam step
    counts have left their initial values."""
    if kind == "mlp":
        tr, _ = _make("fused", "averaging")
    else:
        rng = np.random.default_rng(0)
        x = rng.normal(size=(96, 8, 8, 3)).astype(np.float32)
        y = rng.integers(0, 3, 96).astype(np.int32)
        model = ResNetSplitModel(ResNetConfig(num_classes=3, width_mult=0.125,
                                              image_size=8), seed=0)
        tr = TrainSession.from_config(
            model, SplitEEConfig(profile=HeteroProfile((2, 2, 3)),
                                 strategy="averaging"),
            OptimizerConfig(lr=1e-3, total_steps=10),
            [(x[i::3], y[i::3]) for i in range(3)], batch_size=16,
            engine="fused")
    tr.train(1)
    return tr


def _state_lists(state):
    return (list(state.clients), list(state.client_opts),
            list(state.servers), list(state.server_opts))


def _assert_trees_identical(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert u.dtype == v.dtype and u.shape == v.shape
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("kind", ["mlp", "resnet"])
def test_stack_program_matches_eager_composition(kind):
    tr = _trained_session(kind)
    eng = tr.engine
    args = _state_lists(tr.state)
    carry = eng._stack_carry(*args)
    assert eng._stack_fn._cache_size() == 1        # the one-program path
    # the same composition, run eagerly leaf by leaf
    _assert_trees_identical(carry, eng._stack_lanes(*args))
    for li in eng._cohort_lis:
        assert int(np.asarray(carry[li][1].step)[0]) == 1
        if kind == "resnet":
            assert jax.tree.leaves(carry[li][0]["state"])       # BatchNorm
            assert jax.tree.leaves(carry[li][2]["state"])


@pytest.mark.parametrize("kind", ["mlp", "resnet"])
def test_unstack_program_matches_unstack_pytrees(kind):
    tr = _trained_session(kind)
    eng = tr.engine
    args = _state_lists(tr.state)
    carry = eng._stack_carry(*args)
    outs = tuple([None] * tr.ctx.N for _ in range(4))
    eng._unstack_carry(carry, *outs)
    assert eng._unstack_fn._cache_size() == 1      # the one-program path
    for li in eng._cohort_lis:
        lanes = eng._lanes[li]
        want = [unstack_pytrees(t, len(lanes)) for t in carry[li]]
        for j, i in enumerate(lanes):
            for k in range(4):
                _assert_trees_identical(outs[k][i], want[k][j])
                _assert_trees_identical(outs[k][i], args[k][i])


def test_host_carry_unstacks_to_numpy():
    """A host carry (what the multi-process spmd fetch returns) is sliced
    on the host: per-client leaves stay numpy, and no program runs."""
    tr = _trained_session("mlp")
    eng = tr.engine
    args = _state_lists(tr.state)
    host = jax.device_get(eng._stack_carry(*args))
    outs = tuple([None] * tr.ctx.N for _ in range(4))
    traced = eng._unstack_fn._cache_size()
    eng._unstack_carry(host, *outs)
    assert eng._unstack_fn._cache_size() == traced
    for k in range(4):
        for i in range(tr.ctx.N):
            leaves = jax.tree.leaves(outs[k][i])
            # a lane of a stacked scalar reads back as a numpy scalar
            assert all(isinstance(x, (np.ndarray, np.generic))
                       for x in leaves)
            _assert_trees_identical(outs[k][i], args[k][i])


def test_successive_runs_trace_each_swap_program_once():
    tr, _ = _make("fused", "averaging")
    eng = tr.engine
    for _ in range(2):
        tr.train(2)
        stats = eng.last_run_stats
        assert stats["repro.fused.stack_carry"]["n"] == 1
        assert stats["repro.fused.unstack_carry"]["n"] == 1
    assert eng._stack_fn._cache_size() == 1
    assert eng._unstack_fn._cache_size() == 1


# ---------------------------------------------------------------------------
# evaluate_adaptive threshold edges (both engines share the implementation)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["reference", "fused"])
def test_adaptive_tau_zero_is_pure_server(engine):
    """tau=0: entropy H >= 0 is never < 0, so nothing exits at the client —
    accuracy must equal the server-side path."""
    tr, (x, y) = _make(engine, "averaging")
    tr.train(3)
    ad = tr.evaluate_adaptive(x[:300], y[:300], tau=0.0, batch_size=100)
    assert ad["client_ratio"] == [0.0] * tr.ctx.N
    ev = tr.evaluate(x[:300], y[:300], batch_size=100)
    np.testing.assert_allclose(ad["acc"], ev["server_acc"], atol=1e-6)


@pytest.mark.parametrize("engine", ["reference", "fused"])
def test_adaptive_tau_above_max_entropy_is_pure_client(engine):
    """tau > log(num_classes) >= max H: every sample exits at the client."""
    tr, (x, y) = _make(engine, "averaging")
    tr.train(3)
    tau = float(np.log(3)) + 0.1
    ad = tr.evaluate_adaptive(x[:300], y[:300], tau=tau, batch_size=100)
    assert ad["client_ratio"] == [1.0] * tr.ctx.N
    ev = tr.evaluate(x[:300], y[:300], batch_size=100)
    np.testing.assert_allclose(ad["acc"], ev["client_acc"], atol=1e-6)
