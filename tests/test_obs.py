"""Program spans and device scopes: what a profile and ``last_run_stats``
can attribute a round's time to (docs/ENGINES.md, "Reading a run")."""
import glob
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import TrainSession
from repro.config import HeteroProfile, OptimizerConfig, SplitEEConfig
from repro.core.splitee import MLPSplitModel, ResNetSplitModel
from repro.data.staging import StagedChunkPipeline
from repro.models.resnet import ResNetConfig

SPLITS = (1, 2, 2, 3)


def _session(engine, strategy="averaging", grad_mode="eq1"):
    rng = np.random.default_rng(0)
    n = len(SPLITS)
    x = rng.normal(size=(48 * n, 16)).astype(np.float32)
    y = rng.integers(0, 3, 48 * n).astype(np.int32)
    model = MLPSplitModel(in_dim=16, hidden=32, num_classes=3, num_layers=4,
                          seed=0)
    return TrainSession.from_config(
        model, SplitEEConfig(profile=HeteroProfile(SPLITS),
                             strategy=strategy),
        OptimizerConfig(lr=3e-3, total_steps=50),
        [(x[i::n], y[i::n]) for i in range(n)], batch_size=16,
        engine=engine, grad_mode=grad_mode)


def _scope_tokens(hlo: str):
    """Every name in the ``op_name`` paths of an HLO text; transforms wrap
    a scope as ``vmap(client)`` or ``transpose(jvp(client))``."""
    return {tok for name in re.findall(r'op_name="([^"]*)"', hlo)
            for tok in re.split(r"[/()]", name) if tok}


def _chunk_hlo(session):
    eng, st = session.engine, session.state
    eng.ctx.data.align(st.batches_drawn)
    carry = eng._stack_carry(list(st.clients), list(st.client_opts),
                             list(st.servers), list(st.server_opts))
    xs, ys = eng._stage_chunk(2, 1)
    return eng._chunk_fn(1).lower(carry, eng._put_ts(0, 2), xs,
                                  ys).compile().as_text()


@pytest.mark.parametrize("grad_mode", ["eq1", "sum"])
def test_fused_chunk_ops_carry_the_scopes(grad_mode):
    tokens = _scope_tokens(_chunk_hlo(_session("fused",
                                               grad_mode=grad_mode)))
    assert {"client", "server", "adam", "eq1"} <= tokens


def test_reference_steps_carry_the_scopes():
    s = _session("reference")
    eng, st = s.engine, s.state
    x = jnp.zeros((16, 16), jnp.float32)
    y = jnp.zeros((16,), jnp.int32)
    client, server = st.clients[0], st.servers[0]
    chlo = eng._client_step().lower(client["trainable"], client["state"],
                                    st.client_opts[0], x, y,
                                    1e-3).compile().as_text()
    h = jnp.zeros((16, 32), jnp.float32)
    shlo = eng._server_step(SPLITS[0]).lower(
        server["trainable"], server["state"], st.server_opts[0], h, y,
        1e-3).compile().as_text()
    assert {"client", "adam"} <= _scope_tokens(chlo)
    assert "server" not in _scope_tokens(chlo)
    assert {"server", "adam"} <= _scope_tokens(shlo)
    assert "client" not in _scope_tokens(shlo)


def test_resnet_sides_name_segment_and_heads():
    model = ResNetSplitModel(ResNetConfig(num_classes=3, width_mult=0.125,
                                          image_size=8), seed=0)
    client, server = model.make_client(2), model.make_server(2)
    x = jnp.zeros((2, 8, 8, 3), jnp.float32)

    def fwd_client(tr, st):
        return model.client_forward(tr, st, x, train=True)

    h = jax.eval_shape(fwd_client, client["trainable"], client["state"])[0]

    def fwd_server(tr, st):
        return model.server_forward(tr, st, jnp.zeros(h.shape, h.dtype), 2,
                                    train=True)

    ctok = _scope_tokens(jax.jit(fwd_client).lower(
        client["trainable"], client["state"]).compile().as_text())
    stok = _scope_tokens(jax.jit(fwd_server).lower(
        server["trainable"], server["state"]).compile().as_text())
    assert {"segment", "exit_head"} <= ctok and "head" not in ctok
    assert {"segment", "head"} <= stok and "exit_head" not in stok


def test_span_lands_in_the_profile_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("repro.test.outer"):
            with obs.span("repro.test.inner", obs.Phases()):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"repro.test.outer", "repro.test.inner"} <= names


def test_phases_add_up_across_threads():
    """Two threads close spans into one ``Phases`` at once, with the
    interpreter switching threads as often as it can: no count and no
    second is lost."""
    phases = obs.Phases()
    per_thread = 2000
    seconds = [0.0, 0.0]

    def work(k):
        for _ in range(per_thread):
            with obs.span("repro.test.shared", phases) as sp:
                pass
            seconds[k] += sp.seconds

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    got = phases.as_dict()["repro.test.shared"]
    assert got["n"] == 2 * per_thread
    assert got["s"] == pytest.approx(sum(seconds))


@pytest.mark.parametrize("overlap", [True, False])
def test_stage_stats_are_timed_by_the_spans(overlap):
    phases = obs.Phases()
    p = StagedChunkPipeline(lambda n: n, [1, 2, 3], overlap=overlap,
                            phases=phases)
    try:
        for _ in range(3):
            p.get()
            p.release()
    finally:
        p.close()
    got = phases.as_dict()
    assert got["repro.stage.wait"]["n"] == 3
    assert got["repro.stage.wait"]["s"] == pytest.approx(p.stats.wait_s)
    staged = got["repro.stage.produce" if overlap else "repro.stage.wait"]
    assert staged["s"] == pytest.approx(p.stats.stage_s)


def test_fused_run_stats_count_chunks():
    s = _session("fused")
    s.train(6, chunk_rounds=2)
    stats = s.engine.last_run_stats
    for name in ("repro.stage.fill", "repro.stage.put", "repro.stage.wait",
                 "repro.stage.produce", "repro.fused.dispatch",
                 "repro.fused.loss_sync"):
        assert stats[name]["n"] == 3, name
    for name in ("repro.train", "repro.fused.stack_carry",
                 "repro.fused.unstack_carry"):
        assert stats[name]["n"] == 1, name
    inner = sum(v["s"] for k, v in stats.items()
                if k.startswith("repro.fused."))
    assert 0 < inner <= stats["repro.train"]["s"]
    assert s.engine.last_stage_stats["wait_s"] == pytest.approx(
        stats["repro.stage.wait"]["s"])
    shares = obs.phase_shares(stats)
    assert [v for _, v in shares] == sorted((v for _, v in shares),
                                            reverse=True)
    assert dict(shares)["repro.fused.dispatch"] == pytest.approx(
        100 * stats["repro.fused.dispatch"]["s"] / stats["repro.train"]["s"])
    assert "repro.train" not in dict(shares)


@pytest.mark.parametrize("strategy", ["sequential", "averaging"])
def test_reference_run_stats_count_steps(strategy):
    s = _session("reference", strategy=strategy)
    rounds, epochs = 2, 2
    s.train(rounds, local_epochs=epochs)
    stats = s.engine.last_run_stats
    steps = rounds * len(SPLITS) * epochs
    for name in ("repro.ref.input", "repro.ref.client_step",
                 "repro.ref.server_step", "repro.ref.loss_sync"):
        assert stats[name]["n"] == steps, name
    assert stats["repro.train"]["n"] == 1
    assert stats["repro.ref.schedule"]["n"] == rounds
    assert ("repro.ref.eq1" in stats) == (strategy == "averaging")
    # a later run replaces the stats
    s.train(1, local_epochs=1)
    assert s.engine.last_run_stats["repro.ref.client_step"]["n"] == len(
        SPLITS)
