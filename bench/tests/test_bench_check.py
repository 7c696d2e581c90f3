"""The correctness check at a size a test run holds, on the CPU: the plain
reference agrees with ``TrainSession`` on both engines, and a run whose
timed path is broken underneath, or the bfloat16 control, comes out not
correct."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness

SEED = 2**31 + 77


def small(cfg, work):
    """Smoke width on 16x16 images, three clients at the three cuts."""
    cfg["program"].update(builder="repro.models.resnet.ResNetConfig",
                          args={"width_mult": 0.125, "image_size": 16})
    cfg["model"].update(channels=[8, 8, 8, 16, 32, 64], image_size=16,
                        width_mult=0.125)
    cfg["data"].update(image_size=16, train_size=3 * 8 * 6)
    work["job"].update(splits=[3, 4, 5], batch=8, chunk_rounds=2)


@pytest.fixture
def run(monkeypatch, capsys, tmp_path):
    # no persistent compile cache in the test process, and the option the
    # run sets is put back afterwards
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    before = jax.config.jax_persistent_cache_min_compile_time_secs

    def go(cell):
        capsys.readouterr()
        rc = harness.run(["--workload", cell, "--seed", str(SEED),
                          "--seconds", "0.2", "--trace", "0"],
                         time.perf_counter(), require_chip=False, edit=small)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])

    yield go
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


@pytest.mark.parametrize("cell", ["cifar10.avg-t4", "cifar10.seq-t4"])
def test_reference_agrees_with_the_session(run, cell):
    result = run(cell)
    assert result["correct"] is True
    assert list(result)[-1] == "check"
    assert result["check"]
    for number in result["check"].values():
        # float32 at full precision on both sides of the CPU run
        assert number["value"] < 1e-4, result["check"]
    assert result["metrics"]["train_samples_per_s"]["value"] > 0


def _unchanged_state(monkeypatch):
    from repro.api.fused_engine import FusedEngine
    real = FusedEngine.run

    def run(self, state, rounds, **kw):
        return state, real(self, state, rounds, **kw)[1]
    monkeypatch.setattr(FusedEngine, "run", run)


def _half_batch(monkeypatch):
    import repro.core.strategies as strategies
    real = strategies.softmax_cross_entropy

    def half(logits, labels, mask=None):
        n = logits.shape[0] // 2
        return real(logits[:n], labels[:n])
    monkeypatch.setattr(strategies, "softmax_cross_entropy", half)


def _label_altered(monkeypatch):
    import repro.api.engines as engines
    real = engines.batch_iterator

    def altered(*a, **kw):
        for x, y in real(*a, **kw):
            y = y.copy()
            y[0] = (y[0] + 1) % 10
            yield x, y
    monkeypatch.setattr(engines, "batch_iterator", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _label_altered])
def test_a_broken_timed_path_is_not_correct(run, monkeypatch, fault):
    fault(monkeypatch)
    result = run("cifar10.avg-t4")
    assert result["correct"] is False, result["check"]


def test_the_bfloat16_control_is_not_correct():
    cfg = harness.config("resnet18-cifar10")
    work = harness.workload("cifar10.avg-t4")
    small(cfg, work)
    traffic, reference, _ = harness.cell_parts(cfg, work)
    job, chunk = work["job"], work["job"]["chunk_rounds"]
    shards = traffic.cell_data(SEED, cfg["data"], len(job["splits"]))
    batches = harness.first_batches(traffic, job, SEED, shards, chunk)
    net = cfg["model"]
    ref = reference.run_rounds(net, job, SEED, batches, chunk)
    control = reference.run_rounds(net, job, SEED, batches, chunk,
                                   dtype=jnp.bfloat16)
    numbers = check.compare(control, ref, ref["initial"])
    assert not check.verdict(numbers, work["limits"]), numbers
    assert np.all(np.isfinite([numbers[n] for n in check.NUMBERS]))
