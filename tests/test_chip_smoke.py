"""``chip_smoke.py`` off the chip: its phase functions at tiny sizes on the
CPU (Pallas in interpret mode), so their control flow and checks are
guarded without a TPU; the platform check lives only in ``main()``, which
must refuse to run here."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY_RESNET = ("--smoke", "--train-size", "96", "--test-size", "24",
               "--batch", "8")


def test_resnet18_phase_tiny():
    r = chip_smoke.resnet18_phase(*TINY_RESNET)
    assert r["engine"].startswith("fused")
    assert len(r["losses"]) == 3 and np.isfinite(r["losses"]).all()
    assert len(r["server_acc"]) == 12
    assert r["round_s"] > 0 and r["compile_s"] > 0


@pytest.mark.parametrize("arch", chip_smoke.BACKBONES)
def test_backbone_phase_tiny(arch):
    serve = arch == "glm4_9b"
    r = chip_smoke.backbone_phase(arch, serve=serve)
    # interpret mode on the CPU: no Mosaic call, which main() would refuse
    assert r["native_kernels"] is False
    assert len(r["losses"]) == 2
    assert r["loss_delta"] <= chip_smoke.KERNEL_ATOL
    if serve:
        assert r["serve"]["requests"] == chip_smoke.SERVE_REQUESTS
        assert r["serve"]["tokens"] == (chip_smoke.SERVE_REQUESTS
                                        * chip_smoke.DECODE_TOKENS)
        assert r["serve"]["entropy_delta"] <= chip_smoke.ENTROPY_TOL


def test_round_program_records_the_compiled_round():
    """The compile seam keeps one compiled executable per argument
    signature: a rerun with the same shapes reuses it."""
    run = chip_smoke.train_run(chip_smoke.cli_args(
        "--arch", "rwkv6_3b", *chip_smoke.BACKBONE_ARGV, "--kernels", "ref",
        "--rounds", "3"))
    assert len(run.program.hlo) == 1 and "HloModule" in run.program.hlo[0]
    assert len(run.losses) == 3


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """A cache dir set from outside is left to JAX; otherwise the cache is
    the fixed ``<checkout>/.jax_cache``."""
    import jax

    from repro.launch import compile_cache

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        if from_env:
            assert jax.config.jax_compilation_cache_dir == was
        else:
            assert got == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_script_alone_fails(tmp_path):
    """Copied away from the repo the script cannot import the program: it
    exits non-zero and prints no result line."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


SPMD_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
r = chip_smoke.spmd_phase(*sys.argv[2:])
print(json.dumps(r, default=str))
"""


def test_spmd_phase_on_host_mesh():
    """The --chips 4 phase on four CPU devices: the lanes axis splits
    every cohort (at smoke width no leaf is large enough for FSDP), and the
    spmd losses track the fused ones."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-c", SPMD_SCRIPT, ROOT, *TINY_RESNET,
         "--train-size", "192"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["devices"] == 4
    assert r["mesh"] == {"lanes": 2, "data": 2, "model": 1}
    assert r["max_split"] == 2
    assert r["carry_fraction_per_device"] <= 0.5
