"""End-to-end behaviour tests for the whole system.

Covers: the paper's full workflow (YAML space -> sampled trials -> dynamic
models -> staged criteria with HIL latency -> study results), the training
driver with kill/resume fault tolerance, the serving driver, and the
gradient-compression training path.
"""
import json
import os
import subprocess
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ENV = {**os.environ, "PYTHONPATH": SRC}


def test_paper_workflow_end_to_end(tmp_path):
    """Listing-3-style NAS with staged criteria + pruning + storage."""
    from repro.core.builder import ModelBuilder
    from repro.core.space import parse_search_space
    from repro.core.translate import sample_architecture
    from repro.data.pipeline import SyntheticClassificationData
    from repro.evaluation import (
        CompiledLatencyEstimator,
        CriteriaRunner,
        OptimizationCriteria,
        ParamCountEstimator,
        TrainedAccuracyEstimator,
    )
    from repro.search import Study, TPESampler

    space = parse_search_space("""
input: [2, 128]
output: 4
sequence:
  - block: "features"
    op_candidates: "conv-block"
    type_repeat:
      type: "vary_all"
      depth: [1, 2]
  - block: "head"
    op_candidates: "linear"
    linear:
      width: [16, 32]
default_op_params:
  conv1d:
    kernel_size: [3]
    out_channels: [4, 8]
composites:
  conv-block:
    sequence:
      - block: "c"
        op_candidates: "conv1d"
      - block: "p"
        op_candidates: ["maxpool", "identity"]
preprocessing:
  normalize:
    kind: ["zscore"]
""")
    data = SyntheticClassificationData(n=160, length=128, channels=2, classes=4).split()
    builder = ModelBuilder(space.input_shape, space.output_dim)
    runner = CriteriaRunner([
        OptimizationCriteria(ParamCountEstimator(), kind="hard_constraint", limit=5e5),
        OptimizationCriteria(TrainedAccuracyEstimator(steps=25, batch=16),
                             kind="objective", direction="maximize"),
        OptimizationCriteria(CompiledLatencyEstimator("host_cpu", batch=4),
                             kind="soft_constraint", limit=0.05, weight=0.2),
    ])
    storage = os.path.join(tmp_path, "study.jsonl")
    study = Study(sampler=TPESampler(seed=0, n_startup=3), storage=storage)

    def objective(trial):
        arch = sample_architecture(space, trial)
        model = builder.build(arch)
        return runner.evaluate(model, context={"data": data, "trial": trial}, trial=trial)

    study.optimize(objective, 6)
    done = study.completed_trials
    assert done, "no trial completed"
    best = study.best_trial
    assert best.user_attrs["val_accuracy"] > 0.3  # learned something
    assert best.user_attrs["n_params"] <= 5e5
    # storage survives
    study2 = Study(storage=storage)
    assert len(study2.trials) == 6


def _run(args, timeout=600, **kw):
    return subprocess.run(args, env=ENV, timeout=timeout, capture_output=True,
                          text=True, **kw)


def test_train_driver_resume_after_kill(tmp_path):
    ckpt = os.path.join(tmp_path, "ck")
    base = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-1.7b",
            "--smoke", "--seq", "32", "--global-batch", "2", "--ckpt-dir", ckpt,
            "--ckpt-every", "5", "--log-every", "100"]
    r1 = _run(base + ["--steps", "12"])
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = _run(base + ["--steps", "20"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step" in r2.stdout
    final = json.loads(r2.stdout.strip().splitlines()[-1])
    assert np.isfinite(final["final_loss"])


def test_serve_driver(tmp_path):
    r = _run([sys.executable, "-m", "repro.launch.serve", "--arch", "xlstm-1.3b",
              "--smoke", "--requests", "2", "--max-batch", "2",
              "--prompt-lens", "8", "--gen-lens", "6"])
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["served"] == 2 and out["shed"] == 0
    assert out["tokens_generated"] == 2 * 6


def test_train_with_compression():
    r = _run([sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-1.7b",
              "--smoke", "--steps", "8", "--seq", "32", "--global-batch", "2",
              "--compression", "--log-every", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    final = json.loads(r.stdout.strip().splitlines()[-1])
    assert np.isfinite(final["final_loss"])


def test_dryrun_single_cell_small_mesh():
    """Integration: the dry-run machinery on an 8-device spoofed host."""
    code = (
        "import os; os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8';\n"
        "import jax, functools, jax.numpy as jnp\n"
        "from repro.launch import mesh as M\n"
        "M.make_production_mesh = lambda multi_pod=False: M.make_mesh((2,4), ('data','model'))\n"
        "from repro.launch.dryrun import build_cell\n"
        "step, args, in_sh, out_sh, mesh, meta = build_cell('qwen3-1.7b', 'train_4k', False, cost_variant=True, n_units=2, overrides={'remat': False})\n"
        "lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args)\n"
        "c = lowered.compile()\n"
        "print('flops', c.cost_analysis().get('flops'))\n"
    )
    r = _run([sys.executable, "-c", code], timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "flops" in r.stdout


def test_compile_cache_placement(monkeypatch):
    """Entry points keep JAX's persistent cache where
    JAX_COMPILATION_CACHE_DIR says, else at one fixed path inside the
    checkout."""
    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.place_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # left alone

        monkeypatch.delenv(compile_cache.ENV)
        placed = compile_cache.place_compile_cache()
        checkout = os.path.dirname(os.path.abspath(SRC))
        assert placed == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
        assert os.environ[compile_cache.ENV] == placed  # inherited by workers
        assert compile_cache.place_compile_cache() == placed  # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
