"""Extensions: approximate squaring (paper conclusion) and elastic-scaling
checkpoint restore (mesh-agnostic format)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import matmul as M
from repro.core import squares as sq

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_approx_square_zero_bits_is_exact():
    x = jnp.asarray(np.random.default_rng(0).integers(-128, 128, 64), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(sq.square_approx(x, drop_bits=0)), np.asarray(sq.square(x)))


def test_approx_matmul_error_monotone_in_drop_bits():
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(-128, 128, (32, 64)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, (64, 16)), jnp.int8)
    exact = np.asarray(a, np.int64) @ np.asarray(b, np.int64)
    errs = []
    for db in (0, 2, 4, 6):
        out = np.asarray(M.pm_matmul_approx(a, b, drop_bits=db), np.int64)
        errs.append(np.abs(out - exact).mean())
    assert errs[0] == 0                      # exact squarer == exact matmul
    assert errs == sorted(errs)              # error grows with truncation


def test_approx_float_bf16_squarer_small_error():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    out = np.asarray(M.pm_matmul_approx(a, b))
    ref = np.asarray(a) @ np.asarray(b)
    assert np.abs(out - ref).max() / np.abs(ref).max() < 0.05


def test_elastic_restore_across_device_counts(tmp_path):
    """Checkpoint written from an 8-device sharded training state restores
    on a single device and continues training (the elastic-scaling
    contract of the mesh-agnostic format)."""
    ckpt = str(tmp_path)
    code = textwrap.dedent(f"""
        import jax, json
        from repro.configs import get_config
        from repro.models.lm import build_model
        from repro.optim import adamw
        from repro.train import step as step_mod
        from repro.train.trainer import Trainer, TrainerConfig
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.distributed import sharding as shd, context as dctx

        cfg = get_config("deepseek-7b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        pshard = shd.param_shardings(mesh, model.spec())
        params = jax.device_put(params, pshard)
        tcfg = step_mod.TrainConfig(opt=adamw.AdamWConfig(
            lr=1e-3, warmup_steps=1, total_steps=10))
        with mesh, dctx.use_mesh(mesh):
            ts = jax.jit(step_mod.make_train_step(model, tcfg))
            data = SyntheticLM(DataConfig(global_batch=8, seq_len=16,
                                          vocab=cfg.vocab), cfg)
            tr = Trainer(TrainerConfig(total_steps=3, ckpt_every=3,
                                       ckpt_dir={ckpt!r}),
                         ts, params, adamw.adamw_init(params), data)
            out = tr.run()
        print(json.dumps({{"step": out["final_step"]}}))
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.strip().splitlines()[-1])["step"] == 3

    # restore IN THIS process (1 CPU device) and continue
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models.lm import build_model
    from repro.optim import adamw
    from repro.train import step as step_mod
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tcfg = step_mod.TrainConfig(opt=adamw.AdamWConfig(
        lr=1e-3, warmup_steps=1, total_steps=10))
    ts = jax.jit(step_mod.make_train_step(model, tcfg))
    data = SyntheticLM(DataConfig(global_batch=8, seq_len=16,
                                  vocab=cfg.vocab), cfg)
    tr = Trainer(TrainerConfig(total_steps=6, ckpt_every=100, ckpt_dir=ckpt),
                 ts, params, adamw.adamw_init(params), data)
    assert tr.maybe_resume()
    assert tr.step == 3
    out = tr.run()
    assert out["final_step"] == 6
    assert np.isfinite([m["loss"] for m in out["metrics"]]).all()
