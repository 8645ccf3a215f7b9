"""Distributed correctness tests.

These run in a SUBPROCESS with XLA_FLAGS forcing 8 host devices so the main
pytest session keeps its single-device jax runtime untouched."""
import json
import os
import subprocess
import sys
import textwrap

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_tp_square_matmul_equivalence():
    """Paper correction-term fusion under tensor parallelism (DESIGN §6):
    a square-mode GEMM with the contraction axis sharded must equal the
    unsharded result."""
    res = _run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import matmul as M
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
        ref = np.asarray(a @ b)
        errs = {}
        with mesh:
            for mode in ("square_virtual", "square_scan"):
                f = jax.jit(lambda a, b: M.matmul(a, b, mode=mode),
                            in_shardings=(NamedSharding(mesh, P("data", "model")),
                                          NamedSharding(mesh, P("model", None))))
                out = np.asarray(f(a, b))
                errs[mode] = float(np.abs(out - ref).max())
        print(json.dumps(errs))
    """))
    assert res["square_virtual"] < 1e-3
    assert res["square_scan"] < 1e-3


def test_sharded_train_step_matches_single_device():
    """One train step on a (2, 4) mesh == the same step on 1 device."""
    res = _run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.lm import build_model
        from repro.optim import adamw
        from repro.train import step as step_mod
        from repro.distributed import sharding as shd, context as dctx
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_config("deepseek-7b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.adamw_init(params)
        tcfg = step_mod.TrainConfig(opt=adamw.AdamWConfig(lr=1e-3,
            warmup_steps=1, total_steps=10))
        data = SyntheticLM(DataConfig(global_batch=8, seq_len=16,
                                      vocab=cfg.vocab), cfg)
        batch = data.next_batch()
        # single device
        ts = jax.jit(step_mod.make_train_step(model, tcfg))
        p1, _, m1 = ts(params, opt, batch)
        # sharded
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        pshard = shd.param_shardings(mesh, model.spec())
        ibs = shd.input_shardings(mesh, batch)
        with mesh, dctx.use_mesh(mesh):
            tss = jax.jit(step_mod.make_train_step(model, tcfg),
                          in_shardings=(pshard, None, ibs),
                          out_shardings=(pshard, None, None))
            p2, _, m2 = tss(params, opt, batch)
        d = jax.tree.reduce(max, jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            p1, p2))
        print(json.dumps({"loss1": float(m1["loss"]),
                          "loss2": float(m2["loss"]), "param_delta": d}))
    """))
    assert abs(res["loss1"] - res["loss2"]) < 1e-3
    assert res["param_delta"] < 5e-3


def test_moe_shard_map_matches_local():
    """MoE through shard_map (tokens data-sharded, experts TP on mlp axis)
    == the purely local MoE."""
    res = _run(textwrap.dedent("""
        import json, dataclasses as dc, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.lm import build_model
        from repro.distributed import sharding as shd, context as dctx
        cfg = get_config("mixtral-8x7b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                       jnp.int32)}
        h1, _, _ = model.forward(params, batch)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        pshard = shd.param_shardings(mesh, model.spec())
        ibs = shd.input_shardings(mesh, batch)
        with mesh, dctx.use_mesh(mesh):
            f = jax.jit(lambda p, b: model.forward(p, b)[0],
                        in_shardings=(pshard, ibs))
            h2 = f(params, batch)
        err = float(jnp.max(jnp.abs(h1 - h2)))
        print(json.dumps({"err": err}))
    """))
    assert res["err"] < 2e-2


def test_logical_rules_drop_indivisible():
    """kv=1 / 8-head tensors replicate instead of crashing on a 4-way model
    axis; vocab/mlp still shard."""
    res = _run(textwrap.dedent("""
        import json, jax
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.models.lm import build_model
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("paligemma-3b")      # kv=1, 8 heads, big vocab/mlp
        model = build_model(cfg)
        sh = shd.param_shardings(mesh, model.spec())
        flat = jax.tree_util.tree_leaves_with_path(sh)
        out = {}
        for path, s in flat:
            key = "/".join(str(p.key) for p in path if hasattr(p, "key"))
            out[key] = str(s.spec)
        print(json.dumps({
            "embed": out.get("embed/table"),
            "wk": out.get("scan/pos0/attn/wk/w"),
            "ffn_up": out.get("scan/pos0/ffn/w_up/w"),
        }))
    """))
    assert "model" in res["embed"]            # vocab sharded
    assert "model" in res["ffn_up"]           # mlp sharded
    assert "model" not in (res["wk"] or "")   # kv=1: replicated, not crashed
