"""HLO analyzer correctness: trip-count awareness, dot-flops accounting,
collective parsing (in a multi-device subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline.hlo_analysis import analyze_compiled

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_scan_vs_unrolled_flops_match():
    w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16, 16)),
                    jnp.float32)
    x = jnp.ones((4, 16), jnp.float32)

    def scanned(w, x):
        return jax.lax.scan(lambda c, wi: (jnp.tanh(c @ wi), None), x, w)[0]

    def unrolled(w, x):
        for i in range(8):
            x = jnp.tanh(x @ w[i])
        return x

    cs = analyze_compiled(jax.jit(scanned).lower(w, x).compile())
    cu = analyze_compiled(jax.jit(unrolled).lower(w, x).compile())
    assert cs.dot_flops == cu.dot_flops > 0


def test_dot_flops_formula():
    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 128), jnp.float32)
    c = analyze_compiled(jax.jit(jnp.matmul).lower(a, b).compile())
    assert c.dot_flops == 2 * 32 * 64 * 128


def test_nested_scan_multiplied():
    w = jnp.ones((3, 4, 8, 8), jnp.float32)   # outer 3, inner 4
    x = jnp.ones((2, 8), jnp.float32)

    def f(w, x):
        def outer(c, wo):
            def inner(ci, wi):
                return ci @ wi, None
            return jax.lax.scan(inner, c, wo)[0], None
        return jax.lax.scan(outer, x, w)[0]

    c = analyze_compiled(jax.jit(f).lower(w, x).compile())
    assert c.dot_flops == 3 * 4 * (2 * 2 * 8 * 8)


def test_collective_bytes_parsed():
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.roofline.hlo_analysis import analyze_compiled
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
        with mesh:
            f = jax.jit(jnp.matmul,
                        in_shardings=(NamedSharding(mesh, P(None, "model")),
                                      NamedSharding(mesh, P("model", None))),
                        out_shardings=NamedSharding(mesh, P(None, None)))
            comp = f.lower(a, b).compile()
        c = analyze_compiled(comp)
        print(json.dumps({"coll": c.collectives, "dot": c.dot_flops}))
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # contraction sharded 4-way -> all-reduce of the (128, 64) f32 output
    assert sum(res["coll"].values()) >= 128 * 64 * 4
    # per-device dot flops = full / 4
    assert res["dot"] == 2 * 128 * 256 * 64 / 4


def test_elementwise_not_counted_as_bytes():
    """Fused elementwise chains contribute flops but not HBM bytes."""
    x = jnp.ones((1024,), jnp.float32)
    c = analyze_compiled(jax.jit(
        lambda x: jnp.tanh(x * 2 + 1)).lower(x).compile())
    assert c.elem_flops >= 1024
    assert c.bytes <= 5 * 1024 * 4   # fusion boundary traffic only
