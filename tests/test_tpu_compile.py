"""Compile the Pallas square kernels for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler is installed with jax, and it compiles
for a ``v5e:2x2`` topology that is described, not attached.  Mosaic
refuses what interpret mode accepts (dynamic slices of loaded values,
blocks that break the (8, 128)-or-whole-dim rule, kernels that overrun
the scoped VMEM), so these tests guard the kernels the chip runs.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.  Every test passes
``interpret=False`` and the TPU layout ``pm_layout="mkn"`` explicitly,
because code that asks ``jax.default_backend()`` still sees the CPU.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cost_model as cm
from repro.kernels import ops, tuning
from repro.kernels.sq_matmul import sq_matmul_pallas
from repro.kernels.sq_paged_attn import sq_paged_attn

# danube-3-4b attention geometry: 8 KV heads x 4 query groups, head_dim 120
DANUBE = dict(B=4, KV=8, G=4, hd=120, block_size=16, blocks_per_seq=20)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # Described-device compiles are written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_hlo(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n", [(8, 3840, 10240), (256, 3840, 3840)])
def test_sq_matmul_compiles(one_chip, m, k, n):
    fn = functools.partial(ops.sq_matmul, interpret=False, pm_layout="mkn")
    hlo = _compile_hlo(fn, ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16),
                       sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("fold", [False, True])
def test_sq_matmul_batched_compiles(one_chip, fold):
    # attention-shaped batched GEMM: B*H heads of (S, hd) @ (hd, T)
    fn = functools.partial(ops.sq_matmul, interpret=False, pm_layout="mkn",
                           fold=fold)
    hlo = _compile_hlo(fn, ((32, 16, 128), jnp.bfloat16),
                       ((32, 128, 256), jnp.bfloat16), sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("op", [ops.cpm3_matmul, ops.cpm4_matmul])
def test_cpm_compiles(one_chip, op):
    fn = functools.partial(op, interpret=False, pm_layout="mkn")
    hlo = _compile_hlo(fn, ((256, 256), jnp.complex64),
                       ((256, 256), jnp.complex64), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_sq_conv2d_compiles(one_chip):
    fn = functools.partial(ops.sq_conv2d, interpret=False, pm_layout="mkn")
    hlo = _compile_hlo(fn, ((4, 64, 32, 32), jnp.float32),
                       ((64, 64, 3, 3), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_sq_paged_attn_compiles_at_danube_geometry(one_chip):
    d = DANUBE
    B, KV, G, hd, bs = d["B"], d["KV"], d["G"], d["hd"], d["block_size"]
    nb = d["blocks_per_seq"]
    pool = (1 + B * nb) * bs                 # null block + every table
    fn = functools.partial(sq_paged_attn, block_size=bs, interpret=False,
                           pm_layout="mkn")
    hlo = _compile_hlo(fn, ((B, 1, KV, G, hd), jnp.bfloat16),
                       ((pool, KV, hd), jnp.bfloat16),
                       ((pool, KV, hd), jnp.bfloat16),
                       ((B, nb), jnp.int32), ((pool,), jnp.int32),
                       ((B, 1), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_planner_budget_edge_plan_compiles(one_chip):
    """The largest plan the VMEM check admits still fits the chip: the
    check counts every buffer, the live PM block included, at its tiled
    size."""
    m, n, k = 512, 3840, 3840
    plans = tuning.candidate_plans(m, n, k, pm_layout="mkn")
    edge = max(plans, key=lambda p: cm.pm_grid_cost(
        m, n, k, *p.astuple()).vmem_bytes)
    mp, np_, kp = (-(-d // t) * t for d, t in
                   ((m, edge.bm), (n, edge.bn), (k, edge.bk)))
    fn = functools.partial(sq_matmul_pallas, bm=edge.bm, bn=edge.bn,
                           bk=edge.bk, kc=edge.kc, pm_layout="mkn",
                           interpret=False)
    hlo = _compile_hlo(fn, ((mp, kp), jnp.float32), ((kp, np_), jnp.float32),
                       ((mp, 1), jnp.float32), ((1, np_), jnp.float32),
                       sharding=one_chip)
    assert "tpu_custom_call" in hlo
