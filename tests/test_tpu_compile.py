"""Compile the Pallas square kernels for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler is installed with jax, and it compiles
for a ``v5e:2x2`` topology that is described, not attached.  Mosaic
refuses what interpret mode accepts (dynamic slices of loaded values,
blocks that break the (8, 128)-or-whole-dim rule, kernels that overrun
the scoped VMEM), so these tests guard the kernels the chip runs.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.  Every test passes
``interpret=False`` and a TPU layout (``pm_layout="mkn"``, or "kmn" for
``sq_matmul``) explicitly, because code that asks
``jax.default_backend()`` still sees the CPU.
"""
import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
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


def _names_sq_matmul_kernel(hlo):
    """The compiled body still carries the kernel's name, which the
    benchmark's HLO reader counts it by (``bench/kernels/sq_matmul.py``)."""
    from bench import hlo as bench_hlo
    return any(c.kind == "sq_matmul"
               for c in bench_hlo.parse(hlo).contractions)


# danube's decode and chunk GEMMs, its MLP down-projection and head, and
# Moonlight's head (an eighth of the vocabulary); the "mkn" cases keep
# the ids they had before the layout was a parameter
@pytest.mark.parametrize("m,k,n,pm_layout", [
    pytest.param(m, k, n, layout,
                 id=f"{m}-{k}-{n}" + ("" if layout == "mkn" else "-kmn"))
    for layout in ("mkn", "kmn")
    for (m, k, n) in [(8, 3840, 10240), (256, 3840, 3840), (8, 10240, 3840),
                      (32, 3840, 32000), (32, 2048, 20480)]])
def test_sq_matmul_compiles(one_chip, m, k, n, pm_layout):
    fn = functools.partial(ops.sq_matmul, interpret=False,
                           pm_layout=pm_layout)
    hlo = _compile_hlo(fn, ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16),
                       sharding=one_chip)
    assert "tpu_custom_call" in hlo
    if pm_layout == "kmn":
        assert _names_sq_matmul_kernel(hlo)


# attention-shaped batched GEMM (B*H heads of (S, hd) @ (hd, T)), folded
# or not, and Moonlight's absorbed latent projections, 16 heads of 32
# rows, which route to the batched (unfolded) kernel
@pytest.mark.parametrize("b,m,k,n,fold,pm_layout", [
    pytest.param(b, m, k, n, fold, layout,
                 id=("" if (b, m, k, n) == (32, 16, 128, 256)
                     else f"{b}-{m}-{k}-{n}-") + str(fold)
                 + ("" if layout == "mkn" else "-kmn"))
    for layout in ("mkn", "kmn")
    for (b, m, k, n, fold) in [(32, 16, 128, 256, False),
                               (32, 16, 128, 256, True),
                               (16, 32, 128, 512, False),
                               (16, 32, 512, 128, False)]])
def test_sq_matmul_batched_compiles(one_chip, fold, pm_layout, b, m, k, n):
    fn = functools.partial(ops.sq_matmul, interpret=False,
                           pm_layout=pm_layout, fold=fold)
    hlo = _compile_hlo(fn, ((b, m, k), jnp.bfloat16),
                       ((b, k, n), jnp.bfloat16), sharding=one_chip)
    assert "tpu_custom_call" in hlo
    if pm_layout == "kmn":
        assert _names_sq_matmul_kernel(hlo)


def test_sq_matmul_tpu_plans_compile(one_chip, monkeypatch):
    """The planner's own TPU picks (as ``ops._resolve_plan`` resolves
    them on the chip) compile at the cells' decode shapes, and take the
    row walk."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    for (m, k, n) in [(8, 3840, 10240), (32, 3840, 3840), (32, 2048, 2048)]:
        plan = ops._resolve_plan(m, n, k, jnp.bfloat16, bm=None, bn=None,
                                 bk=None, kc=None, pm_layout=None,
                                 interpret=False, kind="sq_matmul")
        assert plan.pm_layout == "kmn", plan
        fn = functools.partial(ops.sq_matmul, interpret=False,
                               **dataclasses.asdict(plan))
        hlo = _compile_hlo(fn, ((m, k), jnp.bfloat16),
                           ((k, n), jnp.bfloat16), sharding=one_chip)
        assert _names_sq_matmul_kernel(hlo)


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


def _row_major(shape, dtype, sharding):
    """A described argument in the layout the engine keeps its pools in."""
    fmt = Format(Layout(tuple(range(len(shape)))), sharding)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=fmt)


def _pool_moves(hlo, slots, block_size, row_elems):
    """Relayouts, widenings and transposes of a K/V pool: copies,
    converts and transposes (fused or not) whose output has the pool's
    slot or block axis and at least half a pool's elements (``slots *
    row_elems``).  A read straight from the pool has no use for them;
    the asynchronous moves between memory spaces keep the layout."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]"
                     r"\S*\s+([\w\-]+)\(", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        name, op = m.group(1), m.group(3)
        moves = op in ("copy", "convert", "transpose") or (
            op == "fusion" and name.startswith(("copy", "convert",
                                                "transpose")))
        if moves and set(dims) & {slots, slots // block_size} \
                and math.prod(dims) * 2 >= slots * row_elems:
            found.append(line.strip()[:160])
    return found


def test_sq_paged_attn_compiles_at_danube_geometry(one_chip):
    from bench import hlo as bench_hlo
    d = DANUBE
    B, KV, G, hd, bs = d["B"], d["KV"], d["G"], d["hd"], d["block_size"]
    nb = d["blocks_per_seq"]
    pool = (1 + B * nb) * bs                 # null block + every table
    fn = functools.partial(sq_paged_attn, block_size=bs, interpret=False,
                           pm_layout="mkn")
    args = [jax.ShapeDtypeStruct((B, 1, KV, G, hd), jnp.float32,
                                 sharding=one_chip),
            _row_major((pool, KV, hd), jnp.bfloat16, one_chip),
            _row_major((pool, KV, hd), jnp.bfloat16, one_chip),
            jax.ShapeDtypeStruct((B, nb), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((pool,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    # the pools enter the kernel as stored: nothing pool-sized is copied,
    # widened or transposed on the way
    assert not _pool_moves(hlo, pool, bs, KV * hd)
    # the benchmark's HLO reader still counts the kernel's whole work
    attn = [c for c in bench_hlo.parse(hlo).contractions
            if c.kind == "sq_paged_attn"]
    assert len(attn) == 1 and attn[0].count == 1
    assert attn[0].flops == 4 * B * KV * G * hd * nb * bs


@pytest.mark.parametrize("B,S,KV,G,hd,bs,nb,dtype", [
    (8, 1, 32, 1, 128, 16, 96, jnp.bfloat16),    # MHA decode: one-row tiles
    (2, 1, 1, 4, 128, 16, 12, jnp.bfloat16),     # one KV head (MQA)
    (2, 8, 2, 2, 64, 64, 8, jnp.float32),        # S = 8, 64-token blocks
    (4, 2, 4, 2, 128, 32, 16, jnp.bfloat16),     # 32-token blocks
])
def test_sq_paged_attn_compiles_across_geometries(one_chip, B, S, KV, G,
                                                  hd, bs, nb, dtype):
    pool = (1 + B * nb) * bs
    fn = functools.partial(sq_paged_attn, block_size=bs, interpret=False,
                           pm_layout="mkn", window=bs * 3)
    hlo = _compile_hlo(fn, ((B, S, KV, G, hd), jnp.float32),
                       ((pool, KV, hd), dtype), ((pool, KV, hd), dtype),
                       ((B, nb), jnp.int32), ((pool,), jnp.int32),
                       ((B, S), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_danube_decode_step_widens_no_pool(one_chip, monkeypatch):
    """The whole 24-layer danube decode step, compiled as the engine jits
    it, widens and transposes no K/V pool on the way into the paged
    kernel: what it still moves of a pool is bf16, the relayouts between
    the stored layout XLA picks (slot axis minor) and the row-major one
    of the K/V scatter and the kernel."""
    from repro.configs import get_config
    from repro.configs.base import SQUARE_GEMMS_POLICY
    from repro.models.lm import build_model
    cfg = dataclasses.replace(get_config("h2o-danube-3-4b"),
                              matmul_mode="square_pallas",
                              contraction_policy=SQUARE_GEMMS_POLICY)
    model = build_model(cfg)
    B, nb, bs, nblk = 8, 96, 16, 769

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(model.prepare_params,
                                      model.abstract_params()))
    cache = described(jax.eval_shape(
        lambda: model.init_paged_cache(nblk * bs)))

    def decode(params, cache, pos_pool, tables, tokens, positions):
        h, cache, pos_pool = model.decode_paged(
            params, cache, tokens, positions, tables, pos_pool,
            block_size=bs)
        return model.logits(params, h)[:, -1], cache, pos_pool

    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    # the kernels lower for the chip, not the CPU this test runs on
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    hlo = jax.jit(decode).lower(
        params, cache, i32((nblk * bs,)), i32((B, nb)), i32((B, 1)),
        i32((B, 1))).compile().as_text()
    assert "tpu_custom_call" in hlo
    moves = _pool_moves(hlo, nblk * bs, bs,
                        cfg.n_kv_heads * cfg.resolved_head_dim)
    assert all(re.search(r"= bf16\[[\d,]*\]\S* copy\(", m) for m in moves), \
        moves


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


# Moonlight-16B-A3B's cell: 32 decode rows, 16 heads over one latent head
# (Dk = 512 + 64, Dv = 512), 160-column tables of 16-token blocks; 8 held
# experts of d 2048 <-> 1408 at 32 tokens x top-6 picks (decode and the
# 32-token prefill chunk alike)
MOONLIGHT = dict(B=32, H=16, Dk=576, Dv=512, block_size=16,
                 blocks_per_seq=160, d=2048, f=1408, held=8, tokens=32,
                 topk=6)


def test_latent_sq_paged_attn_compiles_at_moonlight_geometry(one_chip):
    from bench import hlo as bench_hlo
    m = MOONLIGHT
    B, H, Dk, Dv, bs, nb = (m["B"], m["H"], m["Dk"], m["Dv"],
                            m["block_size"], m["blocks_per_seq"])
    pool = (1 + B * nb) * bs
    fn = functools.partial(sq_paged_attn, block_size=bs, interpret=False,
                           pm_layout="mkn", dv=Dv)

    def latent(q, kv, tables, pos_pool, q_pos):
        return fn(q, kv, None, tables, pos_pool, q_pos)

    hlo = _compile_hlo(latent, ((B, 1, 1, H, Dk), jnp.float32),
                       ((pool, 1, Dk), jnp.bfloat16), ((B, nb), jnp.int32),
                       ((pool,), jnp.int32), ((B, 1), jnp.int32),
                       sharding=one_chip)
    assert "tpu_custom_call" in hlo
    attn = [c for c in bench_hlo.parse(hlo).contractions
            if c.kind == "sq_paged_attn"]
    assert len(attn) == 1
    assert attn[0].flops == 2 * B * H * nb * bs * (Dk + Dv)


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_sq_grouped_matmul_compiles_at_moonlight_geometry(one_chip, k, n):
    from repro.kernels.sq_grouped_matmul import max_tiles
    m = MOONLIGHT
    tiles = max_tiles(m["tokens"] * m["topk"], m["held"], 8)
    fn = functools.partial(ops.sq_grouped_matmul, bm=8, interpret=False)
    hlo = _compile_hlo(fn, ((tiles * 8, k), jnp.float32),
                       ((m["held"], k, n), jnp.bfloat16),
                       ((tiles,), jnp.int32), ((1,), jnp.int32),
                       sharding=one_chip)
    assert "tpu_custom_call" in hlo
