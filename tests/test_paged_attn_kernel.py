"""Fused paged-attention square kernel: numerics against the gather
reference, route planning, dispatch wiring, and the decode-scatter clamp
regression.

The kernel (:mod:`repro.kernels.sq_paged_attn`) must be numerically
interchangeable with the gather read path -- same masks, same all-padded
row convention, same f32 accumulation -- because the serving engine flips
between them purely on the cost model."""
import dataclasses
import functools
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import routing, tuning
from repro.kernels.sq_paged_attn import sq_paged_attn
from repro.models import attention as attn
from repro.models.lm import build_model
from repro.serve import paged as pg


@pytest.fixture(autouse=True)
def _no_autotune(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    routing.reset_route_health()
    yield
    routing.reset_route_health()


# ------------------------------------------------------------- fixtures

def _setup(B=2, S=3, KV=2, G=2, hd=16, nb=4, block_size=4, n_ctx=None,
           seed=0, dtype=np.float32):
    """Random pools + per-sequence block tables covering ``n_ctx`` tokens
    (default: the full table), queries at the last S positions."""
    rng = np.random.default_rng(seed)
    num_blocks = 1 + B * nb
    P = num_blocks * block_size
    k_pool = rng.normal(size=(P, KV, hd)).astype(np.float32)
    v_pool = rng.normal(size=(P, KV, hd)).astype(np.float32)
    pos_pool = np.full(P, attn.EMPTY_POS, np.int32)
    tables = np.zeros((B, nb), np.int32)
    n = n_ctx if n_ctx is not None else nb * block_size
    for b in range(B):
        blocks = 1 + b * nb + np.arange(-(-n // block_size))
        tables[b, :len(blocks)] = blocks
        for c, blk in enumerate(blocks):
            for j in range(block_size):
                p = c * block_size + j
                if p < n:
                    pos_pool[blk * block_size + j] = p
    q = rng.normal(size=(B, S, KV, G, hd)).astype(np.float32)
    q_pos = np.tile(np.arange(n - S, n), (B, 1)).astype(np.int32)
    return (jnp.asarray(q), jnp.asarray(k_pool, dtype),
            jnp.asarray(v_pool, dtype), jnp.asarray(tables),
            jnp.asarray(pos_pool), jnp.asarray(q_pos))


def _evict(args, window, block_size):
    """What windowed eviction leaves: leading table columns whose every
    position has aged out of every query's window point at the null
    block, and the freed blocks' positions are back to the sentinel."""
    q, kp, vp, tables, pos_pool, q_pos = args
    tables, pos_pool = np.array(tables), np.array(pos_pool)
    for b in range(tables.shape[0]):
        n_dead = max(0, (int(q_pos[b].min()) - window + 1) // block_size)
        for blk in tables[b, :n_dead]:
            pos_pool[blk * block_size:(blk + 1) * block_size] = \
                attn.EMPTY_POS
        tables[b, :n_dead] = 0
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos_pool), q_pos


def _reference(q, k_pool, v_pool, tables, pos_pool, q_pos, *, block_size,
               window=None, softcap=0.0):
    """The gather read path, verbatim semantics."""
    idx = attn.paged_gather_indices(tables, block_size)
    k = jnp.take(k_pool, idx, axis=0).astype(jnp.float32)
    v = jnp.take(v_pool, idx, axis=0).astype(jnp.float32)
    kv_pos = jnp.take(pos_pool, idx, axis=0)
    s = jnp.einsum("bqkgh,btkh->bkgqt", q.astype(jnp.float32), k)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    valid = (kv_pos[:, None, :] <= q_pos[:, :, None]) \
        & (kv_pos[:, None, :] < attn.ATTEND_POS_LIMIT)
    if window is not None:
        valid &= (q_pos[:, :, None] - kv_pos[:, None, :]) < window
    s = jnp.where(valid[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqt,btkh->bqkgh", w, v)


# ------------------------------------------------------- kernel numerics

# (setup, call) keyword sets: masks, chunking, and the live-column walk
# (one live column, a whole 96-column table, leading columns a window
# eviction nulled, padded query rows, 16- and 64-token blocks, bf16 pools)
_CASES = {
    "plain": ({}, {}),
    "window4-kc8x2": ({}, dict(window=4, kc_qk=8, kc_pv=2)),
    "softcap30-kc4x4": ({}, dict(softcap=30.0, kc_qk=4, kc_pv=4)),
    "window6-softcap50-kc16x1": ({}, dict(window=6, softcap=50.0, kc_qk=16,
                                          kc_pv=1)),
    "one-live-column": (dict(S=1, nb=8, n_ctx=3), {}),
    "all-96-columns": (dict(S=1, nb=96, block_size=16), {}),
    "evicted-leading": (dict(S=2, nb=12, block_size=8, n_ctx=90),
                        dict(window=20, evict=True)),
    "s8-padded-rows": (dict(S=8, nb=6, n_ctx=21, pad=3), {}),
    "softcap-bs16": (dict(S=1, nb=20, block_size=16, n_ctx=200),
                     dict(softcap=30.0)),
    "bs64": (dict(S=2, nb=5, block_size=64, n_ctx=300), {}),
    "bf16-pools": (dict(S=1, nb=24, block_size=16, n_ctx=250,
                        dtype=jnp.bfloat16), {}),
}


@pytest.mark.parametrize("pm_layout", ["mnk", "mkn"])
@pytest.mark.parametrize("case", list(_CASES))
def test_kernel_matches_gather_reference(pm_layout, case):
    setup, call = (dict(d) for d in _CASES[case])
    pad = setup.pop("pad", 0)
    bs = setup.get("block_size", 4)
    args = _setup(**setup)
    if call.pop("evict", False):
        args = _evict(args, call["window"], bs)
    if pad:                                   # padded query rows (-1)
        args = (*args[:5], args[5].at[0, -pad:].set(-1))
    out = sq_paged_attn(*args, block_size=bs, pm_layout=pm_layout,
                        interpret=True, **call)
    ref = _reference(*args, block_size=bs, window=call.get("window"),
                     softcap=call.get("softcap", 0.0))
    valid = np.asarray(args[5]) >= 0          # padding rows are discarded
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid], atol=1e-4)


def test_kernel_skips_poisoned_dead_columns():
    """Table columns past each sequence's live range point at pool blocks
    full of NaN: the kernel must not read them (read-and-masked would
    give 0 * NaN in the PM form of p.v), so its output is finite and
    equals the gather route over the live columns alone."""
    KV, G, hd, bs, nb = 2, 2, 16, 16, 24
    ctx = [5, 150, 320]                       # 1, 10 and 20 live columns
    B = len(ctx)
    rng = np.random.default_rng(7)
    P = (1 + 2 * B * nb) * bs
    k_pool = rng.normal(size=(P, KV, hd)).astype(np.float32)
    v_pool = rng.normal(size=(P, KV, hd)).astype(np.float32)
    pos_pool = np.full(P, attn.EMPTY_POS, np.int32)
    tables = np.zeros((B, nb), np.int32)
    live = np.zeros((B, nb), np.int32)
    for b, n in enumerate(ctx):
        blocks = 1 + 2 * b * nb + np.arange(nb)
        tables[b] = blocks
        for c, blk in enumerate(blocks):
            sl = slice(blk * bs, (blk + 1) * bs)
            if c * bs < n:                    # live: real positions
                live[b, c] = blk
                pos = c * bs + np.arange(bs)
                pos_pool[sl] = np.where(pos < n, pos, attn.EMPTY_POS)
            else:                             # dead: poisoned
                k_pool[sl] = np.nan
                v_pool[sl] = np.nan
                pos_pool[sl] = c * bs + np.arange(bs)
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, hd)), jnp.float32)
    q_pos = jnp.asarray([[n - 1] for n in ctx], jnp.int32)
    args = (q, jnp.asarray(k_pool), jnp.asarray(v_pool))
    out = sq_paged_attn(*args, jnp.asarray(tables), jnp.asarray(pos_pool),
                        q_pos, block_size=bs, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    # the reference reads only live columns (dead ones -> null block)
    ref = _reference(*args, jnp.asarray(live), jnp.asarray(pos_pool),
                     q_pos, block_size=bs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("window", [None, 40])
def test_walk_fetches_only_live_blocks(window):
    """Replay the pipeline's fetches: an operand's block is copied in
    only when its index map names another block than at the previous
    grid step.  Every block fetched for a sequence with a live query is
    one of its live columns' blocks, each fetched once per operand that
    reads it, and a sequence with none fetches one block per operand."""
    from repro.kernels.sq_paged_attn import (_walk_tables, tile_blocks,
                                             walk_bounds)
    bs, nb = 16, 24
    q_pos = np.array([[4], [149], [319], [-1], [383]], np.int32)
    B = len(q_pos)
    tables = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
    tpb = tile_blocks(bs, nb)
    lo, hi = walk_bounds(q_pos, bs, window)
    walk = np.asarray(_walk_tables(jnp.asarray(tables), jnp.asarray(lo),
                                   jnp.asarray(hi), tpb))
    for j in range(tpb):
        prev = None
        for i in range(B):
            fetched = []
            for s in range(-(-nb // tpb)):
                blk = walk[i, min(s * tpb + j, nb - 1)]
                if blk != prev:
                    fetched.append(blk)
                prev = blk
            live = set(tables[i, lo[i]:hi[i]])
            if live:
                assert set(fetched) <= live, (i, j, fetched)
                own = {tables[i, c] for c in range(lo[i], hi[i])
                       if c % tpb == j}
                assert len(fetched) == max(1, len(own)), (i, j, fetched)
            else:
                assert len(fetched) <= 1
    if window is not None:
        assert lo[4] == (383 - window + 1) // bs > 0


def test_pools_enter_the_kernel_as_stored():
    """The pallas_call takes the K/V pools in their stored dtype and
    element count, and the wrapper widens or transposes no pool-sized
    array on the way in."""
    q, kp, vp, tb, pp, q_pos = _setup(S=1, nb=24, block_size=16,
                                      n_ctx=200, dtype=jnp.bfloat16)
    pool_n = kp.size
    jaxpr = jax.make_jaxpr(functools.partial(
        sq_paged_attn, block_size=16, interpret=True))(
            q, kp, vp, tb, pp, q_pos)

    def eqns(jx):
        for e in jx.eqns:
            yield e
            if e.primitive.name == "pallas_call":
                continue                      # the kernel body is VMEM work
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    calls = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    pool_ops = [v.aval for v in calls[0].invars
                if v.aval.size == pool_n and v.aval.ndim == 4]
    assert pool_ops and all(a.dtype == jnp.bfloat16 for a in pool_ops)
    for e in eqns(jaxpr.jaxpr):
        if e.primitive.name in ("convert_element_type", "transpose"):
            assert all(v.aval.size < pool_n for v in e.invars), e


def test_kernel_partial_table_and_null_blocks():
    """NULL table entries (short context) mask to nothing, like the
    gather path reading the null block's EMPTY_POS entries."""
    args = _setup(n_ctx=9)            # 3 of 4 table columns live
    out = sq_paged_attn(*args, block_size=4, interpret=True)
    ref = _reference(*args, block_size=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_kernel_padded_query_rows_are_finite():
    q, kp, vp, tb, pp, q_pos = _setup()
    q_pos = q_pos.at[1, :].set(-1)            # a fully padded sequence
    out = sq_paged_attn(q, kp, vp, tb, pp, q_pos, block_size=4,
                        interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    # live rows unaffected by the padded sequence
    ref = _reference(q, kp, vp, tb, pp, q_pos, block_size=4)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               atol=1e-4)


def test_kernel_under_jit():
    args = _setup(S=1, nb=3)
    fn = jax.jit(functools.partial(sq_paged_attn, block_size=4,
                                   interpret=True))
    out = fn(*args)
    ref = _reference(*args, block_size=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_kernel_rejects_bad_args():
    args = _setup()
    with pytest.raises(ValueError, match="float-only"):
        sq_paged_attn(jnp.zeros((1, 1, 1, 1, 8), jnp.int8), *args[1:],
                      block_size=4, interpret=True)
    with pytest.raises(ValueError, match="divide"):
        sq_paged_attn(*args, block_size=4, kc_qk=5, interpret=True)
    with pytest.raises(ValueError, match="whole number"):
        sq_paged_attn(*args, block_size=7, interpret=True)


# ------------------------------------------------------------ routing

def test_paged_attn_route_cost_rules():
    r = routing.select_paged_attn_route(1, 128, kv_heads=2, group=2, hd=64)
    assert r.name == "kernel"
    # short pool: one gather beats the block-walk grid
    assert routing.select_paged_attn_route(1, 32).name == "gather"
    # wide query tile: prefill chunks rematerialize the scores per block
    assert routing.select_paged_attn_route(16, 512).name == "gather"
    # integer logits path never reaches the float-only kernel
    r = routing.select_paged_attn_route(1, 512, dtype=jnp.int8)
    assert r.name == "gather" and "float-only" in r.reason


def test_paged_attn_route_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_ROUTE", "paged_attn=kernel")
    assert routing.select_paged_attn_route(16, 8).name == "kernel"
    # bare "kernel" is shared with matmul: pins both kinds
    monkeypatch.setenv("REPRO_ROUTE", "kernel")
    assert routing.select_paged_attn_route(16, 8).name == "kernel"
    assert routing.select_matmul_route(8, 8, 8).name == "kernel"
    monkeypatch.setenv("REPRO_ROUTE", "paged_attn=gather")
    assert routing.select_paged_attn_route(1, 512).name == "gather"


def test_paged_attn_route_cache_pin(monkeypatch, tmp_path):
    path = str(tmp_path / "cache.json")
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    tuning.clear_cache()
    sizes = {"b": 1, "s": 1, "t": 32, "kv": 2, "g": 2, "hd": 64}
    routing.set_route_override("paged_attn", dict(sizes), "kernel")
    r = routing.select_paged_attn_route(1, 32, kv_heads=2, group=2, hd=64)
    assert r.name == "kernel" and "cache" in r.reason
    tuning.clear_cache()


def test_select_route_generic_and_unknown_kind():
    r = routing.select_route("paged_attn",
                             {"s": 1, "t": 128, "kv": 2, "g": 2, "hd": 64})
    assert r.name == "kernel"
    with pytest.raises(ValueError, match="unknown route kind"):
        routing.select_route("attn", {})
    with pytest.raises(ValueError, match="unknown route kind"):
        routing.set_route_override("attn", {}, "kernel")


def test_plan_paged_attn(monkeypatch, tmp_path):
    # the third axis is the tile the kernel walks per grid step (tokens)
    p = tuning.plan_paged_attn(8, 64, 128, pm_layout="mnk")
    assert p.kc_qk == tuning.KC_MNK_MAX and p.kc_pv == tuning.KC_MNK_MAX
    p = tuning.plan_paged_attn(8, 64, 128, pm_layout="mkn")
    assert (p.kc_qk, p.kc_pv) == (64, 128)       # full-axis chunks
    p = tuning.plan_paged_attn(8, 64, 128, kc_qk=16, kc_pv=4)
    assert (p.kc_qk, p.kc_pv) == (16, 4)
    # explicit knobs are clamped to divide their axes
    p = tuning.plan_paged_attn(8, 48, 96, kc_qk=32, kc_pv=64)
    assert 48 % p.kc_qk == 0 and 96 % p.kc_pv == 0
    # an entry keyed on the one-head, one-block kernel's geometry is never
    # served; one keyed on the tile and head count is
    path = tmp_path / "cache.json"
    path.write_text(json.dumps({
        "sq_paged_attn:8x64x128:float32":
            {"kc_qk": 8, "kc_pv": 8, "pm_layout": "mkn"},
        "sq_paged_attn:4kv:8x64x128:bfloat16":
            {"kc_qk": 32, "kc_pv": 64, "pm_layout": "mkn"}}))
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    tuning.clear_cache()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")        # the expected miss
            p = tuning.plan_paged_attn(8, 64, 128, kv_heads=1)
        assert (p.kc_qk, p.kc_pv) == (64, 128)
        p = tuning.plan_paged_attn(8, 64, 128, jnp.bfloat16, kv_heads=4)
        assert (p.kc_qk, p.kc_pv) == (32, 64)
    finally:
        tuning.clear_cache()


# ----------------------------------------------------- dispatch wiring

def _spied_decode(monkeypatch, arch="deepseek-7b", route="kernel",
                  demote=False):
    """Run a short paged decode with the route pinned; count kernel calls."""
    import repro.kernels.sq_paged_attn as spa
    calls = {"n": 0}
    orig = sq_paged_attn

    def spy(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(spa, "sq_paged_attn", spy)
    monkeypatch.setenv("REPRO_ROUTE", f"paged_attn={route}")
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              matmul_mode="square_pallas")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    block_size, num_blocks, bps = 4, 16, 8
    alloc = pg.BlockAllocator(num_blocks, block_size)
    tables = pg.BlockTables(alloc, 1, bps)
    prompt = list(np.random.default_rng(3).integers(0, cfg.vocab, 11,
                                                    dtype=np.int32))
    n_new = 4
    assert tables.ensure(0, len(prompt) + n_new)
    if demote:
        # the breaker is per shape: demote both the prefill-chunk and the
        # decode-step keys this run will produce
        T = bps * block_size
        hd = cfg.resolved_head_dim
        KV = cfg.n_kv_heads
        G = cfg.n_heads // KV
        for S in (1, len(prompt)):
            hkey = routing.health_key("attn_paged", (1, S, KV, G, hd, T),
                                      jnp.dtype(cfg.dtype))
            routing.route_health().record_trip(hkey, limit=1)
    cache = model.init_paged_cache(num_blocks * block_size)
    pos_pool = jnp.asarray(pg.empty_pos_pool(num_blocks, block_size))
    tb = jnp.asarray(tables.table)
    h, cache, pos_pool = model.decode_paged(
        params, cache, jnp.asarray(np.asarray(prompt)[None]),
        jnp.asarray(np.arange(len(prompt))[None]), tb, pos_pool,
        block_size=block_size)
    toks = [int(np.argmax(np.asarray(
        model.logits(params, h[:, -1:])[0, 0])))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray([[toks[-1]]], dtype=np.int32),
            jnp.asarray([[pos]], dtype=np.int32), tb, pos_pool,
            block_size=block_size)
        toks.append(int(np.argmax(np.asarray(
            model.logits(params, h)[0, 0]))))
        pos += 1
    return toks, calls["n"]


def test_dispatch_kernel_route_engages_and_matches(monkeypatch):
    toks_g, n_g = _spied_decode(monkeypatch, route="gather")
    assert n_g == 0
    toks_k, n_k = _spied_decode(monkeypatch, route="kernel")
    assert n_k > 0, "kernel route pinned but never dispatched"
    assert toks_k == toks_g


def test_dispatch_respects_route_health_demotion(monkeypatch):
    """A demoted attn_paged key serves the gather path even when the
    kernel route is pinned -- same tokens, zero kernel calls."""
    toks_g, _ = _spied_decode(monkeypatch, route="gather")
    toks_d, n_d = _spied_decode(monkeypatch, route="kernel", demote=True)
    assert n_d == 0
    assert toks_d == toks_g


# --------------------------------------- decode scatter clamp regression

def _cache_pos_buffers(cache):
    """All ``pos`` buffers in a decode-cache pytree (stacked (..., B, T))."""
    found = []

    def visit(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "pos":
                    found.append(v)
                else:
                    visit(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                visit(v)

    visit(cache)
    assert found, "no pos buffers in decode cache"
    return found


def test_nonlockstep_past_capacity_scatter_clamps():
    """The no-window per-row scatter must clamp like the lockstep branch:
    a past-capacity pos pins to the last slot instead of silently
    dropping the update out of bounds (jax drops OOB scatters)."""
    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T, batch = 8, 2
    _, cache = model.prefill(
        params, {"tokens": jnp.asarray(np.zeros((batch, 4), np.int32))},
        cache_len=T)
    # per-row (non-lockstep) positions beyond the cache capacity
    over = jnp.asarray([T + 3, T + 5])
    _, cache_r = model.decode_step(params, cache,
                                   jnp.asarray([[1], [1]]), over)
    for pos_buf in _cache_pos_buffers(cache_r):
        got = np.asarray(pos_buf)[..., T - 1]        # (..., B) last slot
        assert (got == np.asarray(over)).all(), \
            "past-capacity scatter did not land on the clamped last slot"
