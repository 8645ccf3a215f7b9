"""Paged KV-cache units: block allocator, slot indexing, and numerical
equivalence of the gather-based paged attention path against the dense
per-slot decode cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models.lm import build_model
from repro.serve import paged as pg


# ------------------------------------------------------------- allocator

def test_allocator_reserves_null_block():
    a = pg.BlockAllocator(8, 4)
    got = a.alloc(7)
    assert got is not None and pg.NULL_BLOCK not in got
    assert a.alloc(1) is None                      # pool empty, block 0 kept


def test_allocator_alloc_is_all_or_nothing():
    a = pg.BlockAllocator(5, 4)
    assert a.alloc(5) is None
    assert a.free_blocks == 4                      # failed alloc untouched
    grant = a.alloc(4)
    assert sorted(grant) == [1, 2, 3, 4]


def test_allocator_free_and_reuse():
    a = pg.BlockAllocator(4, 2)
    g1 = a.alloc(3)
    a.free(g1[:2])
    assert a.free_blocks == 2 and a.used_blocks == 1
    assert sorted(a.alloc(2)) == sorted(g1[:2])    # recycled


def test_allocator_double_free_raises():
    a = pg.BlockAllocator(4, 2)
    g = a.alloc(1)
    a.free(g)
    with pytest.raises(ValueError):
        a.free(g)
    with pytest.raises(ValueError):
        a.free([pg.NULL_BLOCK])


def test_allocator_blocks_for_and_utilization():
    a = pg.BlockAllocator(9, 4)
    assert a.blocks_for(0) == 0
    assert a.blocks_for(1) == 1
    assert a.blocks_for(4) == 1
    assert a.blocks_for(5) == 2
    a.alloc(4)
    assert a.utilization == pytest.approx(0.5)


# ----------------------------------------------------------- block tables

def test_block_tables_grow_and_release():
    a = pg.BlockAllocator(8, 4)
    t = pg.BlockTables(a, max_slots=2, blocks_per_seq=3)
    assert t.max_len == 12
    assert t.ensure(0, 5)                          # 2 blocks
    assert (t.table[0, :2] > 0).all() and t.table[0, 2] == pg.NULL_BLOCK
    assert t.ensure(0, 5)                          # idempotent
    assert a.used_blocks == 2
    freed = t.release(0)
    assert len(freed) == 2 and a.used_blocks == 0
    assert (t.table[0] == pg.NULL_BLOCK).all()


def test_block_tables_ceiling_raises():
    a = pg.BlockAllocator(16, 4)
    t = pg.BlockTables(a, max_slots=1, blocks_per_seq=2)
    with pytest.raises(ValueError):
        t.ensure(0, 9)                             # 3 blocks > ceiling 2


def test_block_tables_exhaustion_returns_false():
    a = pg.BlockAllocator(3, 4)                    # 2 allocatable
    t = pg.BlockTables(a, max_slots=2, blocks_per_seq=2)
    assert t.ensure(0, 8)
    assert not t.ensure(1, 4)                      # untouched on failure
    assert (t.table[1] == pg.NULL_BLOCK).all()


# ------------------------------------------------------------ slot maths

def test_paged_slots_and_gather_indices():
    bs = 4
    tables = jnp.asarray([[2, 5, 0]], jnp.int32)
    pos = jnp.asarray([[0, 3, 4, 6, -1]], jnp.int32)
    phys = np.asarray(attn.paged_slots(tables, pos, bs))
    #    pos 0 -> block 2 slot 0 = 8;  pos 3 -> 11;  pos 4 -> block 5 = 20
    assert phys.tolist() == [[8, 11, 20, 22, 0]]   # padding -> slot 0
    idx = np.asarray(attn.paged_gather_indices(tables, bs))
    assert idx.shape == (1, 12)
    assert idx[0, :8].tolist() == [8, 9, 10, 11, 20, 21, 22, 23]


def test_empty_pos_pool_is_all_sentinel():
    pool = pg.empty_pos_pool(4, 8)
    assert pool.shape == (32,) and (pool == attn.EMPTY_POS).all()


# ------------------------------------- paged vs dense decode equivalence

def _decode_dense(model, params, prompt, n_new, cache_len):
    hidden, cache = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                  cache_len=cache_len)
    logits = [np.asarray(model.logits(params, hidden[:, -1:])[0, 0])]
    toks = [int(np.argmax(logits[-1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        lg, cache = model.decode_step(params, cache,
                                      jnp.asarray([[toks[-1]]]),
                                      jnp.asarray([pos]))
        logits.append(np.asarray(lg[0]))
        toks.append(int(np.argmax(lg[0])))
        pos += 1
    return toks, logits


def _decode_paged(model, params, prompt, n_new, *, block_size, num_blocks,
                  blocks_per_seq, chunk):
    alloc = pg.BlockAllocator(num_blocks, block_size)
    tables = pg.BlockTables(alloc, 1, blocks_per_seq)
    assert tables.ensure(0, len(prompt) + n_new)
    cache = model.init_paged_cache(num_blocks * block_size)
    pos_pool = jnp.asarray(pg.empty_pos_pool(num_blocks, block_size))
    tb = jnp.asarray(tables.table)
    last = 0
    for lo in range(0, len(prompt), chunk):
        part = prompt[lo:lo + chunk]
        t = np.zeros((1, chunk), np.int32)
        p = np.full((1, chunk), -1, np.int32)
        t[0, :len(part)] = part
        p[0, :len(part)] = np.arange(lo, lo + len(part))
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray(t), jnp.asarray(p), tb, pos_pool,
            block_size=block_size)
        last = len(part) - 1
    logits = [np.asarray(model.logits(params, h[:, last:last + 1])[0, 0])]
    toks = [int(np.argmax(logits[-1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray([[toks[-1]]], dtype=np.int32),
            jnp.asarray([[pos]], dtype=np.int32), tb, pos_pool,
            block_size=block_size)
        lg = np.asarray(model.logits(params, h)[0, 0])
        logits.append(lg)
        toks.append(int(np.argmax(lg)))
        pos += 1
    return toks, logits


@pytest.mark.parametrize("arch", ["deepseek-7b", "starcoder2-3b",
                                  "moonlight-16b-a3b", "mixtral-8x7b"])
def test_paged_matches_dense_decode(arch):
    """Gather-based paged attention (chunked prefill + paged decode) must
    agree with the dense prefill + per-slot decode path: same greedy
    tokens, logits within accumulation noise.  Covers MHA (deepseek), GQA
    + sliding window + layernorm/bias (starcoder2), latent attention over
    one latent pool with a leading dense layer and held sigmoid-routed
    experts (moonlight), and softmax top-k MoE (mixtral)."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompt = np.random.default_rng(3).integers(0, cfg.vocab, 11,
                                               dtype=np.int32)
    toks_d, logits_d = _decode_dense(model, params, prompt, 5, cache_len=32)
    toks_p, logits_p = _decode_paged(model, params, prompt, 5, block_size=8,
                                     num_blocks=8, blocks_per_seq=4, chunk=4)
    assert toks_p == toks_d
    for a, b in zip(logits_d, logits_p):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_paged_chunk_size_invariance():
    """The chunked-prefill split must not change the result: one absolute-
    position mask covers prior chunks and intra-chunk causality."""
    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, 10,
                                               dtype=np.int32)
    kw = dict(block_size=4, num_blocks=16, blocks_per_seq=6)
    toks_a, logits_a = _decode_paged(model, params, prompt, 4, chunk=3, **kw)
    toks_b, logits_b = _decode_paged(model, params, prompt, 4, chunk=16, **kw)
    assert toks_a == toks_b
    for a, b in zip(logits_a, logits_b):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_paged_ragged_batch_matches_single():
    """Two sequences decoding at independent offsets in one paged batch
    must produce exactly what each produces alone (slot isolation: block
    tables keep the shared pool's sequences apart)."""
    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(7)
    p0 = rng.integers(0, cfg.vocab, 9, dtype=np.int32)
    p1 = rng.integers(0, cfg.vocab, 4, dtype=np.int32)
    solo0, _ = _decode_paged(model, params, p0, 4, block_size=4,
                             num_blocks=16, blocks_per_seq=4, chunk=16)
    solo1, _ = _decode_paged(model, params, p1, 4, block_size=4,
                             num_blocks=16, blocks_per_seq=4, chunk=16)

    bs, nb = 4, 16
    alloc = pg.BlockAllocator(nb, bs)
    tables = pg.BlockTables(alloc, 2, 4)
    assert tables.ensure(0, len(p0) + 4) and tables.ensure(1, len(p1) + 4)
    cache = model.init_paged_cache(nb * bs)
    pos_pool = jnp.asarray(pg.empty_pos_pool(nb, bs))
    tb = jnp.asarray(tables.table)

    # prefill each prompt (ragged lengths) as single chunks on its own row
    outs = []
    for row, prompt in ((0, p0), (1, p1)):
        t = np.zeros((2, 16), np.int32)
        p = np.full((2, 16), -1, np.int32)
        t[row, :len(prompt)] = prompt
        p[row, :len(prompt)] = np.arange(len(prompt))
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray(t), jnp.asarray(p), tb, pos_pool,
            block_size=bs)
        outs.append(np.asarray(model.logits(
            params, h[row:row + 1, len(prompt) - 1:len(prompt)])[0, 0]))
    toks = [[int(np.argmax(outs[0]))], [int(np.argmax(outs[1]))]]
    pos = np.asarray([len(p0), len(p1)], np.int32)

    for _ in range(3):                      # ragged joint decode
        t = np.asarray([[toks[0][-1]], [toks[1][-1]]], np.int32)
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray(t), jnp.asarray(pos[:, None]), tb,
            pos_pool, block_size=bs)
        lg = np.asarray(model.logits(params, h)[:, 0])
        toks[0].append(int(np.argmax(lg[0])))
        toks[1].append(int(np.argmax(lg[1])))
        pos = pos + 1
    assert toks[0] == solo0 and toks[1] == solo1


def test_recycled_block_does_not_leak_positions():
    """After a release + pos reset, a block recycled to a new sequence must
    not let the previous owner's entries attend (stale-position leak)."""
    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(11)
    pA = rng.integers(0, cfg.vocab, 8, dtype=np.int32)
    pB = rng.integers(0, cfg.vocab, 6, dtype=np.int32)
    solo, _ = _decode_paged(model, params, pB, 3, block_size=4, num_blocks=8,
                            blocks_per_seq=3, chunk=16)

    bs, nb = 4, 8
    alloc = pg.BlockAllocator(nb, bs)
    tables = pg.BlockTables(alloc, 1, 3)
    cache = model.init_paged_cache(nb * bs)
    pos_pool = jnp.asarray(pg.empty_pos_pool(nb, bs))

    def run(prompt, n_new):
        nonlocal cache, pos_pool
        assert tables.ensure(0, len(prompt) + n_new)
        tb = jnp.asarray(tables.table)
        t = np.zeros((1, 16), np.int32)
        p = np.full((1, 16), -1, np.int32)
        t[0, :len(prompt)] = prompt
        p[0, :len(prompt)] = np.arange(len(prompt))
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray(t), jnp.asarray(p), tb, pos_pool,
            block_size=bs)
        toks = [int(np.argmax(np.asarray(model.logits(
            params, h[:, len(prompt) - 1:len(prompt)])[0, 0])))]
        pos = len(prompt)
        for _ in range(n_new - 1):
            h, cache, pos_pool = model.decode_paged(
                params, cache, jnp.asarray([[toks[-1]]], dtype=np.int32),
                jnp.asarray([[pos]], dtype=np.int32), tb, pos_pool,
                block_size=bs)
            toks.append(int(np.argmax(np.asarray(
                model.logits(params, h)[0, 0]))))
            pos += 1
        return toks

    run(pA, 3)                               # occupy + dirty some blocks
    freed = tables.release(0)
    idx = tables.reset_slots_index(freed)    # the engine's reset step
    pos_pool = pos_pool.at[jnp.asarray(idx)].set(attn.EMPTY_POS)
    assert run(pB, 3) == solo                # recycled blocks are clean


def test_swa_eviction_matches_unevicted_paged():
    """Windowed block eviction must be invisible to the logits: blocks
    whose every position has aged out of the sliding window are already
    masked, so freeing them (and NULLing their table columns) changes
    nothing -- while capping the live footprint at
    ``ceil(window / block_size) + 1`` blocks."""
    import dataclasses

    window, bs, nb, bps = 8, 4, 16, 8
    cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                              window=window)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    prompt = np.random.default_rng(13).integers(0, cfg.vocab, 14,
                                                dtype=np.int32)
    n_new = 6
    base, base_logits = _decode_paged(model, params, prompt, n_new,
                                      block_size=bs, num_blocks=nb,
                                      blocks_per_seq=bps, chunk=4)

    alloc = pg.BlockAllocator(nb, bs)
    tables = pg.BlockTables(alloc, 1, bps)
    cache = model.init_paged_cache(nb * bs)
    pos_pool = jnp.asarray(pg.empty_pos_pool(nb, bs))
    peak = 0
    evicted_total = 0

    def evict(next_pos):
        nonlocal pos_pool, evicted_total
        freed = tables.evict_window(0, next_pos, window)
        evicted_total += len(freed)
        if freed:
            idx = tables.reset_slots_index(freed)
            pos_pool = pos_pool.at[jnp.asarray(idx)].set(attn.EMPTY_POS)

    toks, logits, h = [], [], None
    chunk = 4
    for lo in range(0, len(prompt), chunk):
        part = prompt[lo:lo + chunk]
        evict(lo)
        assert tables.ensure(0, lo + len(part))
        peak = max(peak, len(tables.owned(0)))
        t = np.zeros((1, chunk), np.int32)
        p = np.full((1, chunk), -1, np.int32)
        t[0, :len(part)] = part
        p[0, :len(part)] = np.arange(lo, lo + len(part))
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray(t), jnp.asarray(p),
            jnp.asarray(tables.table), pos_pool, block_size=bs)
        last = len(part) - 1
    logits.append(np.asarray(model.logits(params,
                                          h[:, last:last + 1])[0, 0]))
    toks.append(int(np.argmax(logits[-1])))
    pos = len(prompt)
    for _ in range(n_new - 1):
        evict(pos)
        assert tables.ensure(0, pos + 1)
        peak = max(peak, len(tables.owned(0)))
        h, cache, pos_pool = model.decode_paged(
            params, cache, jnp.asarray([[toks[-1]]], dtype=np.int32),
            jnp.asarray([[pos]], dtype=np.int32),
            jnp.asarray(tables.table), pos_pool, block_size=bs)
        lg = np.asarray(model.logits(params, h)[0, 0])
        logits.append(lg)
        toks.append(int(np.argmax(lg)))
        pos += 1

    assert evicted_total > 0, "window never aged a block out"
    assert peak <= -(-window // bs) + 1          # footprint cap
    assert toks == base
    for a, b in zip(logits, base_logits):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_init_paged_cache_rejects_non_kv_archs():
    cfg = get_config("whisper-large-v3").reduced()
    with pytest.raises(ValueError):
        build_model(cfg).init_paged_cache(64)
    cfg = get_config("xlstm-350m").reduced()
    with pytest.raises(ValueError):
        build_model(cfg).init_paged_cache(64)
