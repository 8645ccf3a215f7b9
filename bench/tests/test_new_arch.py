"""A new architecture, or a new Pallas kernel, comes as new files only:
the harness finds ``bench/arch/<arch>.py`` by the name a configuration
gives, fails before any work where there is none, and ``bench/hlo.py``
counts a kernel by its ``bench/kernels/<kernel>.py`` and lists a kernel
that has none instead of counting it as nothing."""
import base64
import functools
import gzip
import os
import shutil

import jax
import numpy as np
import pytest

from bench import arch, harness, hlo, weights
from bench.tests import tiny
from bench.tests.test_units import HLO

HLO_DIR = os.path.join(harness.ROOT, "bench", "fixtures", "hlo")


def test_a_new_architecture_needs_only_new_files(tmp_path, monkeypatch):
    entry, cfg, mix, limits = tiny.serve_cell()
    shutil.copy(arch.path("dense"), tmp_path / "dense_copy.py")
    monkeypatch.setattr(arch, "DIR", str(tmp_path))
    cell = (entry, dict(cfg, arch="dense_copy"), mix, limits)
    res, rec = tiny.run(tiny.SERVE, cell)
    assert res["correct"], res["checks"]
    assert rec.model_flops > 0 and rec.notes["served"]
    assert arch.load("dense_copy").__file__ == str(tmp_path / "dense_copy.py")
    with pytest.raises(FileNotFoundError):      # the run found nothing else
        arch.load("dense")


def test_a_layer_of_its_own_draws_whole_leaves():
    """A layer whose leaves carry no layer axis (``layers`` gives it index
    None, as a leading dense layer may) draws them whole, as ``init``
    draws a leaf outside every stack."""
    cfg = weights.model(tiny.serve_cell()[1])
    key = weights.base_key(2 ** 33 + 7)
    tree = jax.jit(functools.partial(weights.init, cfg))(key)
    own = jax.jit(functools.partial(weights.layer, cfg),
                  static_argnums=1)(key, "final_norm/", None)
    assert list(own) == ["scale"]
    assert np.array_equal(own["scale"], tree["final_norm"]["scale"])


@pytest.mark.parametrize("name", ["no_such_arch", None])
def test_an_unknown_architecture_fails_before_any_work(name):
    entry, cfg, mix, limits = tiny.serve_cell()
    cfg = dict(cfg, arch=name) if name else \
        {k: v for k, v in cfg.items() if k != "arch"}
    started = []
    with pytest.raises(FileNotFoundError) as e:
        tiny.run(tiny.SERVE, (entry, cfg, mix, limits),
                 hooks={"engine": started.append})
    assert not started
    assert os.path.join("bench", "arch", f"{name or '<arch>'}.py") \
        in str(e.value)


def _body(name: bytes) -> str:
    return base64.b64encode(b"ML\xefR..." + name + b"\x00loc").decode()


def test_an_unlisted_pallas_kernel_is_reported_not_counted_as_zero():
    call = ("  %mystery.1 = f32[8,256]{1,0} custom-call(%lhs), "
            'custom_call_target="tpu_custom_call", backend_config={"custom_'
            f'call_config":{{"body":"{_body(b"mystery_kernel")}"}}}}\n')
    text = HLO.replace("  ROOT %dot.1", call + "  ROOT %dot.1")
    base, prog = hlo.parse(HLO), hlo.parse(text)
    assert base.unknown == [] and prog.unknown == ["mystery_kernel"]
    assert prog.kernel_of["mystery.1"] == "mystery_kernel"
    assert "mystery.1" not in {c.name for c in prog.contractions}
    assert prog.flops() == base.flops()
    # a real one: the fused 2-D convolution kernel has no count file
    with gzip.open(os.path.join(HLO_DIR, "v5e_sq_conv2d.hlo.gz"), "rt") as f:
        conv = hlo.parse(f.read())
    assert conv.unknown == ["sq_conv2d_kernel"] and not conv.contractions


def test_each_count_file_names_a_kernel_and_counts_it():
    kernels = hlo.counts()
    assert sorted(kernels) == ["cpm3_matmul", "cpm4_matmul", "sq_conv",
                               "sq_matmul", "sq_paged_attn"]
    for name, mod in kernels.items():
        assert mod.KERNEL.endswith("_kernel") and callable(mod.flops), name
    # the complex GEMMs, compiled for a described v5e at 128^3
    for name in ("cpm3_matmul", "cpm4_matmul"):
        short = name.split("_")[0]
        with gzip.open(os.path.join(HLO_DIR, f"v5e_{short}.hlo.gz"),
                       "rt") as f:
            prog = hlo.parse(f.read())
        assert prog.unknown == []
        [c] = prog.contractions
        assert (c.kind, c.flops, c.count) == (name, 8.0 * 128 ** 3, 1)
    s32, f32, bf16 = "s32", "f32", "bf16"
    assert kernels["sq_conv"].flops(((f32, (1031,)), (f32, (8,))),
                                    ((f32, (1024,)),)) == 2.0 * 1024 * 8
    assert kernels["sq_matmul"].flops(((f32, (2, 8, 256)),
                                       (f32, (2, 256, 512))),
                                      ((f32, (2, 8, 512)),)) == \
        2.0 * 2 * 8 * 256 * 512


def test_paged_attention_is_read_by_shape():
    count = hlo.counts()["sq_paged_attn"].flops
    B, KV, rows, hd, bs, nb, nblk = 8, 8, 4, 120, 16, 96, 769
    ops = [("s32", (B, nb)), ("f32", (B, KV, rows, hd)),
           ("s32", (B, rows, 1))] + [("s32", (nblk, 1, bs))] * 8 \
        + [("bf16", (nblk, bs, KV, hd))] * 16 + [("s32", (B, 2))]
    out = (("f32", (B, KV, rows, hd)),)
    want = 4.0 * B * KV * rows * hd * nb * bs
    assert count(ops, out) == want
    # reordered: only the tables keep their place before the bounds
    moved = ops[3:11] + ops[11:] + ops[2:3] + ops[1:2]
    assert count([ops[0]] + moved, out) == want
    # one query row a head: the query positions are (B, 1, 1)
    one = [("s32", (B, nb)), ("f32", (B, KV, 1, hd)), ("s32", (B, 1, 1)),
           ("s32", (nblk, 1, bs))]
    assert count(one, (("f32", (B, KV, 1, hd)),)) == \
        4.0 * B * KV * 1 * hd * nb * bs
    # a latent head: 16 query rows over one 576-wide K, 512-wide V pool
    lat = [("s32", (B, nb)), ("f32", (B, 1, 16, 576)), ("s32", (B, 16, 1)),
           ("s32", (nblk, 1, bs)), ("bf16", (nblk, bs, 1, 576))]
    assert count(lat, (("f32", (B, 1, 16, 512)),)) == \
        2.0 * B * 16 * nb * bs * (576 + 512)
    assert count(ops[:1], out) is None
