"""The yardstick's readings, recorded while it was one dense-only module
per concern, must come out bit for bit the same now that each
architecture's part lives in ``bench/arch/<arch>.py`` and each Pallas
kernel's count in ``bench/kernels/<kernel>.py``.

``golden.json`` holds the readings on the CPU: the weight layout; digests
of the drawn weights and of the reference's float32 and fp8 logits at the
tiny size; operation and byte counts at four contexts (the last past the
window); and ``hlo.parse``'s contractions of the recorded programs in
``bench/fixtures/hlo``: the synthetic one of ``test_units``, the tiny
cell's three step programs compiled for the CPU, the danube cell's three
step programs as a traced run compiled them on a TPU v5e, and, compiled
for a described v5e, an ``sq_matmul`` call, an ``sq_paged_attn`` call and
the 24-layer danube decode step.  (Source paths are cut out of the
recorded programs; nothing reads them.)
"""
import functools
import gzip
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import arch, checks, flops, harness, hlo, reference, weights
from bench.tests.test_units import HLO as SYNTHETIC

GOLDEN = json.load(open(os.path.join(os.path.dirname(__file__),
                                     "golden.json")))
HLO_DIR = os.path.join(harness.ROOT, "bench", "fixtures", "hlo")
TINY = sorted(GOLDEN["weights"])


def _cfg(name):
    return dict(GOLDEN["configs"][name], arch="dense")


def _digest(a):
    a = np.asarray(a)
    return hashlib.sha256(a.tobytes()
                          + str((a.shape, a.dtype)).encode()).hexdigest()


def _hlo_text(name):
    if name == "synthetic":
        return SYNTHETIC
    with gzip.open(os.path.join(HLO_DIR, name + ".hlo.gz"), "rt") as f:
        return f.read()


@pytest.mark.parametrize("name", sorted(GOLDEN["layout"]))
def test_layout_is_unchanged(name):
    got = {p: [list(s), d] for p, (s, d) in weights.layout(_cfg(name)).items()}
    assert got == GOLDEN["layout"][name]


@pytest.mark.parametrize("name", TINY)
def test_drawn_weights_are_unchanged(name):
    cfg, want = _cfg(name), GOLDEN["weights"][name]
    key = weights.base_key(GOLDEN["seed"])
    tree = jax.jit(functools.partial(weights.init, cfg))(key)
    got = {"/".join(str(getattr(k, "key", k)) for k in path): _digest(a)
           for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == want["init"]
    layer = jax.jit(functools.partial(weights.layer, cfg), static_argnums=1)
    got = [{p: _digest(a) for p, a in layer(key, prefix, i).items()}
           for prefix, i in arch.of(cfg).layers(cfg)]
    assert got == want["layer"]
    top = jax.jit(functools.partial(weights.top, cfg))(key)
    assert {p: _digest(a) for p, a in top.items()} == want["top"]


@pytest.mark.parametrize("name", TINY)
def test_reference_and_control_logits_are_unchanged(name):
    cfg, want = _cfg(name), GOLDEN["logits"][name]
    key = weights.base_key(GOLDEN["seed"])
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab"], n, dtype=np.int32)
            for n in want["lengths"]]
    f32 = checks.reference_logits(cfg, key, seqs)
    fp8 = checks.reference_logits(cfg, key, seqs, ar=reference.FP8)
    assert [_digest(x) for x in f32] == want["f32"]
    assert [_digest(x) for x in fp8] == want["fp8"]


@pytest.mark.parametrize("name", sorted(GOLDEN["flops"]["by_config"]))
def test_operation_and_byte_counts_are_unchanged(name):
    cfg, want = _cfg(name), GOLDEN["flops"]["by_config"][name]
    ctx = GOLDEN["flops"]["contexts"]
    pk = flops.peaks(GOLDEN["flops"]["device"])
    assert [flops.forward_flops(cfg, c, True) for c in ctx] == \
        want["forward_logits"]
    assert [flops.forward_flops(cfg, c, False) for c in ctx] == want["forward"]
    assert flops.logits_flops(cfg) == want["logits"]
    assert [flops.paged_attn_least_s(cfg, [c], pk) for c in ctx] == \
        want["paged_attn_least_s"]
    assert flops.paged_attn_least_s(cfg, ctx, pk) == \
        want["paged_attn_least_s.all"]
    assert flops.paged_attn_least_s(cfg, ctx, pk, itemsize=4) == \
        want["paged_attn_least_s.fp32"]


@pytest.mark.parametrize("name", sorted(GOLDEN["hlo"]))
def test_hlo_contractions_are_unchanged(name):
    prog = hlo.parse(_hlo_text(name))
    want = GOLDEN["hlo"][name]
    got = sorted([c.name, c.kind, c.flops, [list(o) for o in c.operands],
                  c.count] for c in prog.contractions)
    assert got == want["contractions"]
    assert prog.kernel_of == want["kernel_of"]
    assert prog.unknown_trip_counts == want["unknown_trip_counts"]
    assert prog.flops() == want["flops"]
    assert prog.square_flops() == want["square_flops"]
    assert prog.unknown == []
