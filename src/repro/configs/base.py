"""Model / run configuration dataclasses.

One ``ModelConfig`` instance per assigned architecture lives in
``src/repro/configs/<id>.py``; ``reduced()`` derives the CPU smoke-test
config of the same family (small widths, few layers/experts, tiny vocab).

``ContractionPolicy`` is the per-call-site override table for the
fair-square einsum dispatch (:func:`repro.core.einsum.fs_einsum`):
``matmul_mode`` stays the whole-model default, and a policy selectively
pins individual contraction sites to a different mode -- e.g. square-form
FFN/logits GEMMs with the attention softmax path left on the multiplier
baseline (:data:`SQUARE_GEMMS_POLICY`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ModelConfig", "ShapeConfig", "SHAPES", "pad_vocab",
           "ContractionPolicy", "CONTRACTION_SITES", "GRAD_SITE_SUFFIXES",
           "SQUARE_GEMMS_POLICY"]


def pad_vocab(v: int, mult: int = 256) -> int:
    return v + (-v) % mult


# Call-site labels every fs_einsum-routed contraction reports (also the
# keys a ContractionPolicy may override).  Kept here so policies and the
# counter's by-site breakdown share one vocabulary.
CONTRACTION_SITES = (
    "dense",            # generic dense_apply fallback
    "attn_qkv",         # attention input projections
    "attn_out",         # attention output projection
    "attn_scores",      # q @ k^T (softmax path)
    "attn_pv",          # probs @ v (softmax path)
    "ffn",              # dense FFN up/gate/down
    "moe_router",       # MoE router logits
    "moe_expert",       # batched expert GEMMs
    "logits",           # LM head / vocab GEMM
    "loss",             # chunked-xent vocab GEMM
    "recurrent_gates",  # xLSTM / RG-LRU gate projections
    "recurrent_mix",    # recurrent state-mix contractions (scan bodies)
    "recurrent_proj",   # recurrent block dense projections
    "attn_paged",       # fused paged-attention read (serving decode path)
    "mla_absorb",       # latent attention: q_nope through W_UK into the latent
    "mla_unabsorb",     # latent attention: latent output through W_UV
    "moe_shared",       # MoE shared experts (one SwiGLU)
)

# The custom VJP of fs_einsum re-enters the dispatcher for both backward
# contractions under derived site names: ``<site>.bwd_x`` (dL/dx, the
# activation gradient) and ``<site>.bwd_w`` (dL/dW, the weight gradient).
# A policy may pin them independently of the forward site; an unpinned
# backward site inherits the forward site's override (see ``lookup``).
GRAD_SITE_SUFFIXES = (".bwd_x", ".bwd_w")


def _valid_site(site: str) -> bool:
    if site in CONTRACTION_SITES:
        return True
    for suf in GRAD_SITE_SUFFIXES:
        if site.endswith(suf) and site[:-len(suf)] in CONTRACTION_SITES:
            return True
    return False


@dataclasses.dataclass(frozen=True)
class ContractionPolicy:
    """Per-site contraction-mode overrides (hashable; safe as a jit-static
    config field).

    Resolution inside ``fs_einsum``: ``overrides[site]`` if present, else
    this policy's ``default`` if set, else the caller's ``mode`` argument
    (models pass ``cfg.matmul_mode``), else the process default.

    Backward sites (``<site>.bwd_x`` / ``<site>.bwd_w``, noted by the
    fs_einsum custom VJP) may be pinned explicitly -- pass them via a
    dict since dots are not identifier characters -- and otherwise
    inherit the forward site's override before falling to the default:

    >>> from repro.configs.base import ContractionPolicy
    >>> p = ContractionPolicy.of(default="square_virtual",
    ...                          attn_scores="standard")
    >>> p.lookup("attn_scores")
    'standard'
    >>> p.lookup("ffn")                  # falls through to the default
    'square_virtual'
    >>> p.lookup("attn_scores.bwd_x")    # backward inherits the fwd pin
    'standard'
    >>> q = ContractionPolicy.of(**{"ffn.bwd_w": "standard"})
    >>> q.lookup("ffn.bwd_w"), q.lookup("ffn.bwd_x"), q.lookup("ffn")
    ('standard', None, None)
    >>> ContractionPolicy.of(attn_scroes="standard")   # typo fails loudly
    Traceback (most recent call last):
        ...
    ValueError: unknown contraction site(s) ['attn_scroes']; expected names from ('dense', 'attn_qkv', 'attn_out', 'attn_scores', 'attn_pv', 'ffn', 'moe_router', 'moe_expert', 'logits', 'loss', 'recurrent_gates', 'recurrent_mix', 'recurrent_proj', 'attn_paged', 'mla_absorb', 'mla_unabsorb', 'moe_shared'), optionally suffixed with ('.bwd_x', '.bwd_w')
    """
    overrides: Tuple[Tuple[str, str], ...] = ()
    default: Optional[str] = None

    @classmethod
    def of(cls, default: Optional[str] = None,
           **sites: str) -> "ContractionPolicy":
        """Build a policy, validating site names and modes (a typo'd site
        would otherwise be silently ignored at lookup time)."""
        from repro.core.matmul import MODES
        bad = sorted(s for s in sites if not _valid_site(s))
        if bad:
            raise ValueError(f"unknown contraction site(s) {bad}; expected "
                             f"names from {CONTRACTION_SITES}, optionally "
                             f"suffixed with {GRAD_SITE_SUFFIXES}")
        for site, m in sites.items():
            if m not in MODES:
                raise ValueError(f"unknown mode {m!r} for site {site!r}; "
                                 f"expected one of {MODES}")
        if default is not None and default not in MODES:
            raise ValueError(f"unknown default mode {default!r}; expected "
                             f"one of {MODES}")
        return cls(tuple(sorted(sites.items())), default)

    def lookup(self, site: Optional[str]) -> Optional[str]:
        for s, m in self.overrides:
            if s == site:
                return m
        if site is not None and site.endswith(GRAD_SITE_SUFFIXES):
            base = site.rsplit(".", 1)[0]
            for s, m in self.overrides:
                if s == base:
                    return m
        return self.default


# Square-form GEMMs everywhere the operands are weights/activations, but
# the attention softmax path (scores / probs-times-values) kept on the
# multiplier baseline -- the mixed deployment the paper's ASIC story
# implies (weight GEMMs on squarer arrays, attention on the vector unit).
SQUARE_GEMMS_POLICY = ContractionPolicy.of(
    attn_scores="standard", attn_pv="standard")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    activation: str = "swiglu"       # ffn: swiglu | geglu | gelu
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    rope_theta: float = 10000.0
    window: Optional[int] = None     # sliding-window attention size
    attn_bias: bool = False
    ffn_bias: bool = False
    attn_logit_softcap: float = 0.0
    tie_embeddings: bool = True
    embed_scale: bool = True         # multiply input embeddings by sqrt(d)
    norm_eps: float = 1e-6           # RMSNorm epsilon
    # --- MoE ---
    n_experts: int = 0               # the router's width (published count)
    topk: int = 0
    capacity_factor: float = 1.25
    # experts this device holds (0: all n_experts, the capacity path of
    # models.moe.moe_apply_local); > 0 selects the dropless held path
    # (models.moe.moe_apply_held), which computes only the picks that land
    # on experts [0, experts_held) and leaves the rest to other devices
    experts_held: int = 0
    # softmax (the capacity path) | sigmoid with a choice-only bias (the
    # held path, DeepSeek-V3's noaux_tc)
    router_scoring: str = "softmax"
    norm_topk: bool = True           # renormalise the chosen gate weights
    routed_scale: float = 1.0        # routed experts' output scale
    n_shared_experts: int = 0        # shared experts, one SwiGLU of n * d_ff
    # --- leading dense layers before the scanned period ---
    first_k_dense: int = 0
    dense_d_ff: int = 0              # their SwiGLU width (0: d_ff)
    # --- latent attention (MLA); kv_lora_rank 0 = plain attention ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- layer pattern (cycled): attn | moe | mlstm | slstm | rglru | lattn ---
    block_pattern: Tuple[str, ...] = ("attn",)
    # --- recurrent (rg-lru / conv) ---
    rnn_width: int = 0
    conv_width: int = 4
    local_window: int = 2048
    # --- xlstm ---
    inner_factor: float = 2.0        # mLSTM d_inner = factor * d_model
    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0             # fixed frame count (whisper: 1500)
    # --- modality frontend stubs ---
    prefix_tokens: int = 0           # vlm: precomputed patch embeddings
    # --- numerics / execution ---
    dtype: str = "bfloat16"
    matmul_mode: str = "standard"    # standard | square_virtual | ...
    # per-site overrides of matmul_mode (see ContractionPolicy above)
    contraction_policy: Optional[ContractionPolicy] = None
    scan_layers: bool = True
    remat: str = "block"             # none | block
    loss_chunk: int = 2048
    attn_chunk_q: int = 2048
    attn_chunk_kv: int = 1024
    attn_block_skip: bool = False    # causal triangular block schedule
    attn_p_bf16: bool = False        # bf16 probability tensor in PV einsum
    tp_bf16_reduce: bool = False     # explicit bf16 psum on row-parallel GEMMs
    attn_fold_q: bool = False        # fold q-chunks into batch, shard over model
    max_seq: int = 524288
    source: str = ""                 # provenance note

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Every layer's kind: ``first_k_dense`` leading ``attn`` layers,
        then the pattern cycled over the rest."""
        pat = self.block_pattern
        k = self.first_k_dense
        return ("attn",) * k + tuple(pat[i % len(pat)]
                                     for i in range(self.n_layers - k))

    @property
    def is_subquadratic(self) -> bool:
        """True if decode-time state is O(1) in context length (SWA counts:
        its cache is window-bounded)."""
        kinds = set(self.layer_kinds)
        if "attn" in kinds or "moe" in kinds:
            return self.window is not None
        return True                  # recurrent/local-attn only

    def supports_shape(self, shape_name: str) -> bool:
        if shape_name == "long_500k":
            return self.is_subquadratic
        return True

    def reduced(self) -> "ModelConfig":
        """Smoke-test config of the same family (runs a fwd/train step on CPU)."""
        pat_len = len(self.block_pattern)
        n_layers = max(pat_len, 2 if pat_len == 1 else pat_len)
        mla = self.is_mla
        return dataclasses.replace(
            self,
            n_layers=n_layers + min(self.first_k_dense, 1),
            d_model=64,
            n_heads=max(2, min(4, self.n_heads)),
            n_kv_heads=max(1, min(2, self.n_kv_heads)),
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab=512,
            n_experts=4 if self.n_experts else 0,
            topk=2 if self.topk else 0,
            experts_held=4 if self.experts_held else 0,
            first_k_dense=min(self.first_k_dense, 1),
            dense_d_ff=128 if self.dense_d_ff else 0,
            kv_lora_rank=32 if mla else 0,
            qk_nope_head_dim=16 if mla else 0,
            qk_rope_head_dim=8 if mla else 0,
            v_head_dim=16 if mla else 0,
            # drop-free at smoke scale: capacity drops would make
            # prefill+decode legitimately diverge from the full forward
            capacity_factor=8.0 if self.n_experts else self.capacity_factor,
            rnn_width=64 if self.rnn_width else 0,
            encoder_layers=2 if self.encoder_layers else 0,
            encoder_seq=16 if self.encoder_seq else 0,
            prefix_tokens=4 if self.prefix_tokens else 0,
            window=min(self.window, 64) if self.window else None,
            local_window=32,
            dtype="float32",
            loss_chunk=64,
            attn_chunk_q=32,
            attn_chunk_kv=32,
            max_seq=256,
            scan_layers=self.scan_layers,
            remat="none",
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
