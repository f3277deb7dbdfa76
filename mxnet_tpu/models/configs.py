"""Decoder-LM size ladder: tiny (CI/CPU) up to gpt2-small-ish.

The ladder exists so every consumer — tests, bench.py --transformer,
serving — names shapes the same way instead of re-inventing ad-hoc
dims.  ``flops_per_token`` uses the standard dense-training accounting
(6N weight-FLOPs + attention score/value terms, PaLM appendix B
convention, causal masking NOT halved) so MFU numbers are comparable
across published results.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class TransformerConfig:
    """The seven fields every consumer passes (positionally too) describe a
    GPT-2-style decoder; the fields after them, all with defaults that
    leave that graph as it is, select the block variants ``transformer_lm``
    can build: the norm (``layer`` | ``rms``), positions (``learned`` |
    ``rope`` | ``none``), the feed-forward (``gelu``: fc1/gelu/down with
    biases; ``swiglu``: down(silu(gate) * up), no bias), grouped key/value
    heads and per-head query/key normalisation, a pattern of sequence
    mixers per layer (``full_attention`` | ``sliding_attention``, which sees
    the last ``window`` positions | ``conv``, the gated short convolution |
    ``kda``, Kimi Delta Attention of ``n_heads`` heads of ``kda_head_dim``;
    both convolutions take ``conv_kernel`` taps; empty: full attention
    everywhere), heads of a size of their own
    (``head_dim``; 0: ``d_model / n_heads``), rotary parameters of a layer
    kind's own (``rope``: triples ``(kind, theta, yarn)`` with ``yarn``
    empty or ``(factor, original positions, beta_fast, beta_slow,
    attention_factor)``; a kind it does not name turns by ``rope_theta``),
    and sparse experts in place
    of the feed-forward from layer ``num_dense_layers`` on
    (``num_experts`` > 0): ``experts_held`` of them from ``expert_offset``
    live in this graph (0: all), each ``moe_d_ff`` wide, ``experts_per_tok``
    a token, scored by ``moe_score`` (``sigmoid`` under a selection bias |
    ``softmax``), the weights of the selected over ``sum + moe_weight_eps``
    times ``routed_scaling``, and beside them ``n_shared_experts`` experts of
    the same width that every token takes with weight 1.  ``attention`` =
    ``latent`` gives every attention layer (those of a pattern's
    ``full_attention`` kind) low-rank queries (``q_lora_rank``; 0: one
    full-rank product) and keys/values (``kv_lora_rank``) and heads of
    ``qk_nope_head_dim`` dims without position and ``qk_rope_head_dim``
    rotated ones (``position`` ``none``: turned by nothing), values of
    ``v_head_dim`` (``MultiHeadAttention``'s latent form).  ``mtp_layers``
    = 1 adds DeepSeek-V3's multi-token-prediction
    module after the last block (``transformer_lm``), its loss weighted by
    ``mtp_loss_weight``.  ``diffusion_block`` = b > 0 trains by block
    diffusion (BD3-LM's objective, SDAR's): the data is a document noised by
    blocks of b (``io.block_diffusion_noise``: ``diffusion_mask_id`` in
    place of the masked tokens), the label the clean document, both go
    through the layers as one sequence of ``2 seq_len`` positions under the
    block-diffusion mask, and the loss is ``block_diffusion_ce`` over the
    noised half (``transformer_lm``)."""
    name: str
    vocab_size: int
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    seq_len: int
    norm: str = "layer"
    norm_eps: float = 1e-5
    position: str = "learned"
    rope_theta: float = 10000.0
    ffn: str = "gelu"
    n_kv_heads: int = 0
    qk_norm: bool = False
    layer_types: tuple = ()
    conv_kernel: int = 3
    num_dense_layers: int = 0
    num_experts: int = 0
    experts_per_tok: int = 0
    experts_held: int = 0
    expert_offset: int = 0
    moe_d_ff: int = 0
    tie_head: bool = False
    head_dim: int = 0
    window: int = 0
    rope: tuple = ()
    moe_score: str = "sigmoid"
    attention: str = "full"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    moe_weight_eps: float = 1e-6
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.0
    kda_head_dim: int = 0
    diffusion_block: int = 0
    diffusion_mask_id: int = -1

    def __post_init__(self):
        if not self.head_dim and self.attention != "latent" \
                and self.d_model % self.n_heads:
            raise ValueError("d_model %d not divisible by n_heads %d"
                             % (self.d_model, self.n_heads))
        for field, known in (("norm", ("layer", "rms")),
                             ("position", ("learned", "rope", "none")),
                             ("ffn", ("gelu", "swiglu")),
                             ("moe_score", ("sigmoid", "softmax")),
                             ("attention", ("full", "latent"))):
            if getattr(self, field) not in known:
                raise ValueError("%s %r is not one of %s"
                                 % (field, getattr(self, field), known))
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types names %d layers of %d"
                             % (len(self.layer_types), self.n_layers))
        for kind in self.layer_types:
            if kind not in ("full_attention", "sliding_attention", "conv",
                            "kda"):
                raise ValueError("layer type %r is not full_attention, "
                                 "sliding_attention, conv or kda" % (kind,))
        if "kda" in self.layer_types and self.kda_head_dim < 1:
            raise ValueError("kda layers need kda_head_dim")
        if "sliding_attention" in self.layer_types and self.window < 1:
            raise ValueError("sliding_attention layers need a window")
        for kind, theta, yarn in self.rope:
            if not theta > 0 or len(yarn) not in (0, 5):
                raise ValueError("rope of %r is not a theta and a yarn of "
                                 "none or five: %r, %r" % (kind, theta, yarn))

        if self.attention == "latent" and (
                self.position not in ("rope", "none")
                or set(self.layer_types) - {"full_attention", "kda"}
                or self.n_kv_heads or self.qk_norm or self.head_dim
                or self.q_lora_rank < 0
                or min(self.kv_lora_rank, self.qk_nope_head_dim,
                       self.qk_rope_head_dim) < 1):
            raise ValueError(
                "latent attention takes rotary positions or none, its "
                "key/value rank (and a query rank or none) and the head's "
                "two parts, and no layer kinds but full_attention and kda, "
                "grouped heads, per-head norms or head_dim (the head is "
                "qk_nope_head_dim + qk_rope_head_dim)")
        if self.mtp_layers not in (0, 1) or (
                self.mtp_layers and self.layer_types):
            raise ValueError("one multi-token-prediction module or none, "
                             "and none under a layer pattern")
        b = self.diffusion_block
        if b and (b & (b - 1) or self.seq_len % b
                  or not 0 <= self.diffusion_mask_id < self.vocab_size
                  or self.position == "learned" or self.attention != "full"
                  or set(self.layer_types) - {"full_attention"}
                  or self.mtp_layers):
            raise ValueError(
                "block diffusion takes blocks of a power of two that divide "
                "seq_len, a mask id inside the vocabulary, rotary positions "
                "or none, full attention in every layer and no prediction "
                "module; got diffusion_block=%d, diffusion_mask_id=%d"
                % (b, self.diffusion_mask_id))

    def rope_of(self, kind: str):
        """(theta, yarn) the layers of ``kind`` turn their heads by."""
        for name, theta, yarn in self.rope:
            if name == kind:
                return float(theta), tuple(yarn)
        return self.rope_theta, ()

    def n_params(self) -> int:
        """Weight count of the matmul-bearing parameters of the GPT-2-style
        graph the first seven fields describe (embedding + per-block
        QKVO/FFN + untied LM head; norms excluded — noise)."""
        d, f, L, v = self.d_model, self.d_ff, self.n_layers, self.vocab_size
        return v * d + L * (4 * d * d + 2 * d * f) + d * v

    def flops_per_token(self) -> float:
        """Training (fwd+bwd) FLOPs per token: 6 per matmul weight plus
        the attention score/value matmuls, 12·L·T·d_model."""
        d, L, f, v = (self.d_model, self.n_layers, self.d_ff,
                      self.vocab_size)
        matmul_params = v * d + L * (4 * d * d + 2 * d * f)
        return 6.0 * matmul_params + 12.0 * L * self.seq_len * d


CONFIGS = {
    # CI / CPU smoke shape: compiles in seconds, exercises every layer
    "tiny": TransformerConfig("tiny", vocab_size=256, n_layers=2,
                              d_model=64, n_heads=4, d_ff=256, seq_len=64),
    # CPU bench shape: big enough that tokens/s has signal
    "mini": TransformerConfig("mini", vocab_size=1024, n_layers=4,
                              d_model=128, n_heads=4, d_ff=512,
                              seq_len=128),
    # single-chip dev shape
    "small": TransformerConfig("small", vocab_size=8192, n_layers=6,
                               d_model=384, n_heads=6, d_ff=1536,
                               seq_len=256),
    # gpt2-small-ish (124M): the chip target for bench.py --transformer
    "gpt2-small": TransformerConfig("gpt2-small", vocab_size=50257,
                                    n_layers=12, d_model=768, n_heads=12,
                                    d_ff=3072, seq_len=1024),
}


def get_config(name: str, **overrides) -> TransformerConfig:
    """Ladder lookup with field overrides (e.g. a shorter seq_len)."""
    from dataclasses import replace
    try:
        cfg = CONFIGS[name]
    except KeyError:
        raise KeyError("unknown transformer config %r (have: %s)"
                       % (name, ", ".join(sorted(CONFIGS))))
    return replace(cfg, **overrides) if overrides else cfg
