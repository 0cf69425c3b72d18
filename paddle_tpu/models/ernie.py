"""ERNIE-3.0-style MoE model family (BASELINE config #5).

Parity anchors: the reference trains ERNIE-MoE with
``incubate/distributed/models/moe/moe_layer.py:260 MoELayer`` (gshard
gate, global_scatter/gather all-to-all) inside a BERT-shaped encoder —
this file composes the same pieces from this repo: the transformer
encoder stack with every ``moe_every``-th FFN replaced by an MoELayer of
``ExpertLayer`` FFN experts (expert-parallel over the ``sep``/sharding
axis when the topology has one; dense single-chip otherwise).
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn, ops
from ..incubate.distributed.models.moe import ExpertLayer, MoELayer
from .bert import BertEmbeddings, _init_weights, additive_attention_mask


@dataclass
class ErnieMoeConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    num_experts: int = 8
    top_k: int = 2
    moe_every: int = 2          # every 2nd layer's FFN is MoE (ERNIE/GShard)
    capacity_factor: float = None   # None = gate default (1.2/2.4)
    fused_dispatch: bool = False    # Pallas fused MoE dispatch/combine
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.0
    attention_probs_dropout_prob: float = 0.0
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def ernie_moe_tiny_config(**kw):
    base = dict(vocab_size=1024, hidden_size=64, num_hidden_layers=4,
                num_attention_heads=2, intermediate_size=128,
                num_experts=4, max_position_embeddings=128)
    base.update(kw)
    return ErnieMoeConfig(**base)


def ernie_moe_base_config(**kw):
    return ErnieMoeConfig(**kw)


class _MoeFfnBlock(nn.Layer):
    """Post-LN encoder block with an MoE FFN (self-attn + MoE + residuals)."""

    def __init__(self, cfg: ErnieMoeConfig):
        super().__init__()
        self.attn = nn.MultiHeadAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            dropout=cfg.attention_probs_dropout_prob)
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.moe = MoELayer(
            cfg.hidden_size,
            [ExpertLayer(cfg.hidden_size, cfg.intermediate_size,
                         act=cfg.hidden_act)
             for _ in range(cfg.num_experts)],
            gate={"type": "gshard", "top_k": cfg.top_k},
            capacity_factor=cfg.capacity_factor,
            fused_dispatch=cfg.fused_dispatch)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, x, src_mask=None):
        x = self.ln1(x + self.attn(x, x, x, attn_mask=src_mask))
        return self.ln2(x + self.moe(x))


class _DenseBlock(nn.Layer):
    def __init__(self, cfg: ErnieMoeConfig):
        super().__init__()
        self.inner = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_attention_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout_prob, activation=cfg.hidden_act,
            attn_dropout=cfg.attention_probs_dropout_prob,
            act_dropout=0.0, normalize_before=False)

    def forward(self, x, src_mask=None):
        return self.inner(x, src_mask=src_mask)


class ErnieMoeModel(nn.Layer):
    def __init__(self, cfg: ErnieMoeConfig):
        super().__init__()
        self.config = cfg
        from .bert import BertConfig
        bcfg = BertConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            max_position_embeddings=cfg.max_position_embeddings,
            type_vocab_size=cfg.type_vocab_size,
            hidden_dropout_prob=cfg.hidden_dropout_prob,
            layer_norm_eps=cfg.layer_norm_eps)
        self.embeddings = BertEmbeddings(bcfg)
        blocks = []
        for i in range(cfg.num_hidden_layers):
            if cfg.moe_every and (i + 1) % cfg.moe_every == 0:
                blocks.append(_MoeFfnBlock(cfg))
            else:
                blocks.append(_DenseBlock(cfg))
        self.layers = nn.LayerList(blocks)
        _init_weights(self, cfg.initializer_range)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        # 2D padding mask → additive; broadcast 3D/4D (e.g. causal bool
        # for generation) passes through — shared helper with BERT
        attention_mask = additive_attention_mask(attention_mask)
        h = self.embeddings(input_ids, token_type_ids)
        for blk in self.layers:
            h = blk(h, src_mask=attention_mask)
        return h


def _ernie_mlm_head_loss(model, h, masked_lm_labels):
    """Gelu transform + LayerNorm + fused chunked CE over the tied
    decoder weights (the nested tail of ``forward_with_mlm_loss`` —
    transitively captured under ``to_static``)."""
    from .gpt import fused_mlm_cross_entropy

    h = model.layer_norm(nn.functional.gelu(model.transform(h)))
    return fused_mlm_cross_entropy(h, model.decoder_weight,
                                   model.decoder_bias, masked_lm_labels)


def _guard_nonfinite(loss):
    """Skip-step guard: a non-finite loss (overflow, bad batch) is
    replaced by zero so the gradient step is a no-op instead of
    poisoning the weights. Tensor-dependent Python branch — under
    ``to_static`` the capture layer lowers it to ``lax.cond``."""
    if ops.isfinite(loss):
        return loss
    return ops.zeros_like(loss)


class ErnieMoeForPretraining(nn.Layer):
    """Masked-LM head over the MoE encoder (tied embeddings)."""

    def __init__(self, model: ErnieMoeModel):
        super().__init__()
        self.ernie = model
        cfg = model.config
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size,
                                       epsilon=cfg.layer_norm_eps)
        self.decoder_weight = model.embeddings.word_embeddings.weight
        self.decoder_bias = self.create_parameter([cfg.vocab_size],
                                                  is_bias=True)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        h = self.ernie(input_ids, token_type_ids, attention_mask)
        h = self.layer_norm(nn.functional.gelu(self.transform(h)))
        return ops.matmul(h, self.decoder_weight, transpose_y=True) \
            + self.decoder_bias

    def gate_aux_loss(self):
        """Sum of the MoE gates' load-balance losses from the last
        forward (GShard/Switch aux loss), or None when no gate stashed
        one (eval mode, or already consumed)."""
        total = None
        for sub in self.ernie.sublayers(include_self=True):
            gate = getattr(sub, "gate", None)
            if gate is not None and getattr(gate, "has_loss", False):
                l = gate.get_loss()
                total = l if total is None else total + l
        return total

    def forward_with_mlm_loss(self, input_ids, masked_lm_labels,
                              token_type_ids=None, attention_mask=None,
                              aux_loss_weight=0.01, nonfinite_guard=False):
        """Fused MLM head + chunked CE (same design as
        bert.py forward_with_mlm_loss): the [B*S, V] fp32 logits buffer
        never materializes; ignore_index=-100 via the loss mask (see
        ``_ernie_mlm_head_loss``). In training mode the gates'
        load-balance aux loss is added with ``aux_loss_weight`` (GShard
        §2.2 — without it the router collapses onto few experts; the
        analysis deadcode pass flagged the previously
        computed-and-dropped aux loss). ``nonfinite_guard`` routes the
        loss through :func:`_guard_nonfinite` — a tensor-dependent
        nested helper whole-program ``to_static`` capture converts
        transitively (skip-step semantics on overflow)."""
        h = self.ernie(input_ids, token_type_ids, attention_mask)
        loss = _ernie_mlm_head_loss(self, h, masked_lm_labels)
        if self.training and aux_loss_weight:
            aux = self.gate_aux_loss()
            if aux is not None:
                loss = loss + aux_loss_weight * aux
        if nonfinite_guard:
            loss = _guard_nonfinite(loss)
        return loss


# ---------------------------------------------------------------------------
# eager generation oracle
# ---------------------------------------------------------------------------

class ErnieMoeGenerator:
    """Eager greedy generation oracle over :class:`ErnieMoeForPretraining`
    run as a CAUSAL decoder: each step re-runs the full forward under a
    lower-triangular bool mask and takes the argmax of the last
    position's LM-head logits. No KV cache, no compiled program —
    deliberately the simplest possible semantics: the token-for-token
    oracle for an incremental decoder of this model.

    Parity caveat (MoE capacity): incremental decode routes each token
    through the experts once, while full recompute routes the whole
    prefix every step — the two agree only when no token is capacity-
    dropped. Build the model with a no-drop ``capacity_factor``."""

    def __init__(self, model: ErnieMoeForPretraining):
        self.model = model
        self.cfg = model.ernie.config

    def __call__(self, input_ids, max_new_tokens=16):
        import numpy as np
        from .. import to_tensor

        # generate in eval mode but RESTORE the caller's mode after — a
        # mid-training validation sample must not silently flip the
        # gates into their eval (aux-loss-less) branch for good
        was_training = self.model.training
        self.model.eval()
        try:
            ids = np.asarray(input_ids, dtype=np.int64)
            if ids.ndim == 1:
                ids = ids[None, :]
            for _ in range(int(max_new_tokens)):
                S = ids.shape[1]
                causal = np.tril(np.ones((S, S), bool))[None, None]
                logits = self.model(to_tensor(ids),
                                    attention_mask=to_tensor(causal))
                last = np.asarray(logits.numpy())[:, -1]
                nxt = np.argmax(last, axis=-1).astype(np.int64)
                ids = np.concatenate([ids, nxt[:, None]], axis=1)
            return ids[:, -int(max_new_tokens):]
        finally:
            if was_training:
                self.model.train()
