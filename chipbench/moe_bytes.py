"""HBM bytes a decode round of a routed (DeepSeek-V2 style) model must
read, from the configuration's shapes and the program's counters, in
bf16.  Only what no decode program can skip is counted: every weight
outside the routed experts (attention, norms, the dense layers' MLP,
routers, shared experts and the LM head; not the embedding, of which a
round reads one row a token), the routed experts its rows chose, and
the latent pages its rows attend.  Padding, relayouts, the dense view
and the arena copy are not."""
from __future__ import annotations

BF16 = 2


def _attn_params(c: dict) -> int:
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
            + H * vd * d + 2 * d + r)


def expert_bytes(c: dict) -> int:
    """One routed expert's gate, up and down projections."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * BF16


def nonexpert_weight_bytes(c: dict) -> int:
    """Every layer's weights outside its routed experts, the final norm
    and the LM head."""
    d, L, nd = c["hidden_size"], c["num_hidden_layers"], \
        c["first_k_dense_replace"]
    shared = 3 * d * c["moe_intermediate_size"] * c["n_shared_experts"]
    dense = nd * (_attn_params(c) + 3 * d * c["intermediate_size"])
    routed = (L - nd) * (_attn_params(c) + shared
                         + d * c["n_routed_experts"])
    return (dense + routed + d + d * c["vocab_size"]) * BF16


def page_bytes(c: dict, chunk_tokens: int) -> int:
    """One page of the latent arena over all layers: ``chunk_tokens``
    positions of ckv and kpe."""
    return (c["num_hidden_layers"] * chunk_tokens
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16)


def decode_round_bytes(c: dict, chunk_tokens: int, experts_hit: float,
                       pages_attended: float) -> float:
    """A round whose rows hit ``experts_hit`` distinct experts, summed
    over the routed layers, and attend ``pages_attended`` pages."""
    return (nonexpert_weight_bytes(c) + experts_hit * expert_bytes(c)
            + pages_attended * page_bytes(c, chunk_tokens))
