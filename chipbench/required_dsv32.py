"""Operations and bytes one decode step of DeepSeek-V3.2-Exp needs, cut to
a chip's share, from its shapes and from what the step's own counters
say it touched: the yardstick of ``decode_step_roofline.dsv32``.

What the mathematics requires, never what a program executed: an expert's
matrices count only if a token was routed to it in that step; of the
indexer's ring the keys of the valid positions; of the latent ring the
rows the selection kept.  Embedding rows (one a slot) are left out.
``shape`` is the configuration's published keys with ``held`` (experts
here) and ``weight_bytes`` / ``cache_bytes`` (2 for bfloat16).
"""


def attention_params(s):
    """Matrix elements one token multiplies in a layer's attention in the
    absorbed (decode) form, the indexer's projections among them."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    n, r, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    ql, kvl = s["q_lora_rank"], s["kv_lora_rank"]
    mla = d * ql + ql * h * (n + r) + d * (kvl + r) + kvl * h * (n + dv) \
        + h * dv * d
    indexer = ql * s["index_n_heads"] * s["index_head_dim"] \
        + d * s["index_head_dim"] + d * s["index_n_heads"]
    return mla + indexer


def expert_params(s):
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def outside_experts_params(s):
    """Matrix elements a step reads whatever it routes: attention and
    indexer of every layer, the dense layers' feed-forward, each expert
    layer's router and shared expert, the head."""
    d = s["hidden_size"]
    layers, dense = s["num_hidden_layers"], s["first_k_dense_replace"]
    moe = layers - dense
    return layers * attention_params(s) \
        + dense * 3 * d * s["intermediate_size"] \
        + moe * (d * s["router_width"]
                 + s["n_shared_experts"] * expert_params(s)) \
        + d * s["vocab_size"]


def weight_params(s):
    """Every matrix element held here (norms and the selection bias left
    out: under a millionth), the embedding included."""
    moe = s["num_hidden_layers"] - s["first_k_dense_replace"]
    return outside_experts_params(s) + moe * s["held"] * expert_params(s) \
        + s["vocab_size"] * s["hidden_size"]


def decode_step_bytes(s, experts_touched, valid_positions,
                      selected_positions):
    """``experts_touched``: held experts with a token, summed over the
    expert layers; ``valid_positions`` / ``selected_positions``: summed
    over slots and layers, as the step's counters give them."""
    row = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return s["weight_bytes"] * (outside_experts_params(s)
                                + experts_touched * expert_params(s)) \
        + s["cache_bytes"] * (valid_positions * s["index_head_dim"]
                              + selected_positions * row)


def decode_step_flops(s, active, held_pairs, valid_positions,
                      selected_positions):
    """``active`` tokens through everything outside the experts,
    ``held_pairs`` (token, expert) pairs through an expert each, the
    indexer's heads against every valid key, every head's absorbed query
    and output against every selected row."""
    h = s["num_attention_heads"]
    row = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return 2 * (active * outside_experts_params(s)
                + held_pairs * expert_params(s)
                + valid_positions * s["index_n_heads"] * s["index_head_dim"]
                + selected_positions * h * (row + s["kv_lora_rank"]))
