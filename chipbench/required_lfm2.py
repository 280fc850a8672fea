"""Operations and bytes one decode step of LFM2-MoE needs, from its shapes
and from what the step's own counters say it touched: the yardstick of
``decode_step_roofline.lfm2``.

What the mathematics requires, never what a program executed: an expert's
matrices count only if a token was routed to it in that step; of the key
and value rings the rows of the valid positions; of a conv layer its
state, read and written, for the slots that ride.  Embedding rows (one a
slot) are left out; the tied head is read once, as the head.  ``shape`` is
the configuration's published keys with ``held`` (experts here) and
``weight_bytes`` / ``cache_bytes`` (2 for bfloat16).
"""


def _layers(s):
    conv = s["layer_types"].count("conv")
    return conv, len(s["layer_types"]) - conv


def _kv_row(s):
    return s["num_key_value_heads"] * s["hidden_size"] \
        // s["num_attention_heads"]


def conv_params(s):
    """``W_in`` [d, 3d], ``W_out`` [d, d] and the taps."""
    d = s["hidden_size"]
    return 4 * d * d + s["conv_L_cache"] * d


def attention_params(s):
    d = s["hidden_size"]
    return 2 * d * d + 2 * d * _kv_row(s)


def expert_params(s):
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def outside_experts_params(s):
    """Matrix elements a step reads whatever it routes: every layer's
    operator, the dense layers' feed-forward, each expert layer's router,
    the head."""
    d = s["hidden_size"]
    conv, attn = _layers(s)
    dense = s["num_dense_layers"]
    moe = s["num_hidden_layers"] - dense
    return conv * conv_params(s) + attn * attention_params(s) \
        + dense * 3 * d * s["intermediate_size"] \
        + moe * d * s["num_experts"] + d * s["vocab_size"]


def weight_params(s):
    """Every matrix element held here (norms and the selection bias left
    out: under a millionth); the head is the embedding and counts once."""
    moe = s["num_hidden_layers"] - s["num_dense_layers"]
    return outside_experts_params(s) + moe * s["held"] * expert_params(s)


def decode_step_bytes(s, active, experts_touched, valid_positions):
    """``active``: slots that ride; ``experts_touched``: held experts with
    a token, summed over the expert layers; ``valid_positions``: cached
    positions read, summed over slots and attention layers, as the step's
    counters give them."""
    conv, _attn = _layers(s)
    return s["weight_bytes"] * (outside_experts_params(s)
                                + experts_touched * expert_params(s)) \
        + s["cache_bytes"] * (
            valid_positions * 2 * _kv_row(s)
            + active * conv * s["conv_L_cache"] * s["hidden_size"])


def decode_step_flops(s, active, pairs, valid_positions):
    """``active`` tokens through everything outside the experts, ``pairs``
    (token, expert) pairs through an expert each, every query head against
    the key and the value of every valid position."""
    head = s["hidden_size"] // s["num_attention_heads"]
    return 2 * (active * outside_experts_params(s)
                + pairs * expert_params(s)
                + valid_positions * 2 * s["num_attention_heads"] * head)
