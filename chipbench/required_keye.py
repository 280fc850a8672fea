"""Operations and bytes one decode step of Keye-VL-2.0's language model
needs, from its shapes and from what the step's own counters say it
touched: the yardstick of ``decode_step_roofline.keye``.

What the mathematics requires, never what a program executed: an expert's
matrices count only if a token was routed to it in that step; of the
indexer's ring the keys of the valid positions (64 numbers each, whatever
stride they are stored at); of the key and value rings the rows the
selection kept, whatever a form reads beyond them.  Embedding rows (one a
slot) are left out.  ``shape`` is the configuration's published keys with
``held`` (experts here) and ``weight_bytes`` / ``cache_bytes`` (2 for
bfloat16).
"""


def _kv_row(s):
    return s["num_key_value_heads"] * s["head_dim"]


def _indexer(s):
    sa = s["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"]


def attention_params(s):
    """Matrix elements one token multiplies in a layer's attention: q and
    the output at ``heads x head_dim``, k and v at the key/value heads',
    and the indexer's three projections."""
    d = s["hidden_size"]
    hi, di = _indexer(s)
    wide = s["num_attention_heads"] * s["head_dim"]
    return 2 * d * wide + 2 * d * _kv_row(s) + d * (hi * di + di + hi)


def expert_params(s):
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def outside_experts_params(s):
    """Matrix elements a step reads whatever it routes: attention, indexer
    and router of every layer, and the head."""
    d = s["hidden_size"]
    return s["num_hidden_layers"] * (attention_params(s)
                                     + d * s["num_experts"]) \
        + d * s["vocab_size"]


def weight_params(s):
    """Every matrix element held here (norms and the indexer's LayerNorm
    left out: under a millionth), the embedding included."""
    return outside_experts_params(s) \
        + s["num_hidden_layers"] * s["held"] * expert_params(s) \
        + s["vocab_size"] * s["hidden_size"]


def decode_step_bytes(s, experts_touched, valid_positions,
                      selected_positions):
    """``experts_touched``: held experts with a token, summed over the
    layers; ``valid_positions`` / ``selected_positions``: summed over slots
    and layers, as the step's counters give them."""
    _hi, di = _indexer(s)
    return s["weight_bytes"] * (outside_experts_params(s)
                                + experts_touched * expert_params(s)) \
        + s["cache_bytes"] * (valid_positions * di
                              + selected_positions * 2 * _kv_row(s))


def decode_step_flops(s, active, pairs, valid_positions, selected_positions):
    """``active`` tokens through everything outside the experts, ``pairs``
    (token, expert) pairs through an expert each, the indexer's heads
    against every valid key, every query head against the key and the
    value of every selected position."""
    hi, di = _indexer(s)
    return 2 * (active * outside_experts_params(s)
                + pairs * expert_params(s)
                + valid_positions * hi * di
                + selected_positions * 2 * s["num_attention_heads"]
                * s["head_dim"])
