"""Operations and bytes one decode step of Solar Open 2 needs, from its
shapes and from what the step's own counters say it touched: the
yardstick of ``decode_step_roofline.solar``.

What the mathematics requires, never what a program executed: an expert's
matrices count only if a token was routed to it in that step; of the key
and value rings the rows of the valid positions; of a KDA layer its
delta-rule state, **read once and written once** a rider (the step's
``delta_state_kib`` counts exactly that), and the convolution's three rows,
read and written.  Embedding rows (one a slot) are left out; the head is
read once.  ``shape`` is the configuration's published keys with
``router_width`` (the router's experts), ``held`` (experts here) and
``weight_bytes`` / ``cache_bytes`` (2 for bfloat16).
"""

# floating-point operations a decode step spends on one number of a
# head's state: the decay (1), the two products over K, S^T k and S^T q
# (2 each), and the rank-one write k u^T added in (2)
STATE_FLOPS = 7


def _layers(s):
    gqa = len(s["gqa_layers"])
    return s["num_hidden_layers"] - gqa, gqa


def _kda_width(s):
    la = s["linear_attn_config"]
    return la["num_heads"] * la["head_dim"]


def kda_params(s):
    """``W_qkv``, the taps, the decay's and the gate's low-rank pairs
    (rank ``head_dim``), ``W_b``, ``W_o``."""
    d, HK = s["hidden_size"], _kda_width(s)
    la = s["linear_attn_config"]
    rank = la["head_dim"]
    return 3 * d * HK + la["short_conv_kernel_size"] * 3 * HK \
        + 2 * (d * rank + rank * HK) + d * la["num_heads"] + HK * d


def gqa_params(s):
    """``W_q``, ``W_k``, ``W_v``, the gate, ``W_o``."""
    d = s["hidden_size"]
    HD = s["num_attention_heads"] * s["head_dim"]
    return 3 * d * HD + 2 * d * s["num_key_value_heads"] * s["head_dim"]


def expert_params(s):
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def outside_experts_params(s):
    """Matrix elements a step reads whatever it routes: every layer's
    mixer, router and shared expert, the head."""
    kda, gqa = _layers(s)
    d = s["hidden_size"]
    return kda * kda_params(s) + gqa * gqa_params(s) \
        + s["num_hidden_layers"] * (d * s["router_width"]
                                    + s["n_shared_experts"] * expert_params(s)) \
        + d * s["vocab_size"]


def weight_params(s):
    """Every matrix element held here (norms, the decay's parameters and
    the selection bias left out: under a millionth); the embedding and the
    head each count."""
    return outside_experts_params(s) + s["hidden_size"] * s["vocab_size"] \
        + s["num_hidden_layers"] * s["held"] * expert_params(s)


def decode_step_bytes(s, active, experts_touched, valid_positions,
                      state_kib):
    """``active``: slots that ride; ``experts_touched``: held experts with
    a token, summed over the expert layers; ``valid_positions``: cached
    positions read, summed over slots and attention layers;
    ``state_kib``: delta-rule state read and written, as the step's
    counters give them."""
    kda, _gqa = _layers(s)
    conv_rows = (s["linear_attn_config"]["short_conv_kernel_size"] - 1) \
        * 3 * _kda_width(s)
    return s["weight_bytes"] * (outside_experts_params(s)
                                + experts_touched * expert_params(s)) \
        + s["cache_bytes"] * (
            valid_positions * 2 * s["num_key_value_heads"] * s["head_dim"]
            + active * kda * 2 * conv_rows) \
        + state_kib * 1024


def decode_step_flops(s, active, pairs, valid_positions):
    """``active`` tokens through everything outside the experts and every
    KDA state, ``pairs`` (token, expert) pairs through an expert each,
    every query head against the key and the value of every valid
    position."""
    kda, _gqa = _layers(s)
    la = s["linear_attn_config"]
    state = la["num_heads"] * la["head_dim"] ** 2
    return 2 * (active * outside_experts_params(s)
                + pairs * expert_params(s)
                + valid_positions * 2 * s["num_attention_heads"]
                * s["head_dim"]) \
        + active * kda * state * STATE_FLOPS
