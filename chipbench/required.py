"""Operations and bytes an algorithm needs, from its shapes alone.

The yardstick for ``mfu.train`` and the ``*_roofline`` metrics: what the
mathematics requires, never what a compiler executed (XLA's
``cost_analysis()`` counts flash-attention and remat recompute, which are
costs, not work).  One multiply-accumulate is two operations; a backward
pass through a matrix product is two more products of the same size.
"""


def encoder_layer_macs_per_token(cfg, context):
    """Multiply-accumulates one token costs in one post-LN transformer
    layer whose attention reads ``context`` keys: QKV and output
    projections, the two FFN products, QK^T and PV."""
    c, f = cfg["units"], cfg["hidden_size"]
    return 4 * c * c + 2 * c * f + 2 * context * c


def bert_step_flops(cfg, batch):
    """Required forward + backward operations of one BERT pretraining
    step over ``batch`` sequences of ``cfg['seq_length']`` tokens, all of
    them valid (the cell's batches are full).  Heads: the MLM transform
    and tied decoder on ``max_predictions`` positions a sequence, pooler
    and NSP classifier once a sequence.  Embedding look-ups, LayerNorm,
    GELU, softmax and the optimizer are left out: under 1% together."""
    c, v = cfg["units"], cfg["vocab_size"]
    seq, m = cfg["seq_length"], cfg["max_predictions"]
    per_seq = (seq * cfg["num_layers"]
               * encoder_layer_macs_per_token(cfg, seq)
               + m * (c * c + c * v)          # MLM transform + tied decoder
               + c * c + 2 * c)               # pooler + NSP classifier
    return 3 * 2 * per_seq * batch            # fwd 2/MAC, bwd twice fwd


def bert_step_bytes(cfg, batch, state_bytes_per_param=16):
    """Least bytes one step moves through HBM: every parameter read for
    forward and again for backward and its gradient written, in the
    storage type; the optimizer's float32 state (LAMB: mean, variance)
    read and written; each layer's input activation written by forward
    and read by backward.  Far below the compute time at these shapes:
    the step is compute-bound (the reader says so)."""
    n = bert_param_count(cfg)
    w = cfg["storage_bytes"]
    acts = 2 * batch * cfg["seq_length"] * cfg["units"] * w \
        * cfg["num_layers"]
    return n * (3 * w + state_bytes_per_param) + acts


def bert_param_count(cfg):
    c, f, v = cfg["units"], cfg["hidden_size"], cfg["vocab_size"]
    layer = 4 * c * c + 4 * c + 2 * c * f + f + c + 4 * c
    embed = (v + cfg["max_length"] + 2) * c + 2 * c
    heads = (c * c + c) + (c * c + c + 2 * c + v) + (2 * c + 2)
    return cfg["num_layers"] * layer + embed + heads


def lm_decode_weight_bytes(cfg):
    """Bytes of weights one decode step must read whatever the batch:
    every layer's matrices and the output projection.  Of the two
    embedding tables a step gathers one row a slot: left out."""
    c, f, v = cfg["units"], cfg["hidden_size"], cfg["vocab_size"]
    layer = 4 * c * c + 4 * c + 2 * c * f + f + c + 4 * c
    return (cfg["num_layers"] * layer + v * c + v) * cfg["weight_bytes"]


def lm_decode_step_bytes(cfg, context_tokens):
    """Least bytes one decode step reads: the weights once, and the keys
    and values of every valid position of every active slot, of which
    there are ``context_tokens`` in all, in the cache's type."""
    kv = 2 * cfg["num_layers"] * cfg["units"] * cfg["kv_bytes"]
    return lm_decode_weight_bytes(cfg) + context_tokens * kv


def lm_decode_step_flops(cfg, active, context_tokens):
    """Required operations of one decode step with ``active`` slots
    holding ``context_tokens`` valid positions in all."""
    c, f, v = cfg["units"], cfg["hidden_size"], cfg["vocab_size"]
    dense = cfg["num_layers"] * (4 * c * c + 2 * c * f) + c * v
    return 2 * (active * dense
                + cfg["num_layers"] * 2 * context_tokens * c)


def roofline_ms(flops, nbytes, peaks):
    """(least milliseconds at the chip's peaks, which peak bounds it)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (1e3 * max(t_flops, t_bytes),
            "compute" if t_flops >= t_bytes else "memory")
