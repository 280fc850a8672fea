"""Pretraining batches from a seed: ``distinct_batches`` full-length
batches that the job cycles through, as a packed phase-2 pretraining
corpus gives them (every sequence ``seq_length`` long, two segments,
``max_predictions`` masked positions, all weighted)."""
import numpy as onp


def make(traffic, shape, seed, batch, count=None):
    """[(data, labels), ...] of numpy arrays in the order
    ``BERTModel.forward`` and the loss take them."""
    rng = onp.random.RandomState(seed)
    l, m, v = shape["seq_length"], shape["max_predictions"], \
        shape["vocab_size"]
    out = []
    for _ in range(count or traffic["distinct_batches"]):
        split = rng.randint(l // 4, 3 * l // 4, (batch, 1))
        positions = onp.stack([onp.sort(rng.choice(l, m, replace=False))
                               for _ in range(batch)])
        data = (rng.randint(0, v, (batch, l)).astype("int32"),
                (onp.arange(l)[None] >= split).astype("int32"),
                onp.full((batch,), l, dtype="float32"),
                positions.astype("int32"))
        labels = (rng.randint(0, v, (batch, m)).astype("int32"),
                  onp.ones((batch, m), dtype="float32"),
                  rng.randint(0, 2, (batch,)).astype("int32"))
        out.append((data, labels))
    return out
