"""GPT-1 (Radford et al. 2018): token + learned position embeddings, a
post-LN causal transformer stack, a vocabulary projection.  One full
forward over a whole sequence gives the logits at every position, which is
what prefill-then-decode through a KV cache has to reproduce.  Departure
from the paper kept from the program under test: the projection has its
own weights and bias (``proj``), not the embedding's."""
import jax

from . import transformer as T


def logits(p, tokens, layers, heads, eps=1e-12):
    """(L,) token ids -> (L, vocab) float32 logits."""
    with jax.default_matmul_precision("highest"):
        l = tokens.shape[0]
        x = (p["embed.weight"][tokens]
             + p["encoder.position_weight"][:l])[None]
        x = T.encoder(x, p, layers, heads, True, eps)
        return T.project(x[0], p, "proj")
