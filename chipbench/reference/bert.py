"""BERT pretraining (Devlin et al. 2018, section 3.1 and appendix A.2) on
the plain reference encoder: the masked-LM logits at the masked positions
(transform, GELU, LayerNorm, decoder tied to the word embedding) and the
next-sentence logits from the pooled first token; the loss is masked-LM
cross-entropy averaged over the masked positions plus next-sentence
cross-entropy averaged over the batch.  Evaluation mode: no dropout."""
import jax
import jax.numpy as jnp

from . import transformer as T


def logits(p, batch, layers, heads, eps=1e-12):
    """(mlm logits (b, m, vocab), nsp logits (b, 2)), float32."""
    tokens, types, _valid, positions = batch          # sequences are full
    with jax.default_matmul_precision("highest"):
        l = tokens.shape[1]
        x = p["word_embed.weight"][tokens] \
            + p["token_type_embed.weight"][types] \
            + p["encoder.position_weight"][:l][None]
        x = T.layer_norm(x, p, "embed_ln", eps)
        x = T.encoder(x, p, layers, heads, False, eps)
        return _heads(p, x, positions, eps)


@jax.jit
def _heads(p, x, positions, eps):
    pooled = jnp.tanh(T.dense(x[:, 0], p, "pooler"))
    picked = jnp.take_along_axis(x, positions[:, :, None], 1)   # (b, m, c)
    h = T.layer_norm(T.gelu(T.dense(picked, p, "decoder_transform")), p,
                     "decoder_ln", eps)
    mlm = h @ p["word_embed.weight"].T + p["decoder_bias"]      # tied
    return mlm, T.dense(pooled, p, "classifier")


@jax.jit
def loss(mlm_logits, nsp_logits, mlm_labels, mlm_weights, nsp_labels):
    """(masked-LM loss, next-sentence loss)."""
    nsp_logp = jax.nn.log_softmax(nsp_logits)
    nsp = -jnp.take_along_axis(nsp_logp, nsp_labels[:, None], 1).mean()
    logp = jax.nn.log_softmax(mlm_logits)
    nll = -jnp.take_along_axis(logp, mlm_labels[:, :, None], 2)[..., 0]
    return (nll * mlm_weights).sum() / mlm_weights.sum(), nsp
