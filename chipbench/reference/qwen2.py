"""Reference of ``model_type`` ``qwen2``: a Qwen2 decoder, its leaves and
its operation counts.  It never imports the program.

Leaves (L layers stacked on the leading axis), made by ``weights.make``
from ``shapes`` and ``init``:
  embed (V, d) · wq (L, d, H*hd) · wk, wv (L, d, KV*hd) · wo (L, H*hd, d)
  w_gate, w_up (L, d, F) · w_down (L, F, d) · head (d, V) when untied
  attn_norm, mlp_norm (L, d) · final_norm (d,)

``Ref`` is straight ``jax.numpy`` over those leaves: no kernels, no
cache, no packing.  Every matrix product runs at ``Precision.HIGHEST``
in float32; ``dtype=bfloat16`` computes the same thing in bfloat16 at
the default precision, the control.

Published description followed: pre-norm blocks, rotary embeddings on
the two halves of each head, grouped-query attention (query head i reads
key/value head i // (H / KV)), SwiGLU, causal softmax over the published
vocabulary.  Departures of the system under test that the reference
follows, so that the comparison sees the system's own arithmetic:

- no q/k/v biases (Qwen2 publishes them; the system has none);
- tied embeddings where the configuration file says so.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.grpo import Policy

EPS = 1e-6


# ---- leaves ------------------------------------------------------------------

def shapes(c: dict) -> dict:
    """Leaf name -> shape for a configuration file's dict ``c``."""
    d, f, v, n = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    hd = d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    out = {"embed": (v, d), "wq": (n, d, q), "wk": (n, d, kv),
           "wv": (n, d, kv), "wo": (n, q, d), "w_gate": (n, d, f),
           "w_up": (n, d, f), "w_down": (n, f, d),
           "attn_norm": (n, d), "mlp_norm": (n, d), "final_norm": (d,)}
    if not c["tie_word_embeddings"]:
        out["head"] = (d, v)
    return out


def init(leaf: str, key, shape):
    """Leaf ``leaf`` of ``shape`` drawn from ``key``, in float32."""
    if leaf.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "embed":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


# ---- forward -----------------------------------------------------------------

class Ref(Policy):
    """The reference model of configuration ``c`` in ``dtype``."""

    def __init__(self, c: dict, dtype=jnp.float32):
        super().__init__(c, dtype)
        self.hd = c["hidden_size"] // c["num_attention_heads"]

    def _mm(self, x, w):
        return jnp.matmul(x, w, precision=self.prec,
                          preferred_element_type=jnp.float32).astype(self.dtype)

    def _norm(self, x, scale):
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + EPS)
        return (xf * scale.astype(jnp.float32)).astype(self.dtype)

    def _rope(self, x, pos):
        hd = x.shape[-1]
        inv = self.c["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, :, None, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(self.dtype)

    def hidden(self, w, tokens):
        """Final hidden states (B, S, d) of right-padded ``tokens``."""
        c, hd = self.c, self.hd
        b, s = tokens.shape
        h_, kv_ = c["num_attention_heads"], c["num_key_value_heads"]
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        causal = jnp.tril(jnp.ones((s, s), bool))
        h = w["embed"][tokens]
        for i in range(c["num_hidden_layers"]):
            x = self._norm(h, w["attn_norm"][i])
            q = self._rope(self._mm(x, w["wq"][i]).reshape(b, s, h_, hd), pos)
            k = self._rope(self._mm(x, w["wk"][i]).reshape(b, s, kv_, hd), pos)
            v = self._mm(x, w["wv"][i]).reshape(b, s, kv_, hd)
            k = jnp.repeat(k, h_ // kv_, axis=2)
            v = jnp.repeat(v, h_ // kv_, axis=2)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=self.prec,
                            preferred_element_type=jnp.float32) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p.astype(self.dtype), v,
                           precision=self.prec,
                           preferred_element_type=jnp.float32).astype(self.dtype)
            h = h + self._mm(a.reshape(b, s, h_ * hd), w["wo"][i])
            x = self._norm(h, w["mlp_norm"][i])
            g = self._mm(x, w["w_gate"][i]).astype(jnp.float32)
            u = self._mm(x, w["w_up"][i])
            h = h + self._mm((jax.nn.silu(g).astype(self.dtype) * u), w["w_down"][i])
        return self._norm(h, w["final_norm"])

    def logits(self, w, hidden):
        head = w["embed"].T if self.c["tie_word_embeddings"] else w["head"]
        return jnp.matmul(hidden, head, precision=self.prec,
                          preferred_element_type=jnp.float32)


# ---- operation counts (one multiply-add is two operations) --------------------

def _dims(c):
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    return d, hd, c["num_attention_heads"], c["num_key_value_heads"]


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    projections and the MLP of every layer, and the LM head (a tied
    embedding counts once, as the head; an untied embedding is a lookup)."""
    d, hd, h, kv = _dims(c)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d


def attention_flops(c: dict, n_keys_total: int) -> float:
    """Forward operations of attention scores and the weighted sum, for
    ``n_keys_total`` (query, key) pairs in all, in every layer: 2 matrix
    products x 2 x heads x head size per pair."""
    d, hd, h, kv = _dims(c)
    return 4.0 * h * hd * n_keys_total * c["num_hidden_layers"]
