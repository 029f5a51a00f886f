"""Plain reference of a Qwen2 decoder and of its GRPO step.

Straight ``jax.numpy`` over the leaves of ``weights.make``: no kernels,
no cache, no packing.  Every matrix product runs at
``Precision.HIGHEST`` in float32.  ``dtype=bfloat16`` computes the same
thing in bfloat16 at the default precision: the control, which the
comparison must fail.

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
import numpy as np

EPS = 1e-6


class Ref:
    """The reference model of configuration ``c`` in ``dtype``."""

    def __init__(self, c: dict, dtype=jnp.float32):
        self.c = c
        self.dtype = jnp.dtype(dtype)
        self.prec = (jax.lax.Precision.HIGHEST if self.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        self.hd = c["hidden_size"] // c["num_attention_heads"]
        self._logprobs = jax.jit(self._logprobs_fn)
        self.jit_logits = jax.jit(lambda w, x: self.logits(w, self.hidden(w, x)))

    def cast(self, w):
        return {k: v.astype(self.dtype) for k, v in w.items()}

    # ---- forward -----------------------------------------------------------
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

    def _logprobs_fn(self, w, tokens):
        """(B, S): entry t is log p(tokens[t] | tokens[:t]); entry 0 is 0."""
        lg = self.logits(w, self.hidden(w, tokens))[:, :-1]
        lp = jax.nn.log_softmax(lg, axis=-1)
        lp = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.concatenate([jnp.zeros_like(lp[:, :1]), lp], axis=1)

    def logprobs(self, w, seqs, pad_to: int, rows: int) -> list:
        """Per-token log-probabilities of each token list in ``seqs``, in
        blocks of ``rows`` sequences right-padded to ``pad_to``."""
        out = []
        for blk in _blocks(seqs, pad_to, rows):
            lp = np.asarray(self._logprobs(w, jnp.asarray(blk.tokens)), np.float64)
            out += [lp[j, :n] for j, n in enumerate(blk.lens)]
        return out


class _Block:
    def __init__(self, tokens, lens):
        self.tokens, self.lens = tokens, lens


def _blocks(seqs, pad_to, rows):
    for i in range(0, len(seqs), rows):
        part = seqs[i:i + rows]
        tok = np.zeros((rows, pad_to), np.int32)
        for j, s in enumerate(part):
            tok[j, :len(s)] = s
        yield _Block(tok, [len(s) for s in part])


# ---- GRPO step (decoupled PPO, AdamW) --------------------------------------

def group_advantages(rewards, groups, eps=1e-5):
    """GRPO: reward standardised within its prompt's group, then across the
    whole batch."""
    r = np.asarray(rewards, np.float64)
    g = np.asarray(groups)
    adv = np.zeros_like(r)
    for k in np.unique(g):
        m = g == k
        adv[m] = (r[m] - r[m].mean()) / (r[m].std() + eps)
    return (adv - adv.mean()) / (adv.std() + eps)


def microbatches(lens, capacity):
    """Algorithm 1 of the AReaL paper: longest first, each sequence into
    the open micro-batch with the fewest sequences that still has room,
    else a new one."""
    order = sorted(range(len(lens)), key=lambda i: -lens[i])
    groups, loads = [], []
    for i in order:
        fits = [j for j in range(len(groups)) if loads[j] + lens[i] <= capacity]
        if not fits:
            groups.append([i])
            loads.append(lens[i])
        else:
            j = min(fits, key=lambda j: len(groups[j]))
            groups[j].append(i)
            loads[j] += lens[i]
    return groups


class GRPO:
    """The trainer's step, written plainly: advantages, Algorithm 1
    micro-batches, ``minibatches`` sequential AdamW updates of the
    decoupled PPO objective (AReaL Eq. 5)."""

    def __init__(self, ref: Ref, hp: dict, pad_to: int, rows: int):
        self.ref, self.hp, self.pad_to, self.rows = ref, hp, pad_to, rows
        self._grad = jax.jit(jax.value_and_grad(self._loss_fn))
        self._acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=0)
        self._adam = jax.jit(self._adam_fn, donate_argnums=(0, 1, 2, 3))

    def _loss_fn(self, w, tokens, mask, adv, prox, behav, coef):
        lp = self.ref._logprobs_fn(w, tokens)
        bw = jnp.clip(jnp.exp(prox - behav), 0.0, self.hp["ratio_clip"])
        u = jnp.exp(lp - prox)
        eps = self.hp["clip_eps"]
        surr = jnp.minimum(u * adv[:, None],
                           jnp.clip(u, 1 - eps, 1 + eps) * adv[:, None])
        per_seq = jnp.sum(bw * surr * mask, axis=1)
        return jnp.sum(coef * per_seq)

    def _adam_fn(self, w, g, m, v, step):
        hp = self.hp
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        sc = jnp.minimum(1.0, hp["grad_clip"] / (norm + 1e-12))
        b1, b2 = hp["beta1"], hp["beta2"]
        bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
        nw, nm, nv = {}, {}, {}
        for k in w:
            gk = g[k] * sc
            nm[k] = b1 * m[k] + (1 - b1) * gk
            nv[k] = b2 * v[k] + (1 - b2) * gk * gk
            upd = (nm[k] / bc1) / (jnp.sqrt(nv[k] / bc2) + hp["adam_eps"])
            nw[k] = w[k] - hp["lr"] * (upd + hp["weight_decay"] * w[k])
        return nw, nm, nv

    def step(self, w, state, seqs, masks, rewards, groups, prox, behav):
        """One trainer step on a batch.  ``seqs`` are token lists, ``masks``
        the per-token loss masks, ``prox`` and ``behav`` per-token
        log-probabilities.  Returns (weights, state, reported loss)."""
        adv = group_advantages(rewards, groups)
        mbs = microbatches([len(s) for s in seqs], self.hp["capacity"])
        splits = np.array_split(np.arange(len(mbs)), min(self.hp["minibatches"],
                                                         len(mbs)))
        losses = []
        for split in splits:
            idx, coef = [], []
            for j in split:
                count = sum(float(np.sum(masks[i])) for i in mbs[j])
                for i in mbs[j]:
                    idx.append(i)
                    coef.append(-1.0 / (len(split) * (count + 1e-8)))
            g_sum, loss = None, 0.0
            for lo in range(0, len(idx), self.rows):
                part = idx[lo:lo + self.rows]
                arrs = _pack([seqs[i] for i in part], [masks[i] for i in part],
                             [prox[i] for i in part], [behav[i] for i in part],
                             self.pad_to, self.rows)
                cf = np.zeros(self.rows, np.float32)
                cf[:len(part)] = coef[lo:lo + len(part)]
                a = np.zeros(self.rows, np.float32)
                a[:len(part)] = adv[part]
                val, g = self._grad(w, arrs[0], arrs[1], jnp.asarray(a),
                                    arrs[2], arrs[3], jnp.asarray(cf))
                loss += float(val)
                g_sum = g if g_sum is None else self._acc(g_sum, g)
            state["step"] += 1
            w, state["m"], state["v"] = self._adam(
                w, g_sum, state["m"], state["v"], jnp.float32(state["step"]))
            losses.append(loss)
        return w, state, float(np.mean(losses))


def adam_state(w):
    return {"m": {k: jnp.zeros_like(v) for k, v in w.items()},
            "v": {k: jnp.zeros_like(v) for k, v in w.items()}, "step": 0}


def _pack(seqs, masks, prox, behav, pad_to, rows):
    tok = np.zeros((rows, pad_to), np.int32)
    out = [np.zeros((rows, pad_to), np.float32) for _ in range(3)]
    for j, s in enumerate(seqs):
        n = len(s)
        tok[j, :n] = s
        out[0][j, :n] = masks[j]
        out[1][j, :n] = prox[j]
        out[2][j, :n] = behav[j]
    return [jnp.asarray(tok)] + [jnp.asarray(x) for x in out]
