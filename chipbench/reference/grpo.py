"""The GRPO step of the AReaL trainer, written plainly, over any
architecture's reference model.

``Policy`` is what the step reads of a model: an architecture's ``Ref``
(``reference/<model_type>.py``) subclasses it and writes the forward,
``hidden`` and ``logits``; ``Policy`` turns them into per-token
log-probabilities, in blocks of rows.  The step: GRPO advantages,
Algorithm 1 micro-batches, decoupled PPO (AReaL Eq. 5) and AdamW.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class Policy:
    """A reference model of configuration ``c`` in ``dtype``: float32 at
    ``Precision.HIGHEST``, or a lower dtype at the default precision (the
    control, which the comparison must fail).  Subclasses give
    ``hidden(w, tokens)`` (B, S, d) and ``logits(w, hidden)`` (B, S, V),
    computed in ``self.dtype`` at ``self.prec``."""

    def __init__(self, c: dict, dtype=jnp.float32):
        self.c = c
        self.dtype = jnp.dtype(dtype)
        self.prec = (jax.lax.Precision.HIGHEST if self.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        self._logprobs = jax.jit(self._logprobs_fn)
        self.jit_logits = jax.jit(lambda w, x: self.logits(w, self.hidden(w, x)))

    def cast(self, w):
        return {k: v.astype(self.dtype) for k, v in w.items()}

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

    def __init__(self, ref: Policy, hp: dict, pad_to: int, rows: int):
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
