"""Trainer worker (Section 4.1): consumes a global batch of trajectories,
computes advantages, packs them into dynamic micro-batches (Algorithm 1),
recomputes proximal-policy logprobs (Section 5.2 practical remark: the
parameters right before this update step), then runs ``ppo_minibatches``
sequential PPO updates with the decoupled objective.

All device computation is jit'd with static shapes: each micro-batch is
one packed row-block of ``(rows, pack_len)`` tokens with segment ids
(batching.py), so any mix of sequence lengths reuses the same signature.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs.base import RLConfig
from repro.core import advantages as adv_mod
from repro.core import batching, ppo
from repro.core.buffer import Trajectory

# The attention the trainer traces its forwards with.  The Pallas flash
# kernel has no reverse-mode rule (``jax.grad`` through ``pallas_call``
# fails at trace time), so the differentiated forward runs the jnp
# oracle; the proximal-logprob recompute uses the same one so that the
# importance ratio starts at exactly 1.  Rollout engines keep the
# platform's kernels (DESIGN.md §Kernel backend).
TRAIN_ATTENTION = "jnp"


@dataclass
class TrainMetrics:
    version: int
    loss: float
    reward_mean: float
    seq_len_mean: float
    staleness_mean: float
    staleness_max: int
    n_tokens: int
    n_microbatches: int
    diag: Dict[str, float] = field(default_factory=dict)


class PPOTrainer:
    def __init__(self, model, rl: RLConfig, params, *, pack_rows: int = 1,
                 adam: Optional[optim.AdamConfig] = None):
        self.model = model
        self.rl = rl
        self.params = params
        self.adam = adam or optim.AdamConfig(
            lr=rl.lr, beta1=rl.beta1, beta2=rl.beta2, eps=rl.adam_eps,
            weight_decay=rl.weight_decay, grad_clip=rl.grad_clip,
            warmup_steps=max(1, int(rl.warmup_proportion * rl.total_steps)))
        self.opt_state = optim.init_state(params)
        self.version = 0
        self.pack_rows = pack_rows
        self.pack_len = rl.microbatch_token_budget

        self._jit_logprobs = jax.jit(self._logprob_fn)
        self._jit_grad = jax.jit(jax.value_and_grad(self._loss_fn, has_aux=True))
        # The gradient sum and the optimizer state are the trainer's own,
        # so the accumulation and the update reuse their buffers in place;
        # the params are not donated, since the published versions and the
        # engine may still read them.  At 420 M f32 params (areal-qwen-1.5b
        # widths, 4 layers) this keeps ~5 GB of copies off one 16 GB chip.
        self._jit_accumulate = jax.jit(self._accumulate_fn, donate_argnums=0)
        self._jit_apply = jax.jit(self._apply_fn, donate_argnums=(1, 2))

    # ---- jit bodies -------------------------------------------------------
    @staticmethod
    def _accumulate_fn(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    def _apply_fn(self, params, grad_sum, opt_state, n):
        return optim.apply_updates(
            self.adam, params, jax.tree.map(lambda x: x / n, grad_sum),
            opt_state)

    def _forward_logprobs(self, params, batch):
        # imported here, not at the top: the kernels load Pallas, which
        # processes that import repro.core without tracing a model (fleet
        # workers with simulated engines and trainers) need not pay for
        from repro.kernels import ops

        seg = batch["segment_ids"]
        with ops.use_backend(TRAIN_ATTENTION):
            hidden, aux = self.model.hidden_states(
                params, batch["tokens"], positions=batch["positions"],
                segment_ids=seg)
        logits = self.model.logits(params, hidden)
        lp = ppo.next_token_logprobs(logits, batch["tokens"])
        # token t's predictor (t-1) must be in the same segment
        same_seg = jnp.concatenate(
            [jnp.zeros_like(seg[:, :1], bool), seg[:, 1:] == seg[:, :-1]], axis=1)
        lp = jnp.where(same_seg & (seg >= 0), lp, 0.0)
        return lp, aux

    def _logprob_fn(self, params, batch):
        return self._forward_logprobs(params, batch)[0]

    def _loss_fn(self, params, batch):
        lp, aux = self._forward_logprobs(params, batch)
        loss, diag = ppo.ppo_loss(
            lp, batch["behav_logprob"], batch["prox_logprob"],
            batch["advantages"], batch["loss_mask"],
            clip_eps=self.rl.clip_eps, decoupled=self.rl.decoupled_objective)
        if self.model.cfg.is_moe:
            loss = loss + (self.model.cfg.router_aux_coef * aux["lb"]
                           + self.model.cfg.router_z_coef * aux["z"])
        return loss, diag

    # ---- batch preparation -----------------------------------------------
    def _prepare(self, batch: List[Trajectory]):
        rewards = np.array([t.reward for t in batch], np.float32)
        groups = np.array([t.prompt_id for t in batch])
        adv = adv_mod.group_advantages(rewards, groups, self.rl.adv_estimator)
        if self.rl.advantage_norm:
            adv = adv_mod.normalize_global(adv)
        seqs = []
        for t, a in zip(batch, adv):
            toks = list(t.prompt_tokens) + list(t.response_tokens)
            np_ = len(t.prompt_tokens)
            # multi-turn episodes carry a per-response-token mask
            # (DESIGN.md §Environments and reward service): tokens the
            # ENVIRONMENT injected were never sampled by the policy and
            # take no loss, exactly like prompt tokens
            resp_mask = t.meta.get("loss_mask") if t.meta else None
            if resp_mask is None:
                resp_mask = [1.0] * len(t.response_tokens)
            lm = [0.0] * np_ + [float(x) for x in resp_mask]
            blp = [0.0] * np_ + list(t.behav_logprobs)
            seqs.append({"tokens": toks[: self.pack_len],
                         "loss_mask": lm[: self.pack_len],
                         "behav_logprob": blp[: self.pack_len],
                         "advantage": float(a)})
        return seqs

    def _pack_microbatches(self, seqs) -> List[Dict[str, jnp.ndarray]]:
        lens = [len(s["tokens"]) for s in seqs]
        cap = self.pack_rows * self.pack_len
        if self.rl.dynamic_batching:
            groups = batching.dynamic_batching(lens, cap, self.rl.min_microbatches)
        else:
            n_static = max(self.rl.min_microbatches,
                           int(np.ceil(sum(lens) / cap)) * 2)
            groups = batching.static_batching(lens, n_static)
        # micro-batches go straight to the devices holding the params
        # (the trainer submesh when disaggregated), not the default device
        place = getattr(jax.tree.leaves(self.params)[0], "sharding", None)
        mbs = []
        for g in groups:
            pb = batching.pack_sequences([seqs[i] for i in g], self.pack_len,
                                         rows=self.pack_rows)
            mbs.append(jax.device_put({
                "tokens": pb.tokens,
                "positions": pb.positions,
                "segment_ids": pb.segment_ids,
                "loss_mask": pb.loss_mask,
                "advantages": pb.advantages,
                "behav_logprob": pb.behav_logprob,
            }, place))
        return mbs

    # ---- the train step ----------------------------------------------------
    def train_step(self, batch: List[Trajectory],
                   current_version: Optional[int] = None) -> TrainMetrics:
        rl = self.rl
        seqs = self._prepare(batch)
        mbs = self._pack_microbatches(seqs)

        # proximal logprobs: recomputed ONCE on batch arrival with the
        # parameters before this update step (Sec 5.2, practical remark)
        for mb in mbs:
            mb["prox_logprob"] = self._jit_logprobs(self.params, mb)
            if not rl.decoupled_objective:
                # naive PPO (Eq. 2): the trust region centers on the behavior
                # policy; prox is unused but kept equal for diagnostics
                mb["prox_logprob"] = mb["behav_logprob"]

        # minibatch splits (sequential updates, Sec 3.1 footnote 2)
        n_mb = len(mbs)
        n_mini = min(rl.ppo_minibatches, n_mb)
        splits = np.array_split(np.arange(n_mb), n_mini)
        total_loss, diag_acc, n_applied = 0.0, {}, 0
        for idx in splits:
            grads = None
            loss_acc = 0.0
            for i in idx:
                (loss, diag), g = self._jit_grad(self.params, mbs[i])
                loss_acc += float(loss)
                grads = g if grads is None else self._jit_accumulate(grads, g)
                for k, v in diag.items():
                    diag_acc[k] = diag_acc.get(k, 0.0) + float(v)
            # the mean over the split is taken inside the update, in place
            self.params, self.opt_state, om = self._jit_apply(
                self.params, grads, self.opt_state, float(len(idx)))
            diag_acc["grad_norm"] = (diag_acc.get("grad_norm", 0.0)
                                     + float(om["grad_norm"]) * len(idx))
            total_loss += loss_acc / len(idx)
            n_applied += len(idx)

        self.version += 1
        cur = self.version if current_version is None else current_version
        stal = [max(0, (cur - 1) - t.behavior_version) for t in batch]
        return TrainMetrics(
            version=self.version,
            loss=total_loss / max(n_mini, 1),
            reward_mean=float(np.mean([t.reward for t in batch])),
            seq_len_mean=float(np.mean([t.length for t in batch])),
            staleness_mean=float(np.mean(stal)),
            staleness_max=int(np.max(stal)),
            n_tokens=int(sum(t.length for t in batch)),
            n_microbatches=len(mbs),
            diag={k: v / max(n_applied, 1) for k, v in diag_acc.items()},
        )
