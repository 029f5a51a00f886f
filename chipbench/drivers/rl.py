"""Driver of traffic ``"kind": "rl"``: the AReaL loop on one chip.

The system under test is ``ThreadedRuntime``: a rollout thread decoding
on the paged engine with interruptible weight updates, and a trainer
thread running the decoupled-PPO step and publishing every version.
Set-up builds one runtime from the seed and drives it through its first
``setup_steps`` trainer steps with ``ThreadedRuntime.run``, the call the
window makes too, then trains on whatever full batches the rollout
buffered while those steps compiled, so that the window starts from the
loop's steady state.  The window runs the loop for ``--seconds`` and
counts the whole trainer steps that end in it.

Every step's batch and loss are recorded.  Once the window has closed,
the plain reference follows every step from the first to the window's
last whole one, on the recorded batches, and the comparison covers the
behaviour log-probability of every token trained on, each step's loss,
Adam's first moment after step 1 and the parameters' change after
``setup_steps``.

Prompts are the math task's ("<q> a op b = ?"), drawn from the seed.
Random weights never solve it, so every group would score alike and
GRPO's advantages, loss and gradient would all be zero.  The verifier
therefore scores by a fixed rule on the sampled tokens (``reward_rule``),
which mixes every group; lengths and shapes are those of the math task.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

import arch
import system
import weights
from reference.grpo import GRPO, adam_state
from repro.configs.base import RLConfig
from repro.core import (AsyncScheduler, PPOTrainer, ParameterStore,
                        RolloutEngine, ThreadedRuntime)
from repro.core.config import EngineConfig
from repro.data import tasks
from repro.env.base import EnvPromptStream, Environment, Verdict

REF_ROWS = 4          # sequences per reference call: logits + grads fit


def reward_ok(rule: str, response) -> bool:
    if rule == "first_token_even":
        return len(response) > 0 and response[0] % 2 == 0
    raise ValueError(f"unknown reward rule {rule!r}")


class MathPrompts(Environment):
    """The math task's prompts from the seed, scored by the traffic's rule."""
    name = "bench-math"

    def __init__(self, seed: int, max_operand: int, rule: str):
        self.rng = np.random.default_rng(seed)
        self.max_operand, self.rule, self.pid = max_operand, rule, 0

    def sample(self):
        a = int(self.rng.integers(0, self.max_operand))
        b = int(self.rng.integers(1, self.max_operand))
        op = "+-*"[int(self.rng.integers(3))]
        val = {"+": a + b, "-": a - b, "*": a * b}[op]
        self.pid += 1
        return tasks.Problem(self.pid - 1, f"<q> {a} {op} {b} = ?", str(val))

    def verify(self, fin):
        return Verdict(reward_ok(self.rule, fin.response))


class Record:
    """What the benchmark keeps of the run: every step's end time, sizes,
    batch and loss; the first moment's norms after step 1, and the
    parameters after step ``n``.  Host data only."""

    def __init__(self, n: int):
        self.n = n
        self.steps = []       # (end perf_counter, n_tokens, [(prompt, resp)])
        self.batches, self.losses = [], []
        self.m_norms = None
        self.params_host = None


def _traj(t):
    return {"prompt": list(t.prompt_tokens), "response": list(t.response_tokens),
            "behav": list(t.behav_logprobs), "versions": list(t.versions),
            "prompt_id": t.prompt_id}


class RL:
    def __init__(self, c: dict, t: dict, seed: int, fault: str = ""):
        self.c, self.t, self.seed = c, t, seed
        model = system.build(c)
        w = weights.make(c, seed)
        self.w0_host = jax.device_get(w)
        params = system.program_params(c, model, w)
        del w
        rl = RLConfig(batch_size=t["batch_size"],
                      answers_per_prompt=t["answers_per_prompt"],
                      max_staleness=t["eta"], decoupled_objective=True,
                      interruptible=True, lr=t["lr"],
                      microbatch_token_budget=t["prompt_len"] + t["max_gen_len"],
                      ppo_minibatches=t["ppo_minibatches"],
                      total_steps=t["total_steps"], adv_estimator="grpo",
                      max_prompt_len=t["prompt_len"],
                      max_gen_len=t["max_gen_len"])
        self.engine = RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=t["n_slots"], prompt_len=t["prompt_len"],
            max_gen_len=t["max_gen_len"], temperature=t["temperature"],
            seed=seed % (2 ** 31 - 1), cache="paged",
            prefill_chunk=t["prefill_chunk"]))
        self.trainer = PPOTrainer(model, rl, params)
        del params
        env = MathPrompts(seed, t["max_operand"], t["reward_rule"])
        self.sched = AsyncScheduler(
            prompt_stream=EnvPromptStream(env, t["answers_per_prompt"]),
            rl=rl, env=env)
        self.ctl = ThreadedRuntime(engine=self.engine, trainer=self.trainer,
                                   scheduler=self.sched, store=ParameterStore())
        self.rec = Record(t["setup_steps"])
        self._instrument(fault)

    # ---- instrumentation: annotations and the record -----------------------
    def _instrument(self, fault: str):
        eng, tr, rec, c = self.engine, self.trainer, self.rec, self.c
        for name in ("step", "admit", "update_weights"):
            fn = getattr(eng, name)
            setattr(eng, name, _annotated(f"rollout.{name}", fn))
        train = tr.train_step
        if fault == "half_batch":
            def train(batch, current_version=None, _f=tr.train_step):
                return _f(batch[:len(batch) // 2], current_version)
        if fault == "unchanged_state":
            tr._jit_apply = lambda p, g, s, n: (p, s, {"grad_norm": 0.0})
        if fault == "alter_token":
            step = eng.step

            def altered(_f=step):
                fin = _f()
                for f in fin:
                    j = len(f.response) // 2
                    f.response[j] = (f.response[j] + 1) % c["vocab_size"]
                return fin
            eng.step = altered

        def recorded(batch, current_version=None):
            with TraceAnnotation("trainer.train_step"):
                m = train(batch, current_version)
            rec.steps.append((time.perf_counter(), m.n_tokens,
                              [(len(b.prompt_tokens), len(b.response_tokens))
                               for b in batch]))
            rec.batches.append([_traj(b) for b in batch])
            rec.losses.append(m.loss)
            if m.version == 1:
                rec.m_norms = system.leaf_norms(c, tr.opt_state["m"])
            if m.version == rec.n:
                rec.params_host = system.leaves(c, jax.device_get(tr.params))
            return m
        tr.train_step = recorded

    # ---- phases ------------------------------------------------------------
    def setup(self):
        t = self.t
        self.ctl.run(self.rec.n, timeout=t["setup_timeout_s"])
        # while the first steps compiled, the rollout ran ahead, up to eta
        # versions: train on those batches too, so that no batch made in
        # set-up is counted in the window
        for _ in range(t["eta"] + 1):
            if len(self.sched.buffer) < t["batch_size"]:
                break
            self.ctl.run(1, timeout=t["setup_timeout_s"])

    def counters(self):
        e, ctl = self.engine, self.ctl
        return {"trainer_busy_s": ctl.trainer_busy_s,
                "tokens_generated": e.tokens_generated,
                "prefill_tokens": e.prefill_tokens,
                "reprefill_tokens": e.reprefill_tokens,
                "interruptions": e.interruptions, "version": self.trainer.version,
                "pickups": len(self.sched.pickup_latencies)}

    def window(self, seconds: float) -> dict:
        """The loop runs for ``seconds``; the window is the whole trainer
        steps that ended in that time, from its start to the end of the
        last of them.  Returns the window's counters."""
        before, k = self.counters(), len(self.rec.steps)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            try:
                self.ctl.run(1 << 30, timeout=seconds)
            except TimeoutError:
                pass                     # the runtime has stopped both threads
        loop_s = time.perf_counter() - t0
        after = self.counters()
        steps = [s for s in self.rec.steps[k:] if s[0] <= t0 + seconds]
        if not steps:
            raise RuntimeError(f"no trainer step ended within {seconds} s")
        d = {key: after[key] - before[key] for key in before}
        # program counters run to the loop's stop (``loop_s``); the trained
        # tokens are those of the whole steps in ``window_s``
        d.update(window_s=steps[-1][0] - t0, loop_s=loop_s, steps=len(steps),
                 last_step=k + len(steps),
                 trained_tokens=sum(s[1] for s in steps),
                 trajectories=sum(len(s[2]) for s in steps),
                 lengths=[x for s in steps for x in s[2]],
                 pickup_ms=[1e3 * lat for v, _, lat in self.sched.pickup_latencies
                            if v > before["version"]])
        return d

    def free(self):
        """Drop every reference to the program's device state."""
        for name in ("ctl", "engine", "trainer", "sched"):
            setattr(self, name, None)
        gc.collect()


def _annotated(name, fn):
    def call(*a, **k):
        with TraceAnnotation(name):
            return fn(*a, **k)
    return call


# ---- comparison with the reference -----------------------------------------

def reference_readings(c: dict, t: dict, seed: int, rec: Record,
                       n_steps: int, dtype):
    """The reference's own run over the program's first ``n_steps``
    batches: each token's log-probability under the version that sampled
    it, each step's loss, the first moment after step 1 and the change
    after step ``rec.n``."""
    ref = arch.reference(c).Ref(c, dtype)
    pad = t["prompt_len"] + t["max_gen_len"]
    hp = dict(t["optimizer"], lr=t["lr"], capacity=pad,
              minibatches=t["ppo_minibatches"])
    grpo = GRPO(ref, hp, pad, REF_ROWS)
    w = ref.cast(weights.make(c, seed))
    w0 = jax.device_get(w)
    state = adam_state(w)
    batches = rec.batches[:n_steps]
    seqs = [[b["prompt"] + b["response"] for b in batch] for batch in batches]
    # batch s trains under version s; its tokens were sampled by versions <= s
    used = [{v for tr in batch for v in tr["versions"]} | {s}
            for s, batch in enumerate(batches)]
    lp_at = {}                       # (version, batch, i) -> per-token logprobs
    losses, m_norms, change = [], None, None
    for s in range(n_steps):
        need = [(b, i) for b in range(s, n_steps) if s in used[b]
                for i in range(len(seqs[b]))]
        lps = ref.logprobs(w, [seqs[b][i] for b, i in need], pad, 2 * REF_ROWS)
        for (b, i), lp in zip(need, lps):
            lp_at[s, b, i] = lp
        batch = batches[s]
        masks, prox, behav = [], [], []
        for i, tr in enumerate(batch):
            n_p = len(tr["prompt"])
            masks.append([0.0] * n_p + [1.0] * len(tr["response"]))
            prox.append(lp_at[s, s, i])
            bh = np.zeros(len(seqs[s][i]))
            for j, v in enumerate(tr["versions"]):
                bh[n_p + j] = lp_at[v, s, i][n_p + j]
            behav.append(bh)
        rewards = [5.0 if reward_ok(t["reward_rule"], tr["response"]) else -5.0
                   for tr in batch]
        w, state, loss = grpo.step(w, state, seqs[s], masks, rewards,
                                   [tr["prompt_id"] for tr in batch], prox, behav)
        losses.append(loss)
        if s == 0:
            m_norms = {k: float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
                       for k, x in state["m"].items()}
        if s == rec.n - 1:
            w_n = jax.device_get(w)
            change = {k: _norm_diff(w_n[k], w0[k]) for k in w0}
            del w_n
    del state, w, w0
    logprobs = {}
    for s, batch in enumerate(batches):
        for i, tr in enumerate(batch):
            n_p = len(tr["prompt"])
            logprobs[s, i] = np.array([lp_at[v, s, i][n_p + j]
                                       for j, v in enumerate(tr["versions"])])
    return {"losses": losses, "m_norms": m_norms, "change": change,
            "logprobs": logprobs}


def program_readings(rec: Record, w0_host: dict, n_steps: int) -> dict:
    change = {k: _norm_diff(rec.params_host[k], w0_host[k]) for k in w0_host}
    logprobs = {(s, i): np.asarray(tr["behav"], np.float64)
                for s, batch in enumerate(rec.batches[:n_steps])
                for i, tr in enumerate(batch)}
    return {"losses": list(rec.losses[:n_steps]), "m_norms": dict(rec.m_norms),
            "change": change, "logprobs": logprobs}


def _norm_diff(a, b):
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
    tot = 0.0
    for lo in range(0, a.size, 1 << 24):
        d = a[lo:lo + (1 << 24)].astype(np.float64) - b[lo:lo + (1 << 24)]
        tot += float(np.dot(d, d))
    return math.sqrt(tot)


def gaps(got: dict, ref: dict) -> dict:
    """The numbers compared.  Norms are compared leaf by leaf, by the worst
    leaf, as a share of the larger of that leaf's and the median leaf's
    reference norm.  Leaves whose reference first moment is under a
    thousandth of the median leaf's are left out: they move by rounding
    alone."""
    med = float(np.median(list(ref["m_norms"].values())))
    keep = [k for k, v in ref["m_norms"].items() if v >= 1e-3 * med]

    def worst(a, b):
        floor = float(np.median([b[k] for k in keep]))
        return max(abs(a[k] - b[k]) / max(b[k], floor) for k in keep)

    return {
        "loss_gap": max(abs(a - b) for a, b in zip(got["losses"], ref["losses"])),
        "moment_gap": worst(got["m_norms"], ref["m_norms"]),
        "change_gap": worst(got["change"], ref["change"]),
        "logprob_gap": max(float(np.max(np.abs(got["logprobs"][k] - ref["logprobs"][k]),
                                        initial=0.0))
                           for k in ref["logprobs"]),
        "leaves_left_out": sorted(set(ref["m_norms"]) - set(keep)),
    }


# ---- one run of the cell ----------------------------------------------------

def run_cell(c, t, seed, seconds, trace, *, limits, t_start, compiles,
             trace_dir, fault=""):
    """Set-up, window, reference comparison.  Returns the run's result for
    ``run.py``: the readers' context, correctness and device memory."""
    import shutil

    import tracereduce
    import tracing

    cell = RL(c, t, seed, fault=fault)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    n_setup = len(cell.rec.steps)
    n_compiles = compiles.n
    jax.config.update("jax_log_compiles", True)    # names what compiles late
    traced = tracing.Traced(trace_dir, t["trace_seconds"]) if trace else None
    if traced:
        traced.start()
    win = cell.window(seconds)
    red = None
    if traced:
        traced.stop()
    window_compiles = compiles.n - n_compiles
    jax.config.update("jax_log_compiles", False)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    rec, w0_host = cell.rec, cell.w0_host
    cell.free()
    if trace:
        red = tracereduce.reduce(tracereduce.load(trace_dir), window=tracing.WINDOW)
        shutil.rmtree(trace_dir, ignore_errors=True)
    n_steps = win["last_step"]        # set-up's steps and the window's
    got = program_readings(rec, w0_host, n_steps)
    del w0_host
    t_ref = time.perf_counter()
    ref = reference_readings(c, t, seed, rec, n_steps, np.float32)
    g = gaps(got, ref)
    checks = {k: {"value": g[k], "limit": limits[k]} for k in limits}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    ctx = {"c": c, "t": t, "chips": 1, "counters": win, "trace": red,
           "device_kind": jax.devices()[0].device_kind,
           "window_compiles": window_compiles,
           "setup_steps": n_setup, "window_steps": win["steps"],
           "compared_steps": n_steps,
           "reference_s": time.perf_counter() - t_ref,
           "end_to_end": {"setup_s": setup_s,
                          "trained_tokens_per_s": win["trained_tokens"] / win["window_s"]}}
    return {"ctx": ctx, "correct": correct, "checks": checks,
            "attempted": win["trajectories"], "failed": 0,
            "memory_peak_bytes": peak, "gaps": g}
