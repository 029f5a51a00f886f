"""Model operations per second of the window over the chips and the
bf16 peak.  Counted from shapes (``flops.py``): 6 N per trained token
(forward and backward; the proximal forward recomputes log-probabilities
and is left out), 2 N per generated token and per prompt token
prefilled (re-prefill after an interrupt is recompute and left out),
and attention at the trajectories' own context lengths."""
import flops
from peaks import peaks


def read(ctx):
    c, w = ctx["c"], ctx["counters"]
    lengths = [p + r for p, r in w["lengths"]]
    resp = sum(r for _, r in w["lengths"])
    # mean context of a generated token, from the trajectories trained on
    pairs_per_tok = (sum(sum(range(p + 1, p + r + 1)) for p, r in w["lengths"]) / resp
                     if resp else 0.0)
    gen = w["tokens_generated"]
    prompt = w["prefill_tokens"]        # fresh prompt tokens only
    rollout = (flops.decode_flops(c, gen, int(gen * pairs_per_tok))
               + 2.0 * flops.matmul_params(c) * prompt)
    # trained tokens count over the whole steps of the window, the engine's
    # counters over the loop's run to its stop
    rate = flops.train_flops(c, lengths) / w["window_s"] + rollout / w["loop_s"]
    return 100.0 * rate / (ctx["chips"] * peaks(ctx["device_kind"])["bf16_flops"])
