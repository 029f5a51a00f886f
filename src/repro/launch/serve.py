"""Serving launcher: a thin CLI over the production gateway
(repro/serve/, DESIGN.md §Serving gateway).

Two modes share one engine + gateway construction path:

  * ``--port N`` — serve HTTP: streaming ``POST /v1/completions`` plus
    ``/stats`` and ``/healthz`` (serve/http.py).  Handler threads only
    enqueue; a single driver thread owns the engine.
  * offline (default) — submit ``--requests`` synthetic requests drawn
    from ``--env`` through the gateway (optionally spread over
    ``--sessions`` logical sessions so consecutive requests in a
    session prefix-share KV blocks), pump to completion, verify, and
    print a JSON summary.

    PYTHONPATH=src python -m repro.launch.serve --cache paged --prefill-chunk 16
    PYTHONPATH=src python -m repro.launch.serve --scale pod --cache paged \
        --prefill-chunk 64 --fused-decode fused --prompt-len 64 --max-gen 64
    PYTHONPATH=src python -m repro.launch.serve --cache paged --evict lru \
        --port 8000 --sla-ms 2000
"""
from __future__ import annotations

import argparse
import json
import time
from types import SimpleNamespace

import jax

from repro import checkpoint
from repro.core import RolloutEngine
from repro.env import make_env
from repro.launch import cli
from repro.models.model import build_model
from repro.obs import trace
from repro.serve import Gateway, GatewayServer


def build_gateway(args, **engine_overrides):
    """Model + engine + gateway from parsed flags (shared by both
    modes and by the gateway-smoke CI job).  ``engine_overrides`` win
    over the flags (``cli.engine_config_from_args``) — a caller asking
    for greedy decoding passes ``temperature=0.0``."""
    cfg = cli.model_config(args.arch, args.scale)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(args.seed))
    if args.ckpt:
        params, _, meta = checkpoint.load(args.ckpt, params)
        print(f"loaded checkpoint {args.ckpt} (version {meta.get('version')})")
    env = make_env(args.env, seed=args.seed)
    continuation = env.continuation_hook()
    overrides = {}
    if continuation is not None:
        overrides["continuation"] = continuation
        if args.prefill_chunk <= 0:        # turns need the span queue
            overrides["prefill_chunk"] = args.prompt_len
    if args.prefill_chunk <= 0 and "prefill_chunk" not in overrides:
        # the gateway resumes preempted requests through the chunked
        # ingest queue; default to one-span-per-step prompt ingestion
        overrides["prefill_chunk"] = args.prompt_len
    ec = cli.engine_config_from_args(args, **{**overrides, **engine_overrides})
    engine = RolloutEngine(model, params, cfg=ec)
    return Gateway(engine), env


def run_offline(gw: Gateway, env, args, results=None) -> dict:
    """Submit ``args.requests`` requests drawn from ``env``, pump the
    gateway until idle, verify every answer, and return the summary.
    ``results``, when given, receives each request's prompt and its
    end record (tokens, logprobs, ...) keyed by request id."""
    answers = {}
    prompts = {}
    t0 = time.time()
    for i in range(args.requests):
        p = env.sample()
        sid = f"s{i % args.sessions}" if args.sessions else None
        rid = gw.submit(p.prompt_tokens, session=sid,
                        sla=args.sla_ms or None, answer=p.answer)
        answers[rid] = p.answer
        prompts[rid] = list(p.prompt_tokens)
    ticks = gw.run_until_idle()
    dt = time.time() - t0
    n_ok = 0
    toks = 0
    for rid, ans in answers.items():
        d = gw.drain(rid)
        assert d["end"] is not None, f"request {rid} never finished"
        if results is not None:
            results[rid] = {"prompt": prompts[rid], **d["end"]}
        toks += len(d["tokens"])
        fin = SimpleNamespace(response=d["tokens"], answer=ans,
                              prompt=[], rid=rid)
        n_ok += int(env.verify(fin).ok)
    st = gw.stats()
    out = {
        "requests": len(answers), "ticks": ticks,
        "generated_tokens": toks, "tokens_per_s": round(toks / dt, 1),
        "mean_len": round(toks / max(1, len(answers)), 2),
        "env": args.env, "verified_ok": n_ok, "verified": len(answers),
        "sessions": args.sessions,
    }
    for k in ("sla_misses", "preemptions", "resumes", "evictions",
              "revivals", "deferred", "prefix_hit_rate", "session_hits",
              "ttft_p50", "ttft_p99"):
        out[k] = st[k]
    eng = gw.engine
    if eng.continuations:
        out["continuations"] = eng.continuations
        out["continuation_tokens"] = eng.continuation_tokens
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    cli.add_model_flags(ap)
    ap.add_argument("--requests", type=int, default=32,
                    help="offline mode: synthetic requests to serve")
    ap.add_argument("--ckpt", default="", help="load weights from checkpoint")
    cli.add_engine_flags(ap)
    cli.add_env_flags(ap, default="math", allow_legacy=False)
    cli.add_gateway_flags(ap)
    cli.add_obs_flags(ap)
    return ap


def main():
    args = build_parser().parse_args()
    cli.enable_compile_cache()
    cli.obs_setup(args, actor="serve")

    gw, env = build_gateway(args)
    if args.port:
        srv = GatewayServer(gw, host=args.host, port=args.port,
                            default_sla_ms=args.sla_ms)
        print(json.dumps({"serving": f"http://{args.host}:{srv.port}",
                          "arch": args.arch,
                          "evict": gw.engine.engine_config.evict}),
              flush=True)
        try:
            srv.serve_forever()
        finally:
            cli.obs_finish(args, stats={"gateway": gw.stats()},
                           registry=gw.metrics_registry())
    else:
        if trace.get().enabled:
            # offline mode runs on the gateway's deterministic tick
            # clock — trace in that time base (DESIGN.md §Clock domains)
            trace.get().set_clock(gw.now)
        out = run_offline(gw, env, args)
        out.update(cli.obs_finish(args, stats={"gateway": gw.stats()},
                                  registry=gw.metrics_registry()))
        print(json.dumps(out))


if __name__ == "__main__":
    main()
