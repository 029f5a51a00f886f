# The multi-pod dry-run needs 512 placeholder devices; jax locks the device
# count at first init, so this MUST precede every other import.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers AND compiles on the production mesh, and extract the
memory / FLOP / collective figures that feed EXPERIMENTS.md §Dry-run and
§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b \
      --shape train_4k [--multi-pod] [--out runs/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

For train shapes this lowers the full PPO ``train_step`` (decoupled-PPO
loss + AdamW); prefill shapes lower ``prefill_step``; decode shapes lower
``serve_step`` (ONE token against a seq_len KV cache / recurrent state).
All inputs are ShapeDtypeStructs — nothing is allocated.
"""
import argparse
import functools
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs import get_model_config, get_shape, ASSIGNED_ARCHS, SHAPES
from repro.configs.base import RLConfig
from repro.dist import sharding
from repro.launch import cli, hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_mod
from repro.models import model as model_mod
from repro.obs import trace

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}
_COLL_RE = re.compile(
    r"=\s+((?:\w+\[[^\]]*\](?:\{[^}]*\})?,?\s*|\()+\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op, by type."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(shapes):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
        out[kind + "_count"] = out.get(kind + "_count", 0) + 1
    return out


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D for training (N = active params), 2*N*D for
    prefill, 2*N per token for decode."""
    n_active = cfg.param_count()
    if cfg.is_moe:
        # active = non-expert params + top-k/E of expert params
        dense_mlp = 3 * cfg.d_model * cfg.d_ff if cfg.act in ("swiglu", "geglu") \
            else 2 * cfg.d_model * cfg.d_ff
        expert_total = cfg.n_layers * cfg.n_experts * dense_mlp
        n_active = n_active - expert_total + cfg.n_layers * cfg.experts_per_token * dense_mlp
    d_tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * d_tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * d_tokens
    return 2.0 * n_active * shape.global_batch          # decode: one token


def build_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
                 fsdp: bool = True, fsdp_pods: bool = False,
                 vocab_parallel: bool = False,
                 remat_policy: str = "none", accum_steps: int = 8,
                 paged_cache: bool = False, block_size: int = 16,
                 prefill_chunk: int = 0, fused_decode: bool = False,
                 extra: str = ""):
    cfg = get_model_config(arch)
    shape = get_shape(shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape.kind, "fsdp": fsdp, "vocab_parallel": vocab_parallel,
           "remat_policy": remat_policy, "accum_steps": accum_steps,
           "paged_cache": paged_cache,
           "prefill_chunk": prefill_chunk,
           "fused_decode": fused_decode,
           "extra": extra}

    if paged_cache and (shape.kind != "decode" or cfg.is_encdec):
        rec["status"] = "skipped"
        rec["reason"] = ("--paged-cache applies to decoder-only decode "
                        "shapes (DESIGN.md §Arch-applicability)")
        return rec

    if fused_decode and not paged_cache:
        rec["status"] = "skipped"
        rec["reason"] = ("--fused-decode lowers the paged fast-path step: "
                         "combine with --paged-cache on a decode shape "
                         "(DESIGN.md §Fused decode tail)")
        return rec

    if shape.kind == "decode" and shape.seq_len >= 500_000 \
            and not cfg.supports_long_decode:
        rec["status"] = "skipped"
        rec["reason"] = ("pure full attention: long_500k requires "
                         "sub-quadratic decode state (DESIGN.md)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = {"none": None,
              "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
              }[remat_policy]
    model = model_mod.build_model(cfg, remat=True, remat_policy=policy)
    dtype = jnp.bfloat16

    params_shape = jax.eval_shape(functools.partial(model.init, dtype=dtype),
                                  jax.random.key(0))
    pspecs = sharding.make_param_specs(cfg, mesh, params_shape, fsdp=fsdp,
                                       fsdp_pods=fsdp_pods)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            rl = RLConfig()
            step = steps_mod.make_train_step(model, rl,
                                             vocab_parallel_loss=vocab_parallel,
                                             accum_steps=accum_steps)
            batch_shape = model_mod.train_batch_specs(cfg, shape, dtype)
            bspecs = sharding.make_train_batch_specs(mesh, batch_shape)
            opt_shape = jax.eval_shape(optim.init_state, params_shape)
            ospecs = sharding.make_opt_specs(pspecs)
            jitted = jax.jit(
                step,
                in_shardings=(sharding.named(mesh, pspecs),
                              sharding.named(mesh, ospecs),
                              sharding.named(mesh, bspecs)),
                out_shardings=(sharding.named(mesh, pspecs),
                               sharding.named(mesh, ospecs), None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_shape, opt_shape, batch_shape)
        elif shape.kind == "prefill":
            # prefix models (VLM) prepend n_prefix_tokens to the prompt
            max_len = shape.seq_len + (cfg.n_prefix_tokens
                                       if not cfg.is_encdec else 0)
            step = steps_mod.make_prefill_step(model, max_len, dtype)
            batch_shape = model_mod.prefill_batch_specs(cfg, shape, dtype)
            bspecs = sharding.make_train_batch_specs(mesh, batch_shape)
            cache_shape = model_mod.cache_specs(model, cfg, shape.global_batch,
                                                max_len, dtype)
            cspecs = sharding.make_cache_specs(cfg, mesh, cache_shape)
            logit_spec = jax.sharding.PartitionSpec(
                sharding.batch_spec(mesh, shape.global_batch), "model")
            jitted = jax.jit(
                step,
                in_shardings=(sharding.named(mesh, pspecs),
                              sharding.named(mesh, bspecs)),
                out_shardings=(jax.NamedSharding(mesh, logit_spec),
                               sharding.named(mesh, cspecs)))
            lowered = jitted.lower(params_shape, batch_shape)
        elif shape.kind == "decode" and paged_cache:
            # paged pool sized for equal worst-case capacity: every slot
            # can hold seq_len tokens (prefix sharing only shrinks usage)
            step = (steps_mod.make_fused_serve_step(model) if fused_decode
                    else steps_mod.make_paged_serve_step(model))
            n_blocks = shape.global_batch * (-(-shape.seq_len // block_size))
            cache_shape, tables_shape = model_mod.paged_cache_specs(
                model, cfg, shape.global_batch, shape.seq_len, block_size,
                n_blocks, dtype)
            cspecs = sharding.make_cache_specs(cfg, mesh, cache_shape)
            bspec = sharding.batch_spec(mesh, shape.global_batch)
            tok_shape = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            tok_spec = jax.sharding.PartitionSpec(bspec)
            tables_spec = jax.sharding.PartitionSpec(bspec, None)
            logit_spec = jax.sharding.PartitionSpec(bspec, "model")
            jitted = jax.jit(
                step,
                in_shardings=(sharding.named(mesh, pspecs),
                              jax.NamedSharding(mesh, tok_spec),
                              sharding.named(mesh, cspecs),
                              jax.NamedSharding(mesh, tables_spec)),
                out_shardings=(jax.NamedSharding(mesh, logit_spec),
                               sharding.named(mesh, cspecs)),
                donate_argnums=(2,))
            lowered = jitted.lower(params_shape, tok_shape, cache_shape,
                                   tables_shape)
        else:  # decode
            step = steps_mod.make_serve_step(model)
            cache_shape = model_mod.cache_specs(model, cfg, shape.global_batch,
                                                shape.seq_len, dtype)
            cspecs = sharding.make_cache_specs(cfg, mesh, cache_shape)
            tok_shape = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            tok_spec = jax.sharding.PartitionSpec(
                sharding.batch_spec(mesh, shape.global_batch))
            logit_spec = jax.sharding.PartitionSpec(
                sharding.batch_spec(mesh, shape.global_batch), "model")
            jitted = jax.jit(
                step,
                in_shardings=(sharding.named(mesh, pspecs),
                              jax.NamedSharding(mesh, tok_spec),
                              sharding.named(mesh, cspecs)),
                out_shardings=(jax.NamedSharding(mesh, logit_spec),
                               sharding.named(mesh, cspecs)),
                donate_argnums=(2,))
            lowered = jitted.lower(params_shape, tok_shape, cache_shape)

        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

        if prefill_chunk and shape.kind == "decode" and paged_cache:
            # chunked-prefill ingest step (DESIGN.md §Chunked prefill):
            # one (1, prefill_chunk) span scattered into the pool and
            # attended through a slot's block table — the unit the
            # chunked engine interleaves between decode steps; proving
            # it compiles on the production mesh is what gates
            # --prefill-chunk rollouts at scale
            t0 = time.time()
            chunk_step = steps_mod.make_paged_prefill_chunk_step(model)
            entries = tables_shape.shape[1]
            i32 = jnp.int32
            chunk_shapes = (
                jax.ShapeDtypeStruct((1, prefill_chunk), i32),   # tokens
                cache_shape,
                jax.ShapeDtypeStruct((1, entries), i32),         # tables
                jax.ShapeDtypeStruct((1, prefill_chunk), i32),   # dest
                jax.ShapeDtypeStruct((1,), i32),                 # slot_ids
                jax.ShapeDtypeStruct((1,), i32),                 # start
                jax.ShapeDtypeStruct((1,), i32),                 # length
            )
            rep = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
            chunk_logit = jax.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, "model"))
            chunk_jit = jax.jit(
                chunk_step,
                in_shardings=(sharding.named(mesh, pspecs), rep,
                              sharding.named(mesh, cspecs),
                              rep, rep, rep, rep, rep),
                out_shardings=(chunk_logit, sharding.named(mesh, cspecs)),
                donate_argnums=(2,))
            chunk_compiled = chunk_jit.lower(
                params_shape, chunk_shapes[0], cache_shape,
                *chunk_shapes[2:]).compile()
            rec["chunk_compile_s"] = round(time.time() - t0, 2)
            cma = chunk_compiled.memory_analysis()
            rec["chunk_memory_temp_bytes"] = int(cma.temp_size_in_bytes)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {"flops_raw": float(ca.get("flops", 0.0)),
                       "bytes_accessed_raw": float(ca.get("bytes accessed", 0.0))}
        # trip-count-corrected static analysis (see hlo_analysis.py: XLA's
        # cost_analysis counts while bodies once)
        tally = hlo_analysis.analyze(compiled.as_text())
        rec["hlo"] = {"flops": tally.flops, "bytes": tally.bytes,
                      "while_trips": tally.while_trips}
        rec["collectives"] = {k: v for k, v in tally.collectives.items()}
        rec["model_flops"] = model_flops_estimate(cfg, get_shape(shape_name))
        rec["n_devices"] = mesh.size
        rec["status"] = "ok"
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--fsdp-pods", action="store_true",
                    help="cross-pod ZeRO (for models whose optimizer state "
                         "exceeds per-pod HBM)")
    ap.add_argument("--vocab-parallel", action="store_true")
    ap.add_argument("--remat-policy", default="none", choices=["none", "dots"])
    ap.add_argument("--accum", type=int, default=8,
                    help="grad-accumulation micro-steps inside train_step")
    # engine flags (dry-run boolean variants) come from launch/cli.py
    cli.add_engine_flags(ap, dryrun=True)
    cli.add_obs_flags(ap)
    ap.add_argument("--extra", default="", help="free-form variant tag")
    ap.add_argument("--out", default=None, help="output dir for JSON records")
    args = ap.parse_args(argv)
    cli.obs_setup(args, actor="dryrun")

    pairs = []
    if args.all:
        pairs = [(a, s) for a in ASSIGNED_ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    ok = True
    for arch, shp in pairs:
        try:
            with trace.span("dryrun.build", arch=arch, shape=shp):
                rec = build_dryrun(arch, shp, multi_pod=args.multi_pod,
                                   fsdp=not args.no_fsdp,
                                   fsdp_pods=args.fsdp_pods,
                                   vocab_parallel=args.vocab_parallel,
                                   remat_policy=args.remat_policy,
                                   accum_steps=args.accum,
                                   paged_cache=args.paged_cache,
                                   block_size=args.block_size,
                                   prefill_chunk=args.prefill_chunk,
                                   fused_decode=args.fused_decode,
                                   extra=args.extra)
        except Exception as e:  # a dry-run failure is a bug in the system
            rec = {"arch": arch, "shape": shp,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
            ok = False
        print(json.dumps(rec))
        sys.stdout.flush()
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            tag = "_".join(filter(None, [
                arch, shp, rec.get("mesh", ""),
                "vp" if args.vocab_parallel else "",
                args.remat_policy if args.remat_policy != "none" else "",
                "nofsdp" if args.no_fsdp else "",
                "paged" if args.paged_cache else "",
                "fused" if args.fused_decode else "", args.extra]))
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=2)
    cli.obs_finish(args)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
