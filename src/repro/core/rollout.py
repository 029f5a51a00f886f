"""Interruptible rollout worker (Section 4.1; DESIGN.md
§Interruptible generation).

A continuous-batching generation engine over ``n_slots`` concurrent
requests with two request types, mirroring the paper:

  * ``generate``        — admit prompts into free slots (group prefill +
                          cache scatter), then stream decode steps.
  * ``update_weights``  — interrupt all in-flight generations, discard
                          the KV caches / recurrent states computed under
                          the old weights, RE-PREFILL every prefix under
                          the new weights, and continue decoding.  The
                          kept tokens retain the behavior logprobs and
                          policy-version tags recorded when they were
                          sampled — a single trajectory may span several
                          policy versions (Proposition 1).

Device state is one batched cache pytree; host state is per-slot
bookkeeping.  All jit signatures are static: admission groups are padded
to ``n_slots`` rows and dummy rows scatter to an out-of-range slot id
(dropped).  For recurrent/hybrid architectures the "KV recompute" is a
state re-scan through the same prefill path (DESIGN.md §Arch-applicability).

Two cache organizations (``cache="ring" | "paged"``):

  * ``ring``   — per-slot (B, W, ...) ring buffers; every slot carries
                 ``max_len`` (or window) KV rows whether it uses them or
                 not.
  * ``paged``  — a global pool of fixed-size KV blocks plus per-slot
                 block tables (DESIGN.md §Paged KV-cache pool).  Slots
                 only hold the blocks their history needs, shared prompt
                 prefixes (GRPO groups) map to shared read-only blocks
                 via a prefix-hash, and the ``update_weights`` re-prefill
                 rewrites each *physical* block at most once — blocks
                 already tagged with the new version are skipped.

Two prefill disciplines (``prefill_chunk``):

  * ``0`` (monolithic) — admission prefills the whole group in one call
    and ``update_weights`` re-prefills every in-flight prefix before any
    slot decodes again: every decoding slot STALLS for the full prefill.
  * ``> 0`` (chunked, DESIGN.md §Chunked prefill) — prompt ingestion and
    the post-interrupt re-prefill are split into spans of at most
    ``prefill_chunk`` tokens by ``core.batching.plan_prefill_chunks``;
    ``step()`` becomes a unified engine step that ingests at most ONE
    span (strictly FIFO across slots) and then advances every slot whose
    history is fully ingested.  An interrupted slot resumes decoding as
    soon as *its* history is back, not when the whole batch is.  Chunked
    mode requires per-request RNG streams (``rng="request"``): each
    sampled token draws from fold_in(fold_in(seed, rid), draw_index), so
    trajectories are identical to the monolithic engine's no matter how
    ingestion is scheduled.
"""
from __future__ import annotations

import threading
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RLConfig
from repro.core.batching import (BlockAllocator, plan_prefill_chunks,
                                 prefix_block_hashes)
from repro.core.config import EngineConfig
from repro.data import tokenizer
from repro.obs import trace


@dataclass
class Slot:
    active: bool = False
    rid: int = -1
    prompt_id: int = -1
    prompt: List[int] = field(default_factory=list)
    response: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    versions: List[int] = field(default_factory=list)
    behavior_version: int = 0
    pending: int = 0                   # sampled token not yet fed to cache
    answer: object = None
    submit_time: float = 0.0
    # chunked-prefill bookkeeping (DESIGN.md §Chunked prefill):
    # the history being ingested, the per-slot watermark (tokens of it
    # already in the cache), the planned spans still to feed, and — paged
    # mode — the physical blocks this ingest pass has written so far
    ingest_tokens: List[int] = field(default_factory=list)
    ingested: int = 0
    chunk_plan: List[Tuple[int, int]] = field(default_factory=list)
    written_blocks: Set[int] = field(default_factory=set)
    reingest: bool = False             # redo after an interrupt, not fresh
    cont: bool = False                 # multi-turn continuation ingest
    # multi-turn bookkeeping (DESIGN.md §Environments and reward service):
    # completed environment turns, and the [start, end) spans of
    # env-injected tokens inside ``response`` (loss-masked in training)
    turns: int = 0
    env_spans: List[Tuple[int, int]] = field(default_factory=list)

    @property
    def history_len(self) -> int:
        """Tokens already ingested by the cache (prompt + fed responses)."""
        return len(self.prompt) + len(self.response) - (1 if self.response else 0)

    @property
    def ingesting(self) -> bool:
        """True while the slot's history is not yet fully in the cache
        (the slot holds its resources but does not decode)."""
        return self.active and self.ingested < len(self.ingest_tokens)


@dataclass
class Finished:
    rid: int
    prompt_id: int
    prompt: List[int]
    response: List[int]
    logprobs: List[float]
    versions: List[int]
    behavior_version: int
    answer: object
    submit_time: float
    truncated: bool
    # multi-turn episodes: per-response-token loss mask (0.0 on
    # env-injected tokens, None for plain single-turn trajectories) and
    # the number of model turns taken
    loss_mask: Optional[List[float]] = None
    turns: int = 1


class RolloutEngine:
    """Batched, interruptible generation engine for a decoder-only LM.

    Threading contract: the engine is SINGLE-DRIVER.  All state-mutating
    calls (``admit``/``step``/``update_weights``/``maybe_apply_pending``)
    must come from one thread — the rollout thread in the threaded
    runtime (DESIGN.md §Async runtime); weight publication from the
    trainer side goes through the ``ParameterStore``, never by calling
    into the engine directly.  The contract is enforced by a cheap
    owner-thread assertion; ``release_driver()`` hands ownership off."""

    def __init__(self, model, params, cfg: Optional[EngineConfig] = None,
                 **legacy):
        """Primary form: ``RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=..., ...))`` — every pure-config invariant is validated
        by ``EngineConfig.__post_init__`` (DESIGN.md §Serving gateway).

        The legacy flat-kwarg form (``RolloutEngine(model, params,
        n_slots=8, cache="paged", ...)``) is accepted for one release:
        the kwargs forward into an ``EngineConfig`` and a
        ``DeprecationWarning`` is emitted."""
        if legacy:
            if cfg is not None:
                raise TypeError("pass EngineConfig OR legacy kwargs, "
                                "not both")
            warnings.warn(
                "RolloutEngine(model, params, n_slots=..., ...) is "
                "deprecated; pass cfg=EngineConfig(...) instead "
                "(DESIGN.md §Serving gateway)",
                DeprecationWarning, stacklevel=2)
            cfg = EngineConfig(**legacy)
        elif cfg is None:
            cfg = EngineConfig()
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.engine_config = cfg
        self.params = params
        self.version = cfg.version
        self.n_slots = cfg.n_slots
        self.prompt_len = cfg.prompt_len
        self.max_gen_len = cfg.max_gen_len
        self.max_len = cfg.prompt_len + cfg.max_gen_len
        self.temperature = cfg.temperature
        self.eos_id = cfg.eos_id
        self.dtype = jnp.float32 if cfg.dtype is None else cfg.dtype
        self._rng = jax.random.key(cfg.seed)
        self._step_count = 0
        self.mesh = None                   # set by place()
        n_slots = cfg.n_slots
        block_size = cfg.block_size
        cache = cfg.cache
        continuation = cfg.continuation
        fused_decode = cfg.fused_decode
        spec_decode = cfg.spec_decode
        spec_draft_units = cfg.spec_draft_units

        self.slots = [Slot() for _ in range(n_slots)]
        self._pending_weights: Optional[Tuple] = None
        self._driver_thread: Optional[int] = None
        self._ingest_queue: List[int] = []

        # streaming weight publication state (DESIGN.md §Version fence):
        # an in-flight stream assembles host-side in the decoder and
        # stages per-leaf device copies in _staged_dev; self.params flips
        # only when the stream COMPLETES, through update_weights
        self._stream_decoder = None
        self._staged_dev: Dict[str, object] = {}
        self._in_stream_flip = False
        self._stream_need_full = False
        self.weight_streams_completed = 0
        self.weight_streams_torn = 0

        # decode fast paths (DESIGN.md §Fused decode tail,
        # §Self-speculative decoding); pure-config invariants (spec x
        # fused exclusivity, spec-forces-greedy, fused-needs-paged) are
        # validated by EngineConfig — only MODEL-capability checks remain
        self.fused_decode = fused_decode
        self.spec_decode = int(spec_decode)
        if self.spec_decode:
            chunk_attr = ("prefill_chunk_paged" if cache == "paged"
                          else "prefill_chunk")
            if not hasattr(model, chunk_attr):
                raise ValueError(
                    "spec_decode verifies drafts through the chunked "
                    "prefill path; the model lacks " + chunk_attr)
            n_units = getattr(model, "n_units", 1)
            du = (max(1, n_units - 1) if spec_draft_units is None
                  else int(spec_draft_units))
            if not 1 <= du <= n_units:
                raise ValueError(f"spec_draft_units must be in "
                                 f"[1, {n_units}], got {du}")
            self._spec_draft_units = du
        # one in-flight speculative round: set by the draft phase,
        # consumed by verify+commit, discarded by update_weights
        self._draft: Optional[Dict] = None

        # stats
        self.tokens_generated = 0
        self.interruptions = 0
        self.prefill_tokens = 0
        self.reprefill_tokens = 0
        self.prefix_reused_blocks = 0
        self.deferred = 0                  # requests bounced on pool pressure
        self.deferred_last = 0             # ... by the most recent admit()
        self.preemptions = 0               # slots preempted by the gateway
        self.resumes = 0                   # preempted requests re-admitted
        self.decode_steps_during_prefill = 0
        self.continuations = 0             # multi-turn episode extensions
        self.continuation_tokens = 0       # appended-span tokens ingested
        # decode fast-path counters (DESIGN.md §Self-speculative decoding)
        self.decode_dispatches = 0         # jitted decode-path calls
        self.drafted_tokens = 0            # truncated-model draft proposals
        self.accepted_tokens = 0           # tokens committed by spec rounds
        self.accepted_draft_tokens = 0     # drafts the full model agreed with
        self.spec_rounds = 0
        self.spec_member_rounds = 0        # per-slot round participations

        # multi-turn hook (DESIGN.md §Multi-turn continuation in the engine):
        # fn(finished, turn, budget) -> env tokens to
        # append (the trajectory continues in place, reusing its cache
        # and pool blocks) or None to finish.  Appending re-enters the
        # FIFO ingest queue, so it requires the chunked-prefill engine
        # (enforced by EngineConfig).
        self.continuation = continuation

        # RNG discipline: "step" folds a global step counter into one key
        # per jit call (the legacy scheme — trajectories depend on batch
        # timing); "request" derives every draw from (seed, rid,
        # draw_index), making trajectories independent of admission
        # timing, interrupts, and chunking (DESIGN.md §Chunked prefill).
        self.prefill_chunk = int(cfg.prefill_chunk)
        self.rng_mode = cfg.resolved_rng

        self.cache_mode = cache
        if cache == "paged":
            if not hasattr(model, "init_paged_cache"):
                raise ValueError(
                    "cache='paged' needs a decoder-only LM with paged cache "
                    "support (DESIGN.md §Arch-applicability)")
            self.block_size = block_size
            self.n_entries = -(-self.max_len // block_size)
            self.n_blocks = cfg.n_blocks or n_slots * self.n_entries
            self.allocator = BlockAllocator(self.n_blocks, block_size,
                                            evict=cfg.evict)
            self.tables = np.full((n_slots, self.n_entries), -1, np.int32)
            self._tables_dev = None        # device copy, refreshed on change
            self.cache = model.init_paged_cache(n_slots, self.n_blocks,
                                                block_size, self.dtype)
            if self.fused_decode == "fused":
                self._jit_decode_paged = self._jit(self._decode_paged_fused_fn)
            else:
                self._jit_decode_paged = self._jit(self._decode_paged_fn)
            if self.fused_decode == "split":
                self._jit_decode_logits = self._jit(self._decode_paged_logits_fn)
                self._jit_sample = self._jit(self._sample_only_fn)
            self._jit_prefill_paged = self._jit(self._prefill_paged_fn)
            if self.prefill_chunk:
                self._jit_chunk_paged = self._jit(self._chunk_paged_fn)
                self._jit_chunk_paged_quiet = self._jit(self._chunk_paged_quiet_fn)
            if self.spec_decode:
                self._jit_spec_draft = self._jit(self._spec_draft_paged_fn)
                self._jit_spec_verify = self._jit(self._spec_verify_paged_fn)
                self._jit_spec_commit = self._jit(self._spec_commit_paged_fn)
        else:
            if self.prefill_chunk and not hasattr(model, "prefill_chunk"):
                raise ValueError(
                    "prefill_chunk > 0 needs a decoder-only LM with chunked "
                    "prefill support (DESIGN.md §Chunked prefill)")
            self.cache = model.init_cache(n_slots, self.max_len, self.dtype)
            self._jit_decode = self._jit(self._decode_fn)
            self._jit_prefill = self._jit(self._prefill_fn)
            self._jit_insert = self._jit(self.model.cache_insert)
            if self.prefill_chunk:
                self._jit_chunk = self._jit(self._chunk_fn)
                self._jit_chunk_quiet = self._jit(self._chunk_quiet_fn)
            if self.spec_decode:
                self._jit_spec_draft = self._jit(self._spec_draft_fn)
                self._jit_spec_verify = self._jit(self._spec_verify_fn)
                self._jit_spec_commit = self._jit(self._spec_commit_fn)
        if self.prefill_chunk:
            self._jit_reset = self._jit(self.model.reset_slot_rows)

    # ---- placement --------------------------------------------------------
    def place(self, mesh) -> None:
        """Commit the params and KV cache to ``mesh``, replicated, and run
        every step there (DESIGN.md §One process per chip).  Each step
        runs whole on every chip of the mesh under ``shard_map``, since
        Pallas kernels cannot be partitioned by XLA, even replicated; a
        one-chip mesh takes the same path."""
        rep = NamedSharding(mesh, P())
        self.mesh = mesh
        self.params = jax.device_put(self.params, rep)
        self.cache = jax.device_put(self.cache, rep)

    def _jit(self, fn):
        """``jax.jit`` of an engine step that honours ``place``: the mesh
        is read when the step is traced, and a placed engine's committed
        state makes jit trace again."""
        def step(*args):
            if self.mesh is None:
                return fn(*args)
            return jax.shard_map(fn, mesh=self.mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False)(*args)
        return jax.jit(step)

    # ---- sampling ---------------------------------------------------------
    def _masked_logits(self, logits):
        lf = logits.astype(jnp.float32)
        # mask padded vocab tail
        v = self.cfg.vocab_size
        return jnp.where(jnp.arange(lf.shape[-1]) < v, lf, -1e30)

    def _sample(self, logits, rng):
        """Legacy step-counter scheme: one key samples the whole batch."""
        lf = self._masked_logits(logits)
        if self.temperature <= 0.0:            # greedy (evaluation protocol)
            tok = jnp.argmax(lf, axis=-1)
        else:
            if self.temperature != 1.0:
                lf = lf / self.temperature
            tok = jax.random.categorical(rng, lf, axis=-1)
        lp = jax.nn.log_softmax(lf, axis=-1)
        lp_tok = jnp.take_along_axis(lp, tok[..., None], axis=-1)[..., 0]
        return tok.astype(jnp.int32), lp_tok

    def _sample_request(self, logits, rids, draws):
        """Per-request streams: row j draws with key
        fold_in(fold_in(seed, rid_j), draw_j) — batch-layout independent,
        so chunked and monolithic engines sample identically
        (DESIGN.md §Chunked prefill)."""
        lf = self._masked_logits(logits)
        if self.temperature <= 0.0:
            tok = jnp.argmax(lf, axis=-1)
        else:
            if self.temperature != 1.0:
                lf = lf / self.temperature
            keys = jax.vmap(lambda r, d: jax.random.fold_in(
                jax.random.fold_in(self._rng, r), d))(rids, draws)
            tok = jax.vmap(jax.random.categorical)(keys, lf)
        lp = jax.nn.log_softmax(lf, axis=-1)
        lp_tok = jnp.take_along_axis(lp, tok[..., None], axis=-1)[..., 0]
        return tok.astype(jnp.int32), lp_tok

    def _sample_any(self, logits, rng, rids, draws):
        if self.rng_mode == "request":
            return self._sample_request(logits, rids, draws)
        return self._sample(logits, rng)

    # ---- jit bodies -------------------------------------------------------
    def _decode_fn(self, params, token, cache, active, rng, rids, draws):
        logits, cache = self.model.decode_step(params, token, cache, active)
        tok, lp = self._sample_any(logits, rng, rids, draws)
        return tok, lp, cache

    def _prefill_fn(self, params, tokens, lengths, rng, rids):
        """Group prefill over (G, L) right-padded tokens -> fresh sub-cache
        + first sampled token per row."""
        g = tokens.shape[0]
        cache = self.model.init_cache(g, self.max_len, self.dtype)
        logits, cache = self.model.prefill(params, tokens, cache, length=lengths)
        tok, lp = self._sample_any(logits, rng, rids, jnp.zeros_like(rids))
        return tok, lp, cache

    def _decode_paged_fn(self, params, token, cache, tables, active, rng,
                         rids, draws):
        logits, cache = self.model.decode_step_paged(params, token, cache,
                                                     tables, active)
        tok, lp = self._sample_any(logits, rng, rids, draws)
        return tok, lp, cache

    def _prefill_paged_fn(self, params, tokens, lengths, dest, slot_ids,
                          cache, rng, rids):
        """Group prefill writing straight into the global block pool
        (``dest`` carries the physical destination block per token; -1 =
        shared/padded, not written) + first sampled token per row."""
        logits, cache = self.model.prefill_paged(params, tokens, cache, dest,
                                                 slot_ids, length=lengths)
        tok, lp = self._sample_any(logits, rng, rids, jnp.zeros_like(rids))
        return tok, lp, cache

    def _chunk_fn(self, params, tokens, cache, slot_ids, start, length, rids):
        """One ring-cache ingest span + first-token sample (used only for
        the span that completes a prompt; draw index 0 of the request)."""
        logits, cache = self.model.prefill_chunk(params, tokens, cache,
                                                 slot_ids, start, length)
        tok, lp = self._sample_request(logits, rids, jnp.zeros_like(rids))
        return tok, lp, cache

    def _chunk_quiet_fn(self, params, tokens, cache, slot_ids, start, length):
        """Non-completing ingest span: only the cache advance is returned,
        so XLA dead-code-eliminates the logits head and sampling — at
        production vocab sizes that is the dominant per-span FLOP after
        attention."""
        _, cache = self.model.prefill_chunk(params, tokens, cache,
                                            slot_ids, start, length)
        return cache

    def _chunk_paged_fn(self, params, tokens, cache, tables, dest, slot_ids,
                        start, length, rids):
        """One paged ingest span (pool writes at ``dest``) + first-token
        sample."""
        logits, cache = self.model.prefill_chunk_paged(
            params, tokens, cache, tables, dest, slot_ids, start, length)
        tok, lp = self._sample_request(logits, rids, jnp.zeros_like(rids))
        return tok, lp, cache

    def _chunk_paged_quiet_fn(self, params, tokens, cache, tables, dest,
                              slot_ids, start, length):
        """Non-completing paged span (see ``_chunk_quiet_fn``)."""
        _, cache = self.model.prefill_chunk_paged(
            params, tokens, cache, tables, dest, slot_ids, start, length)
        return cache

    # ---- decode fast-path jit bodies --------------------------------------
    def _decode_paged_fused_fn(self, params, token, cache, tables, active,
                               rng, rids, draws):
        """One-dispatch fused decode step (DESIGN.md §Fused decode tail):
        the per-layer table lookup is hoisted to one shared gather, each
        attention block's pool read + output projection runs through the
        fused-tail kernel, and sampling folds into the same program —
        one jit call in, sampled tokens out."""
        logits, cache = self.model.decode_step_paged(
            params, token, cache, tables, active, fused_tail=True)
        tok, lp = self._sample_any(logits, rng, rids, draws)
        return tok, lp, cache

    def _decode_paged_logits_fn(self, params, token, cache, tables, active):
        """Split-mode measurement baseline (DESIGN.md §Fused decode
        tail): the decode step returns full (B, Vp) logits and sampling
        runs as a SECOND dispatch — what the fused path saves."""
        return self.model.decode_step_paged(params, token, cache, tables,
                                            active)

    def _sample_only_fn(self, logits, rng, rids, draws):
        return self._sample_any(logits, rng, rids, draws)

    def _spec_draft_body(self, decode_fn, token, cache):
        """k-1 truncated-layer decode steps under one jit — the draft
        phase of DESIGN.md §Self-speculative decoding.  Every cache
        write (pool K/V, recurrent rows, positions) stays inside the
        scan carry and is DISCARDED: only the proposed tokens escape."""
        def body(carry, _):
            tok, c = carry
            logits, c = decode_fn(tok, c)
            nxt = jnp.argmax(self._masked_logits(logits),
                             axis=-1).astype(jnp.int32)
            return (nxt, c), nxt
        _, drafts = jax.lax.scan(body, (token, cache), None,
                                 length=self.spec_decode - 1)
        return drafts                       # (k-1, B)

    def _spec_draft_paged_fn(self, params, token, cache, tables, active):
        du = self._spec_draft_units
        return self._spec_draft_body(
            lambda tok, c: self.model.decode_step_paged(
                params, tok, c, tables, active, draft_units=du),
            token, cache)

    def _spec_draft_fn(self, params, token, cache, active):
        du = self._spec_draft_units
        return self._spec_draft_body(
            lambda tok, c: self.model.decode_step(
                params, tok, c, active, draft_units=du),
            token, cache)

    def _spec_greedy(self, logits):
        """Greedy verification outputs: per-position argmax + logprob
        over the (G, C, Vp) all-position logits of the verify span."""
        lf = self._masked_logits(logits)
        tok = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(lf, axis=-1),
                                 tok[..., None], axis=-1)[..., 0]
        return tok, lp

    def _spec_verify_paged_fn(self, params, tokens, cache, tables, dest,
                              slot_ids, start, length):
        """Verify ALL draft positions in one chunked-prefill-style pass
        (DESIGN.md §Self-speculative decoding): write-then-read gives
        exact causal logits at every span position; the advanced cache
        is NOT returned — rejected positions' K/V and recurrent state
        must never land, so rollback is a functional discard."""
        logits, _ = self.model.prefill_chunk_paged(
            params, tokens, cache, tables, dest, slot_ids, start, length,
            all_logits=True)
        return self._spec_greedy(logits)

    def _spec_commit_paged_fn(self, params, tokens, cache, tables, dest,
                              slot_ids, start, length):
        """Commit the accepted prefix: the same span re-runs with
        per-slot ``length`` = accepted count, so pool writes and
        recurrent-state advance stop exactly at the acceptance
        watermark and ``t`` lands on start + accepted."""
        _, cache = self.model.prefill_chunk_paged(
            params, tokens, cache, tables, dest, slot_ids, start, length)
        return cache

    def _spec_verify_fn(self, params, tokens, cache, slot_ids, start, length):
        """Ring-cache verify pass (see ``_spec_verify_paged_fn``)."""
        logits, _ = self.model.prefill_chunk(params, tokens, cache, slot_ids,
                                             start, length, all_logits=True)
        return self._spec_greedy(logits)

    def _spec_commit_fn(self, params, tokens, cache, slot_ids, start, length):
        """Ring-cache commit pass (see ``_spec_commit_paged_fn``)."""
        _, cache = self.model.prefill_chunk(params, tokens, cache, slot_ids,
                                            start, length)
        return cache

    def _next_rng(self):
        self._step_count += 1
        return jax.random.fold_in(self._rng, self._step_count)

    # ---- threading contract -----------------------------------------------
    def _assert_single_driver(self) -> None:
        """Slot bookkeeping, the block allocator, and the cache handle are
        mutated without locks: exactly ONE thread may drive
        ``admit``/``step``/``update_weights``/``maybe_apply_pending``
        (DESIGN.md §Async runtime).  The first driving call binds the
        owner; a second driving thread fails loudly here instead of
        silently corrupting slot state."""
        me = threading.get_ident()
        if self._driver_thread is None:
            self._driver_thread = me
        elif self._driver_thread != me:
            raise RuntimeError(
                f"RolloutEngine is single-driver: bound to thread "
                f"{self._driver_thread}, driven from {me}. Route all "
                f"engine calls through one rollout thread, or call "
                f"release_driver() for a deliberate handoff.")

    def release_driver(self) -> None:
        """Unbind the owner thread (deliberate handoff, e.g. the rollout
        thread exiting so the main thread may inspect/drive the engine)."""
        self._driver_thread = None

    # ---- public API -------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    def inflight_tokens(self) -> int:
        return sum(s.history_len for s in self.slots if s.active)

    @property
    def n_active(self) -> int:
        return sum(s.active for s in self.slots)

    def blocks_in_use(self) -> int:
        return self.allocator.n_live if self.cache_mode == "paged" else 0

    def ingest_backlog_tokens(self) -> int:
        """Prefill tokens still queued for chunked ingestion."""
        return sum(len(s.ingest_tokens) - s.ingested
                   for s in self.slots if s.ingesting)

    def stats(self) -> Dict[str, int]:
        """Engine counters (DESIGN.md §Chunked prefill).  ``deferred`` /
        ``deferred_last`` count requests the engine bounced on POOL
        pressure while a free slot existed — the ``AsyncScheduler`` uses
        them to requeue without pulling fresh work the engine cannot
        take, instead of re-probing ``free_slots()`` (which only sees
        slot, not block, headroom)."""
        return {
            "tokens_generated": self.tokens_generated,
            "interruptions": self.interruptions,
            "prefill_tokens": self.prefill_tokens,
            "reprefill_tokens": self.reprefill_tokens,
            "prefix_reused_blocks": self.prefix_reused_blocks,
            "deferred": self.deferred,
            "deferred_last": self.deferred_last,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "evictions": (self.allocator.evictions
                          if self.cache_mode == "paged" else 0),
            "revivals": (self.allocator.revivals
                         if self.cache_mode == "paged" else 0),
            "decode_steps_during_prefill": self.decode_steps_during_prefill,
            "ingest_backlog_tokens": self.ingest_backlog_tokens(),
            "continuations": self.continuations,
            "continuation_tokens": self.continuation_tokens,
            "decode_dispatches": self.decode_dispatches,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "spec_rounds": self.spec_rounds,
            "draft_acceptance_rate": self.draft_acceptance_rate,
            "accepted_tokens_per_step": self.accepted_tokens_per_step,
            **self.stream_stats(),
        }

    @property
    def draft_acceptance_rate(self) -> float:
        """Fraction of truncated-model draft proposals the full model's
        verify pass agreed with (DESIGN.md §Self-speculative decoding)."""
        return self.accepted_draft_tokens / max(1, self.drafted_tokens)

    @property
    def accepted_tokens_per_step(self) -> float:
        """Per-slot committed tokens per FULL-MODEL pass: a slot's round
        costs 2 full-model passes over it (verify + commit; the
        truncated draft pass is excluded because it runs only
        ``spec_draft_units`` of the layer stack) and commits its
        accepted count.  The speculative win condition is this exceeding
        1.0 — the non-speculative engine commits exactly one token per
        full-model pass over a slot.  Normalizing per member-round keeps
        the metric independent of batch occupancy."""
        return self.accepted_tokens / max(1, 2 * self.spec_member_rounds)

    @property
    def spec_pending(self) -> bool:
        """True between a round's draft phase and its verify+commit —
        the window where an ``update_weights`` interrupt lands mid-draft
        and the proposals are discarded with the old weights."""
        return self._draft is not None

    def admit(self, requests: Sequence[Dict], clock: float = 0.0) -> int:
        """requests: dicts with rid, prompt_id, prompt (list[int]), answer.
        Returns number admitted (bounded by free slots; in paged mode also
        by free pool blocks — prefix-shared blocks don't count).  Requests
        bounced on pool pressure are counted in ``deferred_last``."""
        self._assert_single_driver()
        if trace.get().enabled and requests:
            trace.instant("engine.admit", n=len(requests),
                          rids=[r["rid"] for r in requests])
        self.deferred_last = 0
        if self.prefill_chunk:
            return self._admit_chunked(requests, clock)
        if self.cache_mode == "paged":
            return self._admit_paged(requests, clock)
        free = self.free_slots()
        take = list(requests)[:len(free)]
        if not take:
            return 0
        g = self.n_slots
        toks = np.zeros((g, self.prompt_len), np.int32)
        lens = np.zeros((g,), np.int32)
        rids = np.zeros((g,), np.int32)
        slot_ids = np.full((g,), self.n_slots + 1, np.int32)   # OOB -> dropped
        for j, req in enumerate(take):
            p = list(req["prompt"])[: self.prompt_len]
            toks[j, :len(p)] = p
            lens[j] = len(p)
            rids[j] = req["rid"]
            slot_ids[j] = free[j]
        lens = np.maximum(lens, 1)
        tok0, lp0, sub_cache = self._jit_prefill(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            self._next_rng(), jnp.asarray(rids))
        self.cache = self._jit_insert(self.cache, sub_cache, jnp.asarray(slot_ids))
        self._activate_slots(take, free, lens, tok0, lp0, clock)
        return len(take)

    def _activate_slots(self, take, free, lens, tok0, lp0, clock) -> None:
        tok0 = np.asarray(tok0)
        lp0 = np.asarray(lp0)
        for j, req in enumerate(take):
            s = self.slots[free[j]]
            s.active = True
            s.rid = req["rid"]
            s.prompt_id = req.get("prompt_id", req["rid"])
            s.prompt = list(req["prompt"])[: self.prompt_len]
            s.response = [int(tok0[j])]
            s.logprobs = [float(lp0[j])]
            s.versions = [self.version]
            s.behavior_version = self.version
            s.pending = int(tok0[j])
            s.answer = req.get("answer")
            s.submit_time = clock
            self.prefill_tokens += int(lens[j])

    # ---- paged admission (prefix block reuse) -----------------------------
    def blocks_needed(self, prompt: Sequence[int]) -> int:
        """Worst-case pool blocks a request occupies (before sharing):
        enough table entries to cover the prompt plus every token the
        decode loop can feed back (the last sampled token stays pending
        and is never written)."""
        lp = max(min(len(prompt), self.prompt_len), 1)
        return -(-(lp + self.max_gen_len - 1) // self.block_size)

    def _plan_blocks(self, prompt: Sequence[int],
                     fresh_unwritten: bool) -> Optional[Tuple[List[int], int]]:
        """Reserve the block-table row for one request: prefix-shared
        leading blocks plus a freshly allocated tail.  Returns (row,
        n_reused) or None when the pool cannot cover it (the caller
        defers the request).  ``fresh_unwritten`` tags every fresh block
        version -1 ("no contents yet") so the chunked dest rule writes
        it on first touch."""
        bs = self.block_size
        need = self.blocks_needed(prompt)
        n_full = len(prompt) // bs
        try:
            prefix, reused = self.allocator.plan_prefix(self.version, prompt)
        except MemoryError:
            return None
        if self.allocator.n_available < need - n_full:
            # Rollback must not leak resources OR registrations: a fresh
            # block was registered by plan_prefix but never written, so
            # withdraw the registration before releasing — otherwise LRU
            # mode parks it as a garbage-content prefix holder and the
            # eviction cache serves wrong reuse (the continuation-re-entry
            # deferral leak; DESIGN.md §Prefix eviction policy).
            for j, b in enumerate(prefix):
                if j >= reused:
                    self.allocator.invalidate(b)
                self.allocator.release(b)
            return None                    # pool full: request stays queued
        tag = -1 if fresh_unwritten else self.version
        if fresh_unwritten:
            for b in prefix[reused:]:
                self.allocator.set_version(b, -1)
        tail = [self.allocator.alloc(tag) for _ in range(need - n_full)]
        self.prefix_reused_blocks += reused
        return prefix + tail, reused

    def _admit_paged(self, requests: Sequence[Dict], clock: float) -> int:
        free = self.free_slots()
        g = self.n_slots
        toks = np.zeros((g, self.prompt_len), np.int32)
        lens = np.zeros((g,), np.int32)
        rids = np.zeros((g,), np.int32)
        dest = np.full((g, self.prompt_len), -1, np.int32)
        slot_ids = np.full((g,), self.n_slots + 1, np.int32)   # OOB -> dropped
        take: List[Dict] = []
        for req in requests:
            if len(take) >= len(free):
                break
            p = list(req["prompt"])[: self.prompt_len]
            plan = self._plan_blocks(p, fresh_unwritten=False)
            if plan is None:
                break
            row, reused = plan
            j = len(take)
            i = free[j]
            self.tables[i, :] = -1
            self.tables[i, :len(row)] = row
            toks[j, :len(p)] = p
            lens[j] = max(len(p), 1)
            rids[j] = req["rid"]
            slot_ids[j] = i
            # write every position the prefill ingests — lens[j], not
            # len(p): an empty prompt still feeds one pad token whose KV
            # the ring engine stores, and a fresh pool block may hold a
            # released request's stale contents
            for pos in range(int(lens[j])):
                e = pos // self.block_size
                if e >= reused:            # shared blocks are already filled
                    dest[j, pos] = row[e]
            take.append(req)
        self._count_deferred(requests, free, len(take))
        if not take:
            return 0
        self._tables_dev = None
        tok0, lp0, self.cache = self._jit_prefill_paged(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray(dest), jnp.asarray(slot_ids), self.cache,
            self._next_rng(), jnp.asarray(rids))
        self._activate_slots(take, free, lens, tok0, lp0, clock)
        return len(take)

    def _count_deferred(self, requests, free, n_taken: int) -> None:
        """Pool-pressure deferral accounting: the admission loop only
        stops early on block exhaustion, so any request that had a free
        slot but was not taken was deferred for POOL resources."""
        self.deferred_last = max(0, min(len(requests), len(free)) - n_taken)
        self.deferred += self.deferred_last

    def _release_slot_blocks(self, i: int) -> None:
        for b in self.tables[i]:
            if b >= 0:
                self.allocator.release(int(b))
        self.tables[i, :] = -1
        self._tables_dev = None

    # ---- chunked admission / ingestion (DESIGN.md §Chunked prefill) -------
    def _admit_chunked(self, requests: Sequence[Dict], clock: float) -> int:
        """Admission without blocking: occupy the slot (and, paged,
        reserve its blocks) and queue the prompt for span-by-span
        ingestion; no prefill happens here.  The first token is sampled
        by the span that completes the prompt."""
        free = self.free_slots()
        take: List[Dict] = []
        reset_ids: List[int] = []
        for req in requests:
            if len(take) >= len(free):
                break
            i = free[len(take)]
            p = list(req["prompt"])[: self.prompt_len]
            if self.cache_mode == "paged":
                plan = self._plan_blocks(p, fresh_unwritten=True)
                if plan is None:
                    break
                row, _ = plan
                self.tables[i, :] = -1
                self.tables[i, :len(row)] = row
                self._tables_dev = None
            s = self.slots[i] = Slot()
            s.active = True
            s.rid = req["rid"]
            s.prompt_id = req.get("prompt_id", req["rid"])
            s.prompt = p
            s.behavior_version = self.version
            s.answer = req.get("answer")
            s.submit_time = clock
            self._queue_ingest(i, p or [0])
            reset_ids.append(i)
            take.append(req)
        if self.cache_mode == "paged":
            self._count_deferred(requests, free, len(take))
        if reset_ids:
            self._reset_rows(reset_ids)
        return len(take)

    def _queue_ingest(self, i: int, history: List[int],
                      reingest: bool = False) -> None:
        s = self.slots[i]
        s.ingest_tokens = history
        s.ingested = 0
        s.written_blocks = set()
        s.reingest = reingest
        s.cont = False                     # full (re-)ingest, not a turn
        align = self.block_size if self.cache_mode == "paged" else 1
        s.chunk_plan = plan_prefill_chunks(len(history), self.prefill_chunk,
                                           align=align)
        self._ingest_queue.append(i)

    def _reset_rows(self, slot_ids: List[int]) -> None:
        ids = np.full((self.n_slots,), self.n_slots + 1, np.int32)
        ids[:len(slot_ids)] = slot_ids
        self.cache = self._jit_reset(self.cache, jnp.asarray(ids))

    # ---- preempt / resume (DESIGN.md §Serving gateway) --------------------
    def preempt_slot(self, i: int) -> Dict:
        """Evict an ACTIVE slot mid-generation, returning a host-side
        snapshot ``admit_resume`` can later re-admit bit-exactly.

        This is the gateway's SLA lever (DESIGN.md §Serving gateway): a
        low-priority slot is preempted to make room for an urgent
        request, exactly like a weight-update interrupt except only one
        slot is touched and the trajectory is parked host-side instead
        of re-queued immediately.  Bit-exactness rests on the
        per-request RNG discipline: every draw is a pure function of
        (seed, rid, draw_index), so replaying the history through the
        chunked ingest queue and continuing the decode loop reproduces
        the uninterrupted trajectory (requires ``prefill_chunk > 0``)."""
        self._assert_single_driver()
        if not self.prefill_chunk:
            raise ValueError("preempt/resume requires prefill_chunk > 0: "
                             "resumption replays the history through the "
                             "chunked ingest queue "
                             "(DESIGN.md §Serving gateway)")
        s = self.slots[i]
        if not s.active:
            raise ValueError(f"slot {i} is not active")
        snap = {
            "rid": s.rid,
            "prompt_id": s.prompt_id,
            "prompt": list(s.prompt),
            "response": list(s.response),
            "logprobs": list(s.logprobs),
            "versions": list(s.versions),
            "behavior_version": s.behavior_version,
            "answer": s.answer,
            "submit_time": s.submit_time,
            "turns": s.turns,
            "env_spans": list(s.env_spans),
        }
        if i in self._ingest_queue:
            self._ingest_queue.remove(i)
        if self.cache_mode == "paged":
            self._release_slot_blocks(i)
        self.slots[i] = Slot()
        self.preemptions += 1
        return snap

    def admit_resume(self, snap: Dict, clock: float = 0.0) -> Optional[int]:
        """Re-admit a ``preempt_slot`` snapshot.  Returns the slot index,
        or None when no slot / no pool headroom exists (the caller keeps
        the snapshot and retries).  The history (prompt +
        response[:-1]) re-enters the FIFO ingest queue; prefix-shared
        pool blocks still current are skipped by the chunked dest rule,
        evicted ones are recomputed — either way the decode continues
        from the snapshot's pending token with the per-request RNG at
        draw index len(response), which is what makes the resumed
        trajectory bit-exact (tested in tests/test_gateway.py)."""
        self._assert_single_driver()
        if not self.prefill_chunk:
            raise ValueError("admit_resume requires prefill_chunk > 0")
        free = self.free_slots()
        if not free:
            return None
        i = free[0]
        p = list(snap["prompt"])[: self.prompt_len]
        if self.cache_mode == "paged":
            plan = self._plan_blocks(p, fresh_unwritten=True)
            if plan is None:
                self.deferred += 1         # pool pressure: retry later
                return None
            row, _ = plan
            self.tables[i, :] = -1
            self.tables[i, :len(row)] = row
            self._tables_dev = None
        s = self.slots[i] = Slot()
        s.active = True
        s.rid = snap["rid"]
        s.prompt_id = snap["prompt_id"]
        s.prompt = p
        s.answer = snap["answer"]
        s.submit_time = snap["submit_time"]
        s.behavior_version = snap["behavior_version"]
        s.turns = snap["turns"]
        s.env_spans = [tuple(x) for x in snap["env_spans"]]
        resp = list(snap["response"])
        if resp:
            s.response = resp
            s.logprobs = list(snap["logprobs"])
            s.versions = list(snap["versions"])
            s.pending = int(resp[-1])
        # an empty response resumes as a fresh admission: the span that
        # completes the prompt samples draw index 0, same as first time
        hist = ((p or [0]) + resp[:-1])[: self.max_len]
        self._queue_ingest(i, hist, reingest=True)
        self._reset_rows([i])
        self.resumes += 1
        return i

    def _ingest_one_chunk(self) -> None:
        """Feed the head-of-queue slot's next span.  Strictly FIFO across
        slots: a slot's ingestion completes before the next slot's
        starts, which is what makes prefix-shared pool blocks safe to
        skip — a "current" block observed by a later slot was fully
        written by an earlier, completed one."""
        tr = trace.get()
        if not tr.enabled:
            return self._ingest_one_chunk_impl()
        i = self._ingest_queue[0]
        s = self.slots[i]
        b, e = s.chunk_plan[0]
        with tr.span("engine.ingest", slot=i, rid=s.rid, begin=b, end=e):
            return self._ingest_one_chunk_impl()

    def _ingest_one_chunk_impl(self) -> None:
        i = self._ingest_queue[0]
        s = self.slots[i]
        begin, end = s.chunk_plan.pop(0)
        c = self.prefill_chunk
        span = s.ingest_tokens[begin:end]
        toks = np.zeros((1, c), np.int32)
        toks[0, :len(span)] = span
        start = jnp.asarray([begin], jnp.int32)
        length = jnp.asarray([len(span)], jnp.int32)
        sids = jnp.asarray([i], jnp.int32)
        rids = jnp.asarray([max(s.rid, 0)], jnp.int32)
        # the sample matters only for the span completing a fresh prompt;
        # other spans take the "quiet" jit whose logits head is DCE'd
        completes = not s.chunk_plan and not s.response
        tok0 = lp0 = None
        if self.cache_mode == "paged":
            bs = self.block_size
            dest = np.full((1, c), -1, np.int32)
            written = 0
            for k, pos in enumerate(range(begin, end)):
                e_ = pos // bs
                b = int(self.tables[i, e_])
                if (self.allocator.version_of(b) == self.version
                        and b not in s.written_blocks):
                    continue               # fully written by a completed slot
                dest[0, k] = b
                written += 1
                s.written_blocks.add(b)
                # tag current only once the block's contents are COMPLETE
                # (the span reaches its last position, or the history's):
                # sub-block spans happen when budget < block_size, and an
                # interrupt landing BETWEEN them must see the block stale,
                # not skip the half-written remainder on re-ingest
                if end >= min((e_ + 1) * bs, len(s.ingest_tokens)):
                    self.allocator.set_version(b, self.version)
            if completes:
                tok0, lp0, self.cache = self._jit_chunk_paged(
                    self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(self.tables[i:i + 1]), jnp.asarray(dest),
                    sids, start, length, rids)
            else:
                self.cache = self._jit_chunk_paged_quiet(
                    self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(self.tables[i:i + 1]), jnp.asarray(dest),
                    sids, start, length)
        else:
            written = len(span)
            if completes:
                tok0, lp0, self.cache = self._jit_chunk(
                    self.params, jnp.asarray(toks), self.cache,
                    sids, start, length, rids)
            else:
                self.cache = self._jit_chunk_quiet(
                    self.params, jnp.asarray(toks), self.cache,
                    sids, start, length)
        s.ingested = end
        # accounting keys on the ingest kind, not on response presence: a
        # slot interrupted mid-admission re-ingests with no token sampled
        # yet, and those redone spans are reprefill work (deduped writes
        # in paged mode), not additional prompt prefill; multi-turn
        # continuation spans are their own class (appended tokens only —
        # the acceptance check that shared history is never re-written)
        if s.cont:
            self.continuation_tokens += written
        elif s.reingest:
            self.reprefill_tokens += written
        else:
            self.prefill_tokens += len(span)
        if not s.ingesting:                # span completed the history
            self._ingest_queue.pop(0)
            s.written_blocks = set()
            s.cont = False
            if self.cache_mode == "paged":
                # (re-)publish the prompt's full blocks under the current
                # version so later admissions share them
                for e, h in enumerate(prefix_block_hashes(
                        self.version, s.prompt, self.block_size)):
                    self.allocator.register(h, int(self.tables[i, e]))
            if completes:
                # admission ingest: the completing span's sample is the
                # request's first token (draw index 0)
                s.response = [int(np.asarray(tok0)[0])]
                s.logprobs = [float(np.asarray(lp0)[0])]
                s.versions = [self.version]
                s.behavior_version = self.version
                s.pending = s.response[0]

    def step(self) -> List[Finished]:
        """One unified engine step (DESIGN.md §Chunked prefill): ingest at
        most one prefill span, then advance every slot whose history is
        fully in the cache.  Returns finished trajectories.  Monolithic
        engines (prefill_chunk=0) never have a span queued, so this is
        exactly one decode step across all active slots."""
        tr = trace.get()
        if not tr.enabled:                 # inert path: zero overhead
            return self._step_impl()
        with tr.span("engine.step", version=self.version,
                     n_active=self.n_active):
            fin = self._step_impl()
        if fin:
            tr.instant("engine.finished", n=len(fin),
                       rids=[f.rid for f in fin])
        return fin

    def _step_impl(self) -> List[Finished]:
        self._assert_single_driver()
        if self._ingest_queue:
            self._ingest_one_chunk()
            # Forward-progress guarantee: while NO slot can decode there is
            # nothing to overlap with, so keep ingesting until the head
            # slot's history completes and it can resume.  Without this, a
            # weight-publication rate faster than one span per history
            # (e.g. --refresh-every 1) would reset the backlog every step
            # and the engine would never decode a token again.
            while self._ingest_queue and not any(
                    s.active and not s.ingesting for s in self.slots):
                self._ingest_one_chunk()
        if self.spec_decode:
            return self._step_spec()
        act = np.array([s.active and not s.ingesting for s in self.slots])
        if not act.any():
            return []
        if self._ingest_queue:
            self.decode_steps_during_prefill += 1
        pend = np.array([s.pending for s in self.slots], np.int32)
        rids = np.array([max(s.rid, 0) for s in self.slots], np.int32)
        draws = np.array([len(s.response) for s in self.slots], np.int32)
        rng = self._next_rng() if self.rng_mode == "step" else self._rng
        if self.cache_mode == "paged":
            # tables only change at admission/finish/interrupt; keep the
            # decode loop free of per-step host->device table uploads
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self.tables)
            if self.fused_decode == "split":
                # measurement baseline: decode and sampling are separate
                # dispatches (DESIGN.md §Fused decode tail)
                logits, self.cache = self._jit_decode_logits(
                    self.params, jnp.asarray(pend), self.cache,
                    self._tables_dev, jnp.asarray(act))
                tok, lp = self._jit_sample(logits, rng, jnp.asarray(rids),
                                           jnp.asarray(draws))
                self.decode_dispatches += 2
            else:
                tok, lp, self.cache = self._jit_decode_paged(
                    self.params, jnp.asarray(pend), self.cache,
                    self._tables_dev, jnp.asarray(act), rng,
                    jnp.asarray(rids), jnp.asarray(draws))
                self.decode_dispatches += 1
        else:
            tok, lp, self.cache = self._jit_decode(
                self.params, jnp.asarray(pend), self.cache, jnp.asarray(act),
                rng, jnp.asarray(rids), jnp.asarray(draws))
            self.decode_dispatches += 1
        tok = np.asarray(tok)
        lp = np.asarray(lp)
        finished: List[Finished] = []
        for i, s in enumerate(self.slots):
            if not act[i]:
                continue
            # the pending token is now ingested; the new sample continues it
            t_new, lp_new = int(tok[i]), float(lp[i])
            s.response.append(t_new)
            s.logprobs.append(lp_new)
            s.versions.append(self.version)
            s.pending = t_new
            self.tokens_generated += 1
            fin = self._maybe_finish(i, s)
            if fin is not None:
                finished.append(fin)
        return finished

    def _maybe_finish(self, i: int, s: Slot) -> Optional[Finished]:
        """Shared end-of-trajectory handling for the plain and
        speculative decode loops: EOS/truncation check, the multi-turn
        continuation hook, block release, slot reset.  Returns the
        Finished record, or None (still running / continued)."""
        t_new = s.response[-1]
        done = t_new == self.eos_id
        trunc = len(s.response) >= self.max_gen_len
        if not (done or trunc):
            return None
        fin = self._make_finished(s, truncated=trunc and not done)
        extra = None
        if self.continuation is not None and not trunc:
            # multi-turn: the environment may answer back; the budget is
            # the response headroom left after its message plus at least
            # one sampled token
            budget = self.max_gen_len - len(s.response) - 1
            if budget > 0:
                extra = self.continuation(fin, s.turns, budget)
            if extra is not None and not 0 < len(extra) <= budget:
                extra = None
        if extra is not None:
            self._continue_slot(i, [int(t) for t in extra])
            return None                    # slot stays active, turn k+1
        if self.cache_mode == "paged":
            self._release_slot_blocks(i)
        self.slots[i] = Slot()
        return fin

    # ---- speculative decoding (DESIGN.md §Self-speculative decoding) ------
    def _span_dest(self, start: np.ndarray, length: np.ndarray) -> np.ndarray:
        """Physical destination blocks for per-slot decode spans
        [start, start+length): every decode position was preallocated at
        admission (``blocks_needed`` covers the full generation), so the
        lookup is a pure host-side table read."""
        from repro.core.batching import span_dest_blocks
        return span_dest_blocks(self.tables, start, length, self.block_size,
                                self.spec_decode)

    def _step_spec(self) -> List[Finished]:
        """One speculative engine step.  A round is TWO engine steps:

        1. draft — one jit dispatch scans k-1 truncated-layer decode
           steps from each member slot's pending token; the proposals
           park in ``self._draft`` (cache writes discarded).
        2. verify+commit — one full-model chunk pass scores every span
           position (cache discarded), the host accepts the agreeing
           prefix (capped at EOS and response headroom), and a second
           chunk pass with length = accepted commits exactly that
           prefix.

        An ``update_weights`` between the two discards ``_draft`` — the
        mid-draft interrupt of DESIGN.md §Self-speculative decoding."""
        if self._draft is not None:
            return self._spec_verify_commit()
        act = np.array([s.active and not s.ingesting for s in self.slots])
        if not act.any():
            return []
        if self._ingest_queue:
            self.decode_steps_during_prefill += 1
        k = self.spec_decode
        pend = np.array([s.pending for s in self.slots], np.int32)
        t0 = np.array([s.history_len if s.active else 0 for s in self.slots],
                      np.int32)
        if self.cache_mode == "paged":
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self.tables)
            drafts = self._jit_spec_draft(self.params, jnp.asarray(pend),
                                          self.cache, self._tables_dev,
                                          jnp.asarray(act))
        else:
            drafts = self._jit_spec_draft(self.params, jnp.asarray(pend),
                                          self.cache, jnp.asarray(act))
        self.decode_dispatches += 1
        self.drafted_tokens += (k - 1) * int(act.sum())
        self._draft = {"members": act, "pend": pend, "t0": t0,
                       "drafts": np.asarray(drafts)}
        return []

    def _spec_verify_commit(self) -> List[Finished]:
        k = self.spec_decode
        round_ = self._draft
        self._draft = None
        members = round_["members"]
        t0 = round_["t0"]
        drafts = round_["drafts"]                     # (k-1, n_slots)
        g = self.n_slots
        toks = np.zeros((g, k), np.int32)
        toks[:, 0] = round_["pend"]
        toks[:, 1:] = drafts.T
        start = np.where(members, t0, 0).astype(np.int32)
        length = np.where(members, k, 0).astype(np.int32)
        slot_ids = np.where(members, np.arange(g), g + 1).astype(np.int32)
        toks_d = jnp.asarray(toks)
        start_d = jnp.asarray(start)
        sids_d = jnp.asarray(slot_ids)
        if self.cache_mode == "paged":
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self.tables)
            gtok, glp = self._jit_spec_verify(
                self.params, toks_d, self.cache, self._tables_dev,
                jnp.asarray(self._span_dest(start, length)), sids_d,
                start_d, jnp.asarray(length))
        else:
            gtok, glp = self._jit_spec_verify(
                self.params, toks_d, self.cache, sids_d, start_d,
                jnp.asarray(length))
        self.decode_dispatches += 1
        gtok = np.asarray(gtok)                       # (n_slots, k)
        glp = np.asarray(glp)
        # host acceptance: 1 committed token + the leading drafts the
        # full model reproduced, cut at the first EOS and at the
        # response headroom
        acc = np.zeros((g,), np.int32)
        for i, s in enumerate(self.slots):
            if not members[i]:
                continue
            a = 1
            while a < k and drafts[a - 1, i] == gtok[i, a - 1]:
                a += 1
            a = min(a, self.max_gen_len - len(s.response))
            for j in range(a):
                if gtok[i, j] == self.eos_id:
                    a = j + 1
                    break
            acc[i] = a
        length_c = np.where(members, acc, 0).astype(np.int32)
        if self.cache_mode == "paged":
            self.cache = self._jit_spec_commit(
                self.params, toks_d, self.cache, self._tables_dev,
                jnp.asarray(self._span_dest(start, length_c)), sids_d,
                start_d, jnp.asarray(length_c))
        else:
            self.cache = self._jit_spec_commit(
                self.params, toks_d, self.cache, sids_d, start_d,
                jnp.asarray(length_c))
        self.decode_dispatches += 1
        self.spec_rounds += 1
        self.spec_member_rounds += int(members.sum())
        finished: List[Finished] = []
        for i, s in enumerate(self.slots):
            if not members[i]:
                continue
            a = int(acc[i])
            for j in range(a):
                s.response.append(int(gtok[i, j]))
                s.logprobs.append(float(glp[i, j]))
                s.versions.append(self.version)
            s.pending = int(gtok[i, a - 1])
            self.tokens_generated += a
            self.accepted_tokens += a
            self.accepted_draft_tokens += a - 1
            fin = self._maybe_finish(i, s)
            if fin is not None:
                finished.append(fin)
        return finished

    def _make_finished(self, s: Slot, truncated: bool) -> Finished:
        mask = None
        if s.env_spans:
            mask = [1.0] * len(s.response)
            for lo, hi in s.env_spans:
                for k in range(lo, hi):
                    mask[k] = 0.0
        return Finished(
            rid=s.rid, prompt_id=s.prompt_id, prompt=s.prompt,
            response=list(s.response), logprobs=list(s.logprobs),
            versions=list(s.versions), behavior_version=s.behavior_version,
            answer=s.answer, submit_time=s.submit_time, truncated=truncated,
            loss_mask=mask, turns=s.turns + 1)

    def _continue_slot(self, i: int, extra: List[int]) -> None:
        """Multi-turn continuation (DESIGN.md §Environments and reward
        service): append the environment's tokens to the slot's context
        and re-enter the FIFO ingest queue at the slot's existing
        watermark — the cache rows / pool blocks holding the shared
        history are REUSED, only the appended span is ingested.

        The appended tokens ride in ``response`` with logprob 0.0 and a
        loss-masking env span, so every existing invariant (history =
        prompt + response[:-1], interrupt re-ingest, staleness tags)
        holds unchanged; the last env token becomes the pending token the
        next decode step feeds."""
        s = self.slots[i]
        w = len((s.prompt or [0])) + len(s.response) - 1   # ingested history
        lo = len(s.response)
        for t in extra:
            s.response.append(t)
            s.logprobs.append(0.0)
            s.versions.append(self.version)
        s.env_spans.append((lo, len(s.response)))
        s.pending = int(s.response[-1])
        s.turns += 1
        hist = ((s.prompt or [0]) + s.response[:-1])[: self.max_len]
        s.ingest_tokens = hist
        s.ingested = w
        s.written_blocks = set()
        s.reingest = False
        s.cont = True
        align = self.block_size if self.cache_mode == "paged" else 1
        s.chunk_plan = plan_prefill_chunks(len(hist), self.prefill_chunk,
                                           align=align, start=w)
        if self.cache_mode == "paged" and w % self.block_size:
            # the boundary block is only partially filled (its tag may
            # already read "current" from the admission ingest): mark it
            # writable so the dest rule fills the appended positions —
            # full shared-history blocks stay skipped (never rewritten)
            s.written_blocks.add(int(self.tables[i, w // self.block_size]))
        self._ingest_queue.append(i)
        self.continuations += 1

    # ---- streaming weight pickup (DESIGN.md §Version fence) ---------------
    def feed_weight_message(self, msg, *, interruptible: bool = True) -> bool:
        """Version-fenced application of one publication-stream message
        (DESIGN.md §Version fence).

        While a stream is in flight the engine keeps decoding under the
        LAST COMPLETE version: chunks assemble host-side in the stream
        decoder and each completed leaf is immediately staged onto the
        device (``on_leaf`` → ``_stage_stream_leaf``), so the
        host→device transfer of later layers overlaps decode under the
        earlier ones.  Slots only interrupt when the stream COMPLETES —
        the flip is one ordinary ``update_weights`` call assembled from
        the staged leaves (unchanged leaves reuse their existing device
        buffers and are never re-transferred).  A torn stream (missing
        chunks, superseding begin — DESIGN.md §Torn-stream recovery)
        discards the staging and the engine keeps serving the last
        complete version.

        Returns True when ``msg`` completed a stream (the flip was
        applied, or queued via the non-interruptible pending path)."""
        self._assert_single_driver()
        if self._stream_decoder is None:
            from repro.core.weights import StreamDecoder
            from repro.launch.disaggregated import host_weights
            self._stream_decoder = StreamDecoder(
                host_weights(self.params), self.version,
                on_leaf=self._stage_stream_leaf)
        dec = self._stream_decoder
        torn_before = dec.torn
        out = dec.feed(msg)
        if dec.torn > torn_before:
            self.weight_streams_torn += 1
            self._staged_dev = {}
        if dec.need_full:
            dec.need_full = False
            self._stream_need_full = True
            self._staged_dev = {}
        if out is None:
            return False
        version, _host_tree = out
        staged, self._staged_dev = self._staged_dev, {}
        from repro.core.weights import tree_rebuild
        new_params = tree_rebuild(self.params, staged)
        self._in_stream_flip = True
        try:
            self.update_weights(new_params, version,
                                interruptible=interruptible)
        finally:
            self._in_stream_flip = False
        self.weight_streams_completed += 1
        return True

    def _stage_stream_leaf(self, path: str, arr) -> None:
        """Decoder ``on_leaf`` hook: push one completed leaf to the
        device NOW, under decode of the earlier layers (DESIGN.md
        §Version fence).  The staged buffer joins ``self.params`` only
        at the stream-complete flip."""
        self._staged_dev[path] = jnp.asarray(arr)

    def consume_stream_need_full(self) -> bool:
        """True once after a delta stream arrived whose base version this
        engine does not hold (DESIGN.md §Torn-stream recovery): the
        caller should request a full retransmit from the publisher."""
        flag = self._stream_need_full
        self._stream_need_full = False
        return flag

    def _invalidate_stream_decoder(self) -> None:
        """A full-tree update replaced ``self.params`` outside the
        stream path: the decoder's host base no longer matches, so drop
        it (recreated lazily from the new params) along with anything
        staged.  An open stream dies torn — last-complete semantics."""
        if self._stream_decoder is not None:
            if self._stream_decoder.mid_stream:
                self.weight_streams_torn += 1
            self._stream_decoder = None
            self._staged_dev = {}

    def stream_stats(self) -> Dict[str, int]:
        """Streaming-pickup counters (DESIGN.md §Streaming weight
        publication), merged into heartbeats by the fleet worker."""
        dec = self._stream_decoder
        base = dec.stats() if dec is not None else {
            "streams_completed": 0, "streams_torn": 0,
            "stream_chunks_received": 0, "stream_orphans": 0,
            "stream_base_mismatches": 0, "stream_active": 0}
        base["streams_completed"] = self.weight_streams_completed
        base["streams_torn"] = self.weight_streams_torn
        return base

    # ---- update_weights (the interruption path) ---------------------------
    def update_weights(self, params, version: int, *,
                       interruptible: bool = True) -> bool:
        """Returns True if applied now; False if deferred (non-interruptible
        mode with in-flight requests — the Fig. 6b baseline)."""
        self._assert_single_driver()
        trace.instant("engine.weight_flip", version=version,
                      n_active=self.n_active,
                      interruptible=interruptible,
                      stream=self._in_stream_flip)
        if not self._in_stream_flip:
            self._invalidate_stream_decoder()
        if not interruptible and self.n_active > 0:
            self._pending_weights = (params, version)
            return False
        # a speculative round caught mid-draft dies with the old weights:
        # its proposals were drafted under them and must not be verified
        # or committed under the new ones
        # (DESIGN.md §Self-speculative decoding)
        self._draft = None
        same_version = version == self.version
        params_changed = params is not self.params
        self.params = params
        self.version = version
        if self.cache_mode == "paged" and (params_changed or not same_version):
            # stale prefix hashes must never match again: the version seed
            # handles a bump, clearing handles new params under a REUSED
            # version number (the tag no longer identifies the contents)
            self.allocator.clear_prefix_map()
        if self.n_active > 0:
            force = params_changed and same_version
            if self.prefill_chunk:
                self._requeue_all_histories(force)
            elif self.cache_mode == "paged":
                # force: version tags can't detect staleness when the
                # caller swapped params without bumping the version —
                # rewrite everything, like the ring engine does
                self._reprefill_paged(force=force)
            else:
                self._reprefill_all()
            self.interruptions += 1
        return True

    def maybe_apply_pending(self) -> bool:
        self._assert_single_driver()
        if self._pending_weights is not None and self.n_active == 0:
            params, version = self._pending_weights
            self._pending_weights = None
            self.params = params
            if self.cache_mode == "paged":
                self.allocator.clear_prefix_map()
            self.version = version
            return True
        return False

    @property
    def has_pending_weights(self) -> bool:
        return self._pending_weights is not None

    def _requeue_all_histories(self, force: bool) -> None:
        """Chunked interruption (DESIGN.md §Chunked prefill): instead of a
        monolithic re-prefill, every in-flight history re-enters the
        ingest queue at watermark 0; decoding resumes per slot as its
        history completes.  A slot interrupted mid-ingest simply restarts
        its (possibly grown) history.  With ``force`` (new params under a
        reused version number) every live block of the interrupted slots
        is tagged stale so the dest rule rewrites it."""
        if self.cache_mode == "paged" and force:
            for i, s in enumerate(self.slots):
                if not s.active:
                    continue
                for b in self.tables[i]:
                    if b >= 0:
                        self.allocator.set_version(int(b), -1)
        self._ingest_queue = []
        reset_ids = []
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            # the re-fed history includes the pad token an empty prompt
            # was admitted with (see _reprefill_all) and keeps the last
            # sampled token pending
            hist = ((s.prompt or [0]) + s.response[:-1])[: self.max_len]
            self._queue_ingest(i, hist, reingest=True)
            reset_ids.append(i)
        self._reset_rows(reset_ids)

    def _reprefill_all(self) -> None:
        """Discard all device state computed under the old weights and
        recompute it for every in-flight prefix under the new weights.
        The prefix fed back is history = prompt + response[:-1]; the last
        sampled token stays ``pending`` and the ordinary decode loop
        continues — identical to uninterrupted generation had the weights
        never changed (tested: Prop. 1 equivalence when params are equal).
        """
        g = self.n_slots
        L = self.max_len
        toks = np.zeros((g, L), np.int32)
        lens = np.zeros((g,), np.int32)
        slot_ids = np.full((g,), self.n_slots + 1, np.int32)
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            # an empty prompt was admitted as one pad token: the re-fed
            # history must include it or every position shifts by one
            hist = ((s.prompt or [0]) + s.response[:-1])[:L]
            toks[i, :len(hist)] = hist
            lens[i] = len(hist)
            slot_ids[i] = i
            self.reprefill_tokens += len(hist)
        lens = np.maximum(lens, 1)
        # Full-width re-prefill (one flash-attention/scan pass per slot batch;
        # same jit as admission, traced once more for the (n_slots, max_len)
        # signature).  The sampled token is discarded — the decode loop
        # continues from each slot's kept ``pending`` token.  A constant key
        # keeps the decode RNG stream untouched, so an interruption with
        # unchanged weights is bit-identical to no interruption (Prop. 1 test).
        _, _, sub_cache = self._jit_prefill(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            jax.random.key(0), jnp.zeros((g,), jnp.int32))
        self.cache = self._jit_insert(self.cache, sub_cache,
                                      jnp.asarray(slot_ids))

    def _reprefill_paged(self, force: bool = False) -> None:
        """Paged counterpart of ``_reprefill_all``: the forward re-scan is
        the same full-width flash pass, but the pool *writes* are planned
        per physical block — a block is rewritten only if its contents
        are stale (version tag != the new version, or ``force``) and only
        by ONE of the slots referencing it, so a prompt shared by a GRPO
        group is recomputed once instead of once per slot.  Recurrent
        state is still re-scanned per slot (per-slot, nothing to dedup)."""
        g = self.n_slots
        L = self.max_len
        bs = self.block_size
        toks = np.zeros((g, L), np.int32)
        lens = np.zeros((g,), np.int32)
        dest = np.full((g, L), -1, np.int32)
        slot_ids = np.full((g,), self.n_slots + 1, np.int32)
        written = set()
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            # effective history includes the pad token an empty prompt
            # was admitted with (see _reprefill_all)
            hist = ((s.prompt or [0]) + s.response[:-1])[:L]
            toks[i, :len(hist)] = hist
            lens[i] = len(hist)
            slot_ids[i] = i
            for e in range(-(-len(hist) // bs)):
                b = int(self.tables[i, e])
                if b < 0 or b in written:
                    continue               # another sharer rewrites it
                written.add(b)
                if not force and self.allocator.version_of(b) == self.version:
                    continue               # contents already current
                lo, hi = e * bs, min((e + 1) * bs, len(hist))
                dest[i, lo:hi] = b
                self.reprefill_tokens += hi - lo
                self.allocator.set_version(b, self.version)
            # re-publish full prompt blocks under the new version's hashes
            # so post-interrupt admissions keep sharing them
            for e, h in enumerate(prefix_block_hashes(
                    self.version, s.prompt, bs)):
                self.allocator.register(h, int(self.tables[i, e]))
        lens = np.maximum(lens, 1)
        _, _, self.cache = self._jit_prefill_paged(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray(dest), jnp.asarray(slot_ids), self.cache,
            jax.random.key(0), jnp.zeros((g,), jnp.int32))
