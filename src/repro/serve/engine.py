"""Serving: prefill + decode steps and a batched continuous-batching loop.

The decode step is the paper's workload (§4 of DESIGN.md): a batched GEMV
against bank-resident weights — PIM-suitable by all three takeaways. The
engine keeps the weight layout identical between prefill and decode (no
resharding at the boundary) and a slot-based KV cache so requests of
different lengths share one batch (continuous batching):

  * `Slots` tracks per-slot position/liveness; arrivals fill free slots,
    finished sequences free them. Positions are per-slot (`positions`
    argument of the model forward), so one decode step advances every live
    slot by one token regardless of length skew.
  * Greedy sampling by default; temperature knob for examples.
  * Two backends share the loop: the fused-jit steps (default) and the
    planner-routed hybrid steps (`engine="dispatch"`,
    `serve.dispatch_engine`) — same signatures, same tokens. Under
    dispatch, BOTH phases flow through the offload planner (decode over
    the decode DAG, prefill chunked over the prefill DAG) and execute
    through the unified plan executor's schedule timeline (DESIGN.md
    §9-§11). Dense and routed-MoE decoders both dispatch: MoE layers
    run as the planner's exchange-phase ladder (router -> token
    exchange -> bank-sharded expert FFNs -> combine, DESIGN.md §12).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..dispatch.trace.events import maybe_span
from ..models import ModelConfig, Shardings, forward, init_cache


def make_prefill_step(cfg: ModelConfig, shd: Shardings):
    """(params, cache, batch_inputs) -> (last_logits, cache)."""
    def prefill_step(params, cache, inputs):
        logits, cache, _ = forward(params, cfg, shd, cache=cache, **inputs)
        return logits[:, -1], cache
    return prefill_step


def make_decode_step(cfg: ModelConfig, shd: Shardings):
    """(params, cache, tokens (B,1)) -> (logits (B,V), cache)."""
    def decode_step(params, cache, tokens):
        logits, cache, _ = forward(params, cfg, shd, tokens=tokens,
                                   cache=cache)
        return logits[:, -1], cache
    return decode_step


def sample(logits, key, temperature: float = 0.0):
    """Greedy argmax (`temperature <= 0`) or temperature sampling over the
    last axis of `logits`; returns int32 token ids."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


# --------------------------------------------------------------------- #
# batched serving engine
# --------------------------------------------------------------------- #

@dataclasses.dataclass
class Request:
    """One serving request: an int32 prompt, a new-token budget, and the
    tokens generated so far (`out_tokens`, filled by the engine)."""
    rid: int
    prompt: jnp.ndarray          # (S,) int32
    max_new_tokens: int
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Slot-based batched decoding over a fixed batch of cache slots.

    Single-sequence prefill per arrival (depth-first admission) + batched
    decode for all live slots. CPU-host loop; the steps themselves are
    jitted and mesh-shardable (the decode step is what the dry-run lowers).
    """

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int,
                 max_len: int, shd: Shardings | None = None,
                 temperature: float = 0.0, eos_id: int | None = None,
                 seed: int = 0, engine: str = "jit",
                 dispatch_kwargs: dict | None = None):
        if engine not in ("jit", "dispatch"):
            raise ValueError(f"engine must be 'jit' or 'dispatch', "
                             f"got {engine!r}")
        self.cfg = cfg
        self.shd = shd or Shardings(None)
        self.params = params
        self.n_slots = batch_slots
        self.max_len = max_len
        self.temperature = temperature
        self.eos_id = eos_id
        self.key = jax.random.PRNGKey(seed)
        self.engine = engine

        # per-slot caches live stacked in one batched cache
        self.tracer = None               # dispatch.trace.Trace | None
        self._step_no = 0
        self.cache = init_cache(cfg, batch_slots, max_len, self.shd)
        # the model's cache carries one global index; per-slot positions
        # are maintained here and passed through `positions`
        self.slot_pos = jnp.zeros((batch_slots,), jnp.int32)
        self.slot_live = [False] * batch_slots
        self.slot_req: list[Request | None] = [None] * batch_slots
        self.last_tok = jnp.zeros((batch_slots, 1), jnp.int32)

        if engine == "dispatch":
            # both serving phases route through the offload planner
            # (serve.dispatch_engine): decode over the decode DAG, prefill
            # chunked over the prefill DAG — PIM stages run as BankGrid
            # phases, host stages under per-stage jit. `prefill_*` keys of
            # dispatch_kwargs configure the prefill step; the rest go to
            # both steps.
            from .dispatch_engine import (DispatchDecodeStep,
                                          DispatchPrefillStep)
            dk = dict(dispatch_kwargs or {})
            pk = {"chunk": dk.pop("prefill_chunk", None),
                  "objective": dk.pop("prefill_objective", "overlapped"),
                  "force_assignment":
                      dk.pop("prefill_force_assignment", None)}
            # `prefill_engine="jit"` keeps prefill on the fused path —
            # the dispatch prefill is ulp-close but not bitwise to it
            # (per-stage jit changes XLA fusion), so decode-only bitwise
            # identity gates need fused-prefilled caches
            prefill_engine = dk.pop("prefill_engine", "dispatch")
            if prefill_engine not in ("dispatch", "jit"):
                raise ValueError(f"prefill_engine must be 'dispatch' or "
                                 f"'jit', got {prefill_engine!r}")
            self._decode = DispatchDecodeStep(
                cfg, self.shd, batch_slots=batch_slots, max_len=max_len,
                temperature=temperature, **dk)
            self.dispatch_plan = self._decode.plan
            if prefill_engine == "dispatch":
                self._prefill_step = DispatchPrefillStep(
                    cfg, self.shd, max_len=max_len, grid=self._decode.grid,
                    devices=dk.get("devices", ("xeon", "upmem_2556")),
                    kv_home=dk.get("kv_home", "upmem_2556"), **pk)
                self.prefill_plan = self._prefill_step.plan
                self._prefill_one = self._prefill_step
            else:
                self.prefill_plan = None
                self._prefill_one = jax.jit(self._prefill_one_fn)
        else:
            self._decode = jax.jit(self._decode_step_fn)
            self.dispatch_plan = None
            self.prefill_plan = None
            # retraces once per distinct prompt length (padded buckets
            # in prod)
            self._prefill_one = jax.jit(self._prefill_one_fn)

    # ------------------------------------------------------------- #
    def _decode_step_fn(self, params, cache, tokens, slot_pos, live_mask,
                        key):
        positions = slot_pos[:, None]
        # index drives slot addressing; per-slot validity is the per-row
        # positions array (cache index is the max position across slots)
        logits, new_cache, _ = forward(params, self.cfg, self.shd,
                                       tokens=tokens, cache=cache,
                                       positions=positions)
        nxt = sample(logits[:, -1], key, self.temperature)
        # dead slots keep their last token and don't advance
        nxt = jnp.where(live_mask, nxt, tokens[:, 0])
        new_pos = jnp.where(live_mask, slot_pos + 1, slot_pos)
        return nxt[:, None], new_cache, new_pos

    def _prefill_one_fn(self, params, cache, tokens, slot):
        """Prefill one slot: run the single sequence through, scatter its
        KV rows into the batched cache at `slot`."""
        one = init_cache(self.cfg, 1, self.max_len, self.shd)
        logits, one, _ = forward(params, self.cfg, self.shd,
                                 tokens=tokens[None], cache=one)
        # scatter every per-batch tensor of `one` into row `slot` of cache
        def scatter(c_dst, c_src):
            # leaves have shape (blocks, B, ...) for stacked layers or (B,...)
            def leaf(d, s):
                if d.ndim >= 2 and d.shape[0] == self.cfg.n_blocks \
                        and s.shape[0] == self.cfg.n_blocks:
                    return jax.vmap(
                        lambda dd, ss: jax.lax.dynamic_update_slice_in_dim(
                            dd, ss.astype(dd.dtype), slot, axis=0))(d, s)
                return jax.lax.dynamic_update_slice_in_dim(
                    d, s.astype(d.dtype), slot, axis=0)
            return jax.tree.map(leaf, c_dst, c_src)

        new_layers = scatter(cache["layers"], one["layers"])
        new_cache = dict(cache, layers=new_layers,
                         index=jnp.maximum(cache["index"], one["index"]))
        return logits[0, -1], new_cache

    # ------------------------------------------------------------- #
    def attach_tracer(self, tracer) -> None:
        """Attach a `dispatch.trace.Trace`: the serving loop records one
        `prefill_step` span per admission (with the slot and prompt
        length; it ends once the first token is on the host, a wait held
        by its child `prefill_sync`) and one `decode_step` span per
        batched step (with the live slots and per-slot positions —
        per-slot latency attribution: every live slot advanced one token
        in that span), whose child `decode_sync` holds the step's one
        host sync; `decode_prep` (live mask, key split) precedes it and
        `decode_record` (per-slot tokens and finishes) follows it. While
        attached, every program JAX traces in the process is recorded as
        a `compile` event on resource `"jax"` (a `jax.monitoring`
        listener, removed on detach). Under `engine="dispatch"` the
        tracer also threads through both planner-routed steps into
        `PlanExecutor.run` (per-node compute spans, channel occupancy)
        and the FaceCache (compile vs cache-hit). Pass None to detach."""
        if self.tracer is not None:
            jax.monitoring.unregister_event_duration_listener(
                self.tracer.record_jax_compile)
        self.tracer = tracer
        if tracer is not None:
            jax.monitoring.register_event_duration_secs_listener(
                tracer.record_jax_compile)
        if self.engine == "dispatch":
            self._decode.tracer = tracer
            if self.prefill_plan is not None:
                self._prefill_step.tracer = tracer

    @property
    def n_free(self) -> int:
        """Number of free (admittable) cache slots right now."""
        return self.slot_live.count(False)

    def prefill_splits(self, plen: int) -> list[int]:
        """Chunk lengths a `plen`-token prompt prefills in: the dispatch
        prefill step's chunk grid when that path is active, one fused
        chunk otherwise. This is the chunk-splits component of the batch
        signature `serve.gateway`'s plan cache keys prefill pricing by."""
        if self.engine == "dispatch" and self.prefill_plan is not None:
            return self._prefill_step.chunk_splits(plen)
        return [int(plen)]

    def admit(self, req: Request) -> bool:
        """Admit a request into a free slot (prefill now). False if full.

        Raises ValueError for prompts the slot cache cannot hold
        (`len(prompt) >= max_len` would overflow the scatter into the
        batched cache — the slot must fit the prompt plus at least one
        generated token) and for non-positive token budgets; admission
        control above the engine (`serve.gateway`) turns both into
        reject/shed decisions. A request whose budget or EOS is already
        satisfied by its FIRST sampled token finishes at admit: it is
        marked done and the slot stays free — it never enters decode."""
        try:
            slot = self.slot_live.index(False)
        except ValueError:
            return False
        plen = int(req.prompt.shape[0])
        if plen >= self.max_len:
            raise ValueError(
                f"prompt of {plen} tokens does not fit max_len="
                f"{self.max_len} (slot cache holds prompt + generated "
                "tokens); reject or shed it upstream")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens}")
        name = f"req{req.rid}"
        with maybe_span(self.tracer, "prefill_step", name, "engine",
                        slot=slot, prompt_len=plen):
            logits, self.cache = self._prefill_one(
                self.params, self.cache, req.prompt, jnp.int32(slot))
            self.key, k = jax.random.split(self.key)
            with maybe_span(self.tracer, "prefill_sync", name, "engine"):
                first = int(sample(logits, k, self.temperature))
        req.out_tokens.append(first)
        # the first token can already exhaust the budget or hit EOS —
        # finish here and leave the slot free instead of decoding (and
        # billing) an extra token
        if (len(req.out_tokens) >= req.max_new_tokens
                or (self.eos_id is not None and first == self.eos_id)):
            req.done = True
            return True
        self.slot_live[slot] = True
        self.slot_req[slot] = req
        self.slot_pos = self.slot_pos.at[slot].set(plen)
        self.last_tok = self.last_tok.at[slot, 0].set(first)
        return True

    def step(self) -> int:
        """One batched decode step for all live slots. Returns #live."""
        if not any(self.slot_live):
            return 0
        tracer = self.tracer
        if tracer is not None:
            self._step_no += 1
        name = f"step{self._step_no}"
        with maybe_span(tracer, "decode_prep", name, "engine"):
            live = jnp.asarray(self.slot_live)
            self.key, k = jax.random.split(self.key)
        with maybe_span(tracer, "decode_step", name, "engine") as span:
            self.last_tok, self.cache, self.slot_pos = self._decode(
                self.params, self.cache, self.last_tok, self.slot_pos, live,
                k)
            # ONE host sync per step: tokens and positions fetched
            # together. (The finish loop's per-slot int(self.slot_pos[slot])
            # and the tracer's second device_get were each an extra device
            # round-trip.)
            with maybe_span(tracer, "decode_sync", name, "engine"):
                toks, pos = jax.device_get((self.last_tok[:, 0],
                                            self.slot_pos))
            if span is not None:         # device_get synced: span = real
                span.attrs.update(       # step latency, one token per slot
                    n_live=sum(self.slot_live),
                    slots=[s for s, lv in enumerate(self.slot_live) if lv],
                    positions=[int(p) for p, lv in zip(pos, self.slot_live)
                               if lv])
        with maybe_span(tracer, "decode_record", name, "engine"):
            for slot, req in enumerate(self.slot_req):
                if req is None or not self.slot_live[slot]:
                    continue
                t = int(toks[slot])
                req.out_tokens.append(t)
                limit_hit = len(req.out_tokens) >= req.max_new_tokens
                eos_hit = self.eos_id is not None and t == self.eos_id
                if (limit_hit or eos_hit
                        or int(pos[slot]) >= self.max_len - 1):
                    req.done = True
                    self.slot_live[slot] = False
                    self.slot_req[slot] = None
        return sum(self.slot_live)

    def serve(self, requests: list[Request]) -> list[Request]:
        """Run a full workload: admit as slots free up, decode until done."""
        pending = list(requests)
        done: list[Request] = []
        inflight: list[Request] = []
        while pending or inflight:
            while pending and self.admit(pending[0]):
                inflight.append(pending.pop(0))
            self.step()
            for r in list(inflight):
                if r.done:
                    inflight.remove(r)
                    done.append(r)
        return done
