"""Serving steps and the continuous-batching request scheduler.

``make_prefill_step`` / ``make_decode_step`` are the single-program
building blocks.
:class:`BatchScheduler` composes them into request-level micro-batching:

* **admission** — FIFO queue; a free slot triggers a one-row prefill of
  the request's exact prompt (no padding, so the first sampled token is
  taken at the true last prompt position, and no pad token enters a
  recurrent state) whose cache rows are spliced into the slot's row of
  the shared batch cache, in the same jitted op that sets the slot's
  token, position and mask on the device. On a TPU the prefill compiles
  with the configuration's scoped VMEM (``prefill_vmem_kib``), where it
  sets one;
* **per-slot positions** — every decode step runs ONE program over the
  whole batch with a ``(B,)`` position vector (``attn_decode``'s per-row
  path), so co-batched requests at different depths neither pad nor
  re-compile; with ``decode_impl='pallas'`` the ragged depths feed the
  flash-decode kernel's scalar-prefetch lengths directly;
* **device-resident control** — each slot's last token, its position and
  the active-row mask live on the device. A step takes its tokens from
  the last step's output and advances the positions of active rows only;
  an empty row's position stays frozen. The host sends the mask again
  only when a slot is evicted;
* **one step ahead** — ``step()`` launches decode step n+1 before it
  fetches step n's tokens, so the device runs n+1 while the host fetches
  and does its per-slot work. A slot that ends by ``max_new_tokens`` or
  cache exhaustion is known from the host's counts when n+1 is launched
  and is not launched past its end. An EOS is learned one step late: the
  token of the step launched past it is dropped (``serving.overrun_steps``
  counts such steps) and the step still counts as a decode step.
  ``serving.steps_ahead`` counts the steps launched while the previous
  step's tokens were still unfetched; ``run()`` drains the step in flight;
* **eviction** — EOS, ``max_new_tokens`` or cache exhaustion frees the
  slot for the next queued request mid-flight;
* **weights** — every tree given to the scheduler is stored in the
  compute dtype (``registry.serving_params``), so the step converts no
  weight; ``serving.weight_bytes`` reads the tree's bytes;
* **cache** — the model's own cache tree, one row per slot (batch on
  axis 1 of every leaf): one stacked tree per run of layers
  (``transformer.layer_runs``), per-head K/V or one latent row a position
  for attention, and for the Mamba-2 or KDA runs of a hybrid model a
  fixed-size recurrent state (the convs' last inputs, and the SSM state
  or each head's delta-rule matrix) beside them.
  Admission splices the prefilled row of every leaf into the slot, so a
  re-admitted slot starts from the new request's state and KV rows, never
  the last request's; ``serving.cache_bytes`` reads the tree's bytes and
  ``serving.state_bytes`` the recurrent state's part of them;
* **metrics** — per-request latency and token counts land in the
  process-wide observability registry (``serving.*``).
* **spans** — ``serving.admit`` per request; ``serving.step`` per call of
  ``step()``, holding the launch of the next decode step and then
  ``serving.fetch``, the fetch of the step before it; unpersisted
  (profiler only), so a long generation adds no rows to its process
  timeline.

Greedy decoding throughout: a given (model, prompt) pair always yields
the same continuation, which is what lets generations participate in the
content-addressed cache (see ``serving/inference.py``).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.attention import decode_kernel_block
from repro.models.registry import (LM_FAMILIES, ModelBundle, refused_reason,
                                   serving_params)
from repro.models.transformer import STATE_LEAVES, expert_layers
from repro.observability import trace
from repro.observability.metrics import get_registry


def _greedy(bundle: ModelBundle, logits: jax.Array) -> jax.Array:
    """The best token of each row's last position, (B, 1) int32."""
    next_tok = jnp.argmax(logits[:, -1, :bundle.cfg.vocab_size], axis=-1)
    return next_tok.astype(jnp.int32)[:, None]


def make_prefill_step(bundle: ModelBundle) -> Callable:
    def prefill_step(params, batch, cache):
        logits, cache = bundle.prefill_fn(params, batch, cache)
        return _greedy(bundle, logits), cache

    return prefill_step


def make_decode_step(bundle: ModelBundle) -> Callable:
    def serve_step(params, cache, tokens, pos):
        logits, cache = bundle.decode_fn(params, cache, tokens, pos)
        return _greedy(bundle, logits), cache

    return serve_step


def make_scheduled_step(bundle: ModelBundle) -> Callable:
    """The scheduler's decode step: ``make_decode_step``'s, which also
    returns the positions advanced for the active rows, and for a model
    with the held-expert layer the held experts the step read."""
    count = {"experts_read": True} if expert_layers(bundle.cfg) else {}

    def serve_step(params, cache, tokens, pos, active):
        logits, cache, *read = bundle.decode_fn(params, cache, tokens, pos,
                                                **count)
        return (_greedy(bundle, logits), cache,
                pos + active.astype(pos.dtype), *read)

    return serve_step


# ---------------------------------------------------------------------------
# Continuous-batching scheduler (host-side control, one jitted decode step)
# ---------------------------------------------------------------------------

class QueueFullError(RuntimeError):
    """submit() rejected: the admission queue is at ``max_pending``."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str = ""           # 'eos' | 'length' | 'cache_full'
    submitted_at: float = 0.0
    started_at: float = 0.0
    finished_at: float = 0.0


#: a launched decode step: its tokens on the device, the held experts it
#: read (one device scalar, or none for a model without the held-expert
#: layer), and the (slot, request) rows it advances
_Launched = tuple[jax.Array, list[jax.Array], list[tuple[int, Request]]]


class BatchScheduler:
    """Slot-based continuous batching with per-slot decode positions.

    ``batch_size`` fixes the decode micro-batch (the compiled program's
    batch dim); requests beyond that wait in the FIFO queue and are
    admitted the moment a slot is evicted. ``max_len`` bounds prompt +
    generation per slot.
    """

    def __init__(self, bundle: ModelBundle, params: Any, batch_size: int,
                 max_len: int, eos_id: int = -1,
                 max_pending: int | None = None):
        if bundle.cfg.family not in LM_FAMILIES:
            raise ValueError(
                f"BatchScheduler drives the LM families {LM_FAMILIES} (with "
                f"Mamba-2 and KDA layers through layer_types), not "
                f"{bundle.cfg.family!r}: {refused_reason(bundle.cfg)}")
        self.bundle = bundle
        self._g_weight_bytes = get_registry().gauge("serving.weight_bytes")
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        self.eos_id = eos_id
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        #: admission bound: submissions beyond batch-occupancy + this many
        #: queued requests are rejected (backpressure to the caller)
        #: instead of growing the FIFO without limit
        self.max_pending = max_pending
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * batch_size
        self.decode_step = jax.jit(make_scheduled_step(bundle),
                                   donate_argnums=(1,))
        # one-row prefill; retraces per distinct prompt length (serving
        # workloads draw from a small set of lengths — see docs/serving.md)
        vmem = bundle.cfg.prefill_vmem_kib
        self.prefill_step = jax.jit(
            make_prefill_step(bundle),
            compiler_options=(
                {"xla_tpu_scoped_vmem_limit_kib": vmem}
                if vmem and jax.default_backend() == "tpu" else None))
        self._insert_row = jax.jit(self._admit_row_impl, donate_argnums=(0,))
        self.cache = bundle.init_cache(batch_size, max_len)
        get_registry().gauge("serving.cache_bytes").set(sum(
            leaf.nbytes for leaf in jax.tree.leaves(self.cache)))
        get_registry().gauge("serving.state_bytes").set(sum(
            leaf.nbytes for path, leaf in
            jax.tree_util.tree_flatten_with_path(self.cache)[0]
            if getattr(path[-1], "key", None) in STATE_LEAVES))
        # device-resident control state: last token, cache depth and mask
        # per slot. Empty slots keep a frozen pos — their rows are never
        # read, and admission overwrites the whole row before re-activating
        # one. ``_active`` and ``_pos`` are the host's copies of the
        # device's mask and positions.
        self.tokens = jnp.zeros((batch_size, 1), jnp.int32)
        self.pos = jnp.zeros(batch_size, jnp.int32)
        self.active = jnp.zeros(batch_size, bool)
        self._active = np.zeros(batch_size, bool)
        self._pos = np.zeros(batch_size, np.int64)
        self._in_flight: _Launched | None = None
        reg = get_registry()
        self._m_submitted = reg.counter("serving.requests_submitted")
        self._m_completed = reg.counter("serving.requests_completed")
        self._m_evicted = reg.counter("serving.slot_evictions")
        self._m_decode_steps = reg.counter("serving.decode_steps")
        self._m_steps_ahead = reg.counter("serving.steps_ahead")
        self._m_overrun = reg.counter("serving.overrun_steps")
        self._m_prefill_tokens = reg.counter("serving.prefill_tokens")
        self._m_tokens = reg.counter("serving.tokens_generated")
        self._g_active = reg.gauge("serving.slots_active")
        self._g_queue = reg.gauge("serving.queue_depth")
        self._h_latency = reg.histogram("serving.request_seconds")
        self._m_rejected = reg.counter("serving.rejected")
        #: held experts a step could read: held x expert layers
        self._experts_held = (expert_layers(bundle.cfg)
                              * bundle.cfg.held_experts)
        if self._experts_held:
            self._m_experts_read = reg.counter("moe.experts_read")
            self._m_experts_held = reg.counter("moe.experts_held")
        #: the dense decode kernel's layers and positions a KV block, for
        #: a model whose decode runs it (else 0), and its grid's blocks a
        #: step over those layers
        ks = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(self.cache)[0]
              if getattr(path[-1], "key", None) == "k"]
        self._kv_layers = sum(k.shape[0] for k in ks)
        self._kv_block = decode_kernel_block(bundle.cfg, ks[0]) if ks else 0
        if self._kv_block:
            self._kv_grid = (self._kv_layers * batch_size
                             * (ks[0].shape[-1] // self._kv_block))
            self._m_kv_read = reg.counter("attn.kv_blocks_read")
            self._m_kv_grid = reg.counter("attn.kv_blocks_grid")

    @property
    def params(self) -> Any:
        """The tree the steps read. Assigning one stores its leaves used at
        the compute dtype in that dtype (a no-op on a tree that already
        is) and sets ``serving.weight_bytes``."""
        return self._params

    @params.setter
    def params(self, tree: Any) -> None:
        self._params = serving_params(self.bundle.cfg, tree)
        self._g_weight_bytes.set(sum(
            leaf.nbytes for leaf in jax.tree.leaves(self._params)))

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) >= self.max_len:
            raise ValueError(f"prompt of {len(req.prompt)} tokens cannot fit "
                             f"a max_len={self.max_len} cache")
        if (self.max_pending is not None
                and len(self.queue) >= self.max_pending):
            self._m_rejected.inc()
            raise QueueFullError(
                f"admission queue full: {len(self.queue)} pending "
                f"(max_pending={self.max_pending}); retry after the batch "
                "drains or raise max_pending")
        req.submitted_at = time.monotonic()
        self.queue.append(req)
        self._m_submitted.inc()
        self._g_queue.set(len(self.queue))

    @staticmethod
    def _insert_row_impl(full_cache, row_cache, slot):
        return jax.tree.map(
            lambda f, r: lax.dynamic_update_slice_in_dim(
                f, r.astype(f.dtype), slot, axis=1),
            full_cache, row_cache)

    @staticmethod
    def _admit_row_impl(cache, tokens, pos, active, row_cache, first_tok,
                        slot, length):
        """Splice a prefilled row into the cache and start its slot: the
        first token, the prompt's length as position, the row active."""
        return (BatchScheduler._insert_row_impl(cache, row_cache, slot),
                tokens.at[slot].set(first_tok[0]), pos.at[slot].set(length),
                active.at[slot].set(True))

    def _prefill_into_slot(self, req: Request, slot: int) -> None:
        with trace.span("serving.admit"):
            req.started_at = time.monotonic()
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            row_cache = self.bundle.init_cache(1, self.max_len)
            first_tok, row_cache = self.prefill_step(
                self.params, {"tokens": prompt}, row_cache)
            self.cache, self.tokens, self.pos, self.active = \
                self._insert_row(self.cache, self.tokens, self.pos,
                                 self.active, row_cache, first_tok,
                                 np.int32(slot), np.int32(len(req.prompt)))
            self._active[slot] = True
            self._pos[slot] = len(req.prompt)
            self.slots[slot] = req
            tok = int(jax.device_get(first_tok)[0, 0])
            req.generated = [tok]
            self._m_prefill_tokens.inc(len(req.prompt))
            self._m_tokens.inc()

    def _admit(self) -> list[Request]:
        """Fill free slots from the queue; returns requests that finished
        at admission (single-token generations)."""
        finished = []
        for i in range(self.batch_size):
            if self.slots[i] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            self._prefill_into_slot(req, i)
            if self._maybe_finish(req):
                self.slots[i] = None
                finished.append(req)
        self._g_queue.set(len(self.queue))
        self._g_active.set(sum(s is not None for s in self.slots))
        return finished

    # -- eviction ------------------------------------------------------------
    def _has_room(self, req: Request, tokens: int) -> bool:
        """Whether a request that holds ``tokens`` tokens decodes another:
        it wants more, and the cache has a position past the last one."""
        return (tokens < req.max_new_tokens
                and len(req.prompt) + tokens < self.max_len)

    def _maybe_finish(self, req: Request) -> bool:
        if req.generated[-1] == self.eos_id:
            req.finish_reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            req.finish_reason = "length"
        elif not self._has_room(req, len(req.generated)):
            req.finish_reason = "cache_full"
        else:
            return False
        req.done = True
        req.finished_at = time.monotonic()
        self._m_completed.inc()
        self._m_evicted.inc()
        self._h_latency.observe(req.finished_at - req.submitted_at)
        return True

    def _release_ending(self) -> None:
        """Free the slots whose request gets its last token from the step
        in flight, so that no step is launched past its end."""
        pending = dict(self._in_flight[2]) if self._in_flight else {}
        for i, req in enumerate(self.slots):
            if req is not None and not self._has_room(
                    req, len(req.generated) + (pending.get(i) is req)):
                self.slots[i] = None

    # -- the decode loop -----------------------------------------------------
    def _launch(self) -> _Launched | None:
        """Launch one decode step over the occupied slots, fed from the
        device's tokens and positions; None when no slot is occupied."""
        rows = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not rows:
            return None
        occupied = np.array([r is not None for r in self.slots])
        if not np.array_equal(occupied, self._active):   # a slot was evicted
            self._active = occupied
            self.active = jnp.asarray(occupied)
        if self._in_flight is not None:
            self._m_steps_ahead.inc()
        next_tok, self.cache, self.pos, *read = self.decode_step(
            self.params, self.cache, self.tokens, self.pos, self.active)
        next_tok.copy_to_host_async()
        self.tokens = next_tok
        self._m_decode_steps.inc()
        if self._kv_block:
            # the blocks the kernel fetches: ceil(kv_len / block) a row,
            # kv_len = pos + 1, in each layer that runs it
            blocks = (self._pos + self._kv_block) // self._kv_block
            self._m_kv_read.inc(self._kv_layers * int(blocks.sum()))
            self._m_kv_grid.inc(self._kv_grid)
        self._pos += self._active
        return next_tok, read, rows

    def _collect(self, launched: _Launched) -> list[Request]:
        """Fetch a launched step's tokens and hand them to its requests;
        returns the requests that finished."""
        next_tok, read, rows = launched
        with trace.span("serving.fetch", persist=False):
            next_host, read = jax.device_get((next_tok, read))
        next_host = next_host[:, 0]
        if read:
            self._m_experts_read.inc(int(read[0]))
            self._m_experts_held.inc(self._experts_held)
        finished, dropped = [], False
        for i, req in rows:
            if req.done:          # ended by an EOS learned after the launch
                dropped = True
                continue
            req.generated.append(int(next_host[i]))
            self._m_tokens.inc()
            if self._maybe_finish(req):
                finished.append(req)
                if self.slots[i] is req:
                    self.slots[i] = None
        if dropped:
            self._m_overrun.inc()
        return finished

    def step(self) -> list[Request]:
        """Admit waiting requests, launch the next decode step across all
        active slots, then fetch the step launched before it; returns the
        requests that finished."""
        with trace.span("serving.step", persist=False):
            self._release_ending()
            finished = self._admit()
            before, self._in_flight = self._in_flight, self._launch()
            if before is not None:
                finished += self._collect(before)
            self._g_active.set(sum(s is not None for s in self.slots))
            return finished

    def run(self) -> list[Request]:
        """Drain queue, slots and the step in flight to completion;
        finished in completion order."""
        finished: list[Request] = []
        while (self.queue or self._in_flight is not None
               or any(s is not None for s in self.slots)):
            finished.extend(self.step())
        return finished
