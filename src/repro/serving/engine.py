"""Continuous-batching serving engine on real JAX models.

The CPU-runnable counterpart of the simulator's instance model: fixed
decode slots over a preallocated KV cache, policy-ordered admission
through the shared ``Scheduler`` protocol (any registered scheduler —
FCFS/EDF/PF/DPA/WSL — or a custom ordering callable), prefill-then-
decode.  ``ServeRequest`` satisfies the same ``RequestLike`` shape as
the simulator's ``Request``, so schedulers and the NIW queue manager
run unchanged against either path.  At smoke scale this runs actual
forward passes; on TPU the same engine drives the sharded model (see
launch/serve.py).

Each ``step()`` is a tree of host spans (``repro.serving.telemetry``),
which a profiler session puts on the device trace's clock::

    engine.step              args: step, engine
      engine.admit           only in a step that admits
        engine.schedule      the scheduler's ordering
        engine.prefill       one per admitted request; args: rid, tokens
          engine.prefill.launch   prompt upload and prefill launch
          engine.prefill.pull     first-token argmax and pull (waits for it)
          engine.slot_write       slot-write launch; args: state_bytes,
                                  kv_bytes (what the write moves)
      engine.decode          args: rows
        engine.decode.inputs      token and position build and uploads
        engine.decode.launch      decode launch
        engine.decode.pull        argmax and pull (waits for the decode)
        engine.decode.emit        tokens appended, slots finished

Every request carries host timestamps (``time.perf_counter_ns``), always
on: ``submit_ns``, ``admit_ns`` (its prefill starts) and one ``token_ns``
per token, taken when the token reached the host.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import resolve
from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.serving.telemetry import span, step_span


@dataclasses.dataclass
class ServeRequest:
    """RequestLike over a real token prompt: prompt/output token counts
    derive from the prompt array and decode budget unless set."""

    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int
    model: str = ""
    region: str = "local"
    tier: str = "IW-N"
    arrival: float = 0.0
    ttft_deadline: float = math.inf
    priority: int = 1
    prompt_tokens: int = 0           # 0 → len(prompt)
    output_tokens: int = 0           # 0 → max_new_tokens
    # outputs
    tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_step: Optional[int] = None
    done_step: Optional[int] = None
    submit_ns: Optional[int] = None
    admit_ns: Optional[int] = None
    token_ns: List[int] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        if not self.prompt_tokens:
            self.prompt_tokens = len(self.prompt)
        if not self.output_tokens:
            self.output_tokens = self.max_new_tokens

    @property
    def total_tokens(self) -> int:
        return self.prompt_tokens + self.output_tokens

    @property
    def deadline(self):
        return self.ttft_deadline


@dataclasses.dataclass
class _Slot:
    req: Optional[ServeRequest] = None
    pos: int = 0                      # next position to write
    remaining: int = 0


class ServingEngine:
    _ids = itertools.count()

    def __init__(self, cfg: ModelConfig, params, max_batch: int = 4,
                 max_seq: int = 512,
                 scheduler: Union[str, Callable] = "fcfs",
                 greedy: bool = True):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.order_fn = resolve("scheduler", scheduler)
        self.greedy = greedy
        self.queue: List[ServeRequest] = []
        self.slots = [_Slot() for _ in range(max_batch)]
        self.cache = model_mod.init_decode_cache(cfg, max_batch, max_seq)
        self.step_count = 0
        #: this engine's index in the process (the ``engine`` of its spans)
        self.index = next(ServingEngine._ids)
        #: device launches: one prefill per admitted request, one decode
        #: per step with an active slot
        self.prefill_count = 0
        self.decode_count = 0

        self._prefill = jax.jit(_prefill_last, static_argnums=0)
        # the decode cache is donated into every step and every slot
        # write, so one copy of it is live on the device, not two
        self._decode = jax.jit(
            lambda p, toks, cache, pos: model_mod.decode_step(
                cfg, p, toks, cache, pos), donate_argnums=(2,))
        self._write = jax.jit(_write_slot, donate_argnums=(0,))

    # ---------------------------------------------------------------- intake
    def submit(self, req: ServeRequest) -> None:
        req.submit_ns = time.perf_counter_ns()
        self.queue.append(req)

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s.req is not None)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.active > 0

    # ----------------------------------------------------------------- steps
    def _admit(self) -> None:
        free = [i for i, s in enumerate(self.slots) if s.req is None]
        if not free or not self.queue:
            return
        with span("engine.admit"):
            with span("engine.schedule"):
                self.queue = self.order_fn(self.queue,
                                           float(self.step_count))
            while free and self.queue:
                req = self.queue.pop(0)
                slot = free.pop(0)
                self._prefill_into(slot, req)

    def _prompt_batch(self, prompt: np.ndarray) -> Dict:
        batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None, :]}
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.encoder_seq, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "vlm":
            pn = min(self.cfg.num_patches, 4)
            batch["patches"] = jnp.zeros((1, pn, self.cfg.d_model),
                                         jnp.dtype(self.cfg.dtype))
        return batch

    def _prefill_into(self, slot: int, req: ServeRequest) -> None:
        S = len(req.prompt)
        with span("engine.prefill", rid=req.rid, tokens=S):
            with span("engine.prefill.launch"):
                req.admit_ns = time.perf_counter_ns()
                batch = self._prompt_batch(req.prompt)
                logits, pcache = self._prefill(self.cfg, self.params, batch)
                self.prefill_count += 1
            with span("engine.prefill.pull"):
                next_tok = int(jnp.argmax(logits[0]))
                req.token_ns.append(time.perf_counter_ns())
            offset = (batch["patches"].shape[1]
                      if self.cfg.family == "vlm" else 0)
            with span("engine.slot_write", **cache_bytes(pcache)):
                self.cache = self._write(self.cache, pcache, jnp.int32(slot))
            st = self.slots[slot]
            st.req = req
            st.pos = S + offset
            st.remaining = req.max_new_tokens - 1
            req.tokens.append(next_tok)
            req.ttft_step = self.step_count
            if st.remaining <= 0:
                self._finish(slot)

    def _finish(self, slot: int) -> None:
        st = self.slots[slot]
        st.req.done_step = self.step_count
        st.req = None
        st.pos = 0
        st.remaining = 0

    def step(self) -> None:
        """One engine iteration: admit waiting requests, decode one token
        for every active slot."""
        self.step_count += 1
        with step_span("engine.step", self.step_count, engine=self.index):
            self._admit()
            rows = self.active
            if rows:
                with span("engine.decode", rows=rows):
                    self._decode_one()

    def _decode_one(self) -> None:
        """Decode one token for every active slot."""
        with span("engine.decode.inputs"):
            toks = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch,), np.int32)
            for i, s in enumerate(self.slots):
                if s.req is not None:
                    toks[i, 0] = s.req.tokens[-1]
                    pos[i] = s.pos
            toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        with span("engine.decode.launch"):
            logits, self.cache = self._decode(self.params, toks, self.cache,
                                              pos)
            self.decode_count += 1
        with span("engine.decode.pull"):
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
            t = time.perf_counter_ns()
        with span("engine.decode.emit"):
            for i, s in enumerate(self.slots):
                if s.req is None:
                    continue
                s.req.tokens.append(int(nxt[i]))
                s.req.token_ns.append(t)
                s.pos += 1
                s.remaining -= 1
                if s.remaining <= 0 or s.pos >= self.max_seq - 1:
                    self._finish(i)

    def run(self, max_steps: int = 10_000) -> None:
        while self.has_work and self.step_count < max_steps:
            self.step()


#: cache leaves that hold a recurrent state (the rest hold KV rows)
STATE_LEAVES = ("conv", "ssm")


def cache_bytes(cache) -> Dict[str, int]:
    """Bytes of a cache tree by leaf kind: ``state_bytes`` of the
    recurrent state (SSM and conv), ``kv_bytes`` of the rest (keys,
    values, positions)."""
    out = {"state_bytes": 0, "kv_bytes": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        kind = getattr(path[-1], "key", None)
        out["state_bytes" if kind in STATE_LEAVES else "kv_bytes"] += \
            leaf.nbytes
    return out


def _prefill_last(cfg: ModelConfig, params, batch):
    """Prefill one request: (last-position logits (1, V), its cache)."""
    logits, cache, _ = model_mod.forward(cfg, params, batch,
                                         return_cache=True)
    return logits[:, -1], cache


def _write_slot(cache, prefill_cache, slot):
    """Write a single-request prefill cache into decode-cache slot `slot`.

    Decode leaves are stacked (L, B, W, ...); prefill leaves are
    (L, 1, S, ...): write at [0, slot, 0, ...].
    """
    def merge(dst, src):
        src = src.astype(dst.dtype)
        start = (0, slot) + (0,) * (dst.ndim - 2)
        return jax.lax.dynamic_update_slice(dst, src, start)

    return jax.tree.map(merge, cache, prefill_cache)
