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
      engine.decode          one per launch; args: rows (live), batch (rows
                             launched), ahead (1: the previous decode's
                             tokens were still on the device)
        engine.decode.inputs      position and fresh-token build and upload
        engine.decode.launch      decode launch
        engine.decode.pull        the previous decode's tokens (waits for it)
        engine.decode.emit        its tokens appended, its requests finished
      engine.drain           nothing left to launch: the last decode's
                             tokens pulled and emitted

The decode is a one-deep pipeline. Its program takes the greedy argmax
itself and keeps the tokens on the device: a step launches decode k, whose
input tokens are decode k-1's output, and only then pulls and emits decode
k-1's tokens, so the device runs decode k while the host emits, returns to
its caller and builds decode k+1's inputs. The host needs no token to
launch: a request stops by length alone, so which rows are live, and at
which positions, is known before the tokens are. A slot is free for
admission once its request's last decode is launched (the slot write runs
after that decode on the device: the cache is donated through both); the
request is finished (``done_step``) in the step that emits its last token,
and ``has_work`` holds while a decode's tokens are unpulled.
``decodes_ahead`` counts the decodes launched with the previous one's
tokens unpulled.

Every request carries host timestamps (``time.perf_counter_ns``), always
on: ``submit_ns``, ``admit_ns`` (its prefill starts) and one ``token_ns``
per token, taken when the token reached the host.

A decode launches a prefix of the slots: the smallest rung of
``decode_ladder(max_batch)`` that holds the highest live slot. The model
then reads and writes only those rows of the cache, so a lightly loaded
engine moves little more than the weights. Slots are filled lowest first.
Every rung's decode program is built when the engine is.
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
    remaining: int = 0                # decodes still to launch
    fresh: int = -1                   # the prefill's token, which the next
                                      # decode takes (-1: the last decode's)


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
        #: rows the decodes launched, live or dead (the ``batch`` args)
        self.decode_rows_launched = 0
        #: decodes launched while the previous decode's tokens were still
        #: on the device (the ``ahead`` args)
        self.decodes_ahead = 0
        self.ladder = decode_ladder(max_batch)
        #: the last launched decode's tokens, one row per slot, on the device
        self._tokens = jnp.zeros((max_batch,), jnp.int32)
        #: the launched decode whose tokens are not pulled yet: its token
        #: buffer and (slot, request, last token?) of each live row
        self._pending: Optional[Tuple[jax.Array, List[Tuple[
            int, ServeRequest, bool]]]] = None

        self._prefill = jax.jit(_prefill_last, static_argnums=0)
        # the decode cache is donated into every step and every slot
        # write, so one copy of it is live on the device, not two. The
        # decode is the engine's one lambda: its module, jit__lambda, is
        # how a profile tells it from the other programs
        self._decode = jax.jit(
            lambda p, toks, rows, cache: _greedy_decode(
                cfg, p, toks, rows, cache), donate_argnums=(3,))
        self._write = jax.jit(_write_slot, donate_argnums=(0,))
        self._build_decodes()

    def _build_decodes(self) -> None:
        """One decode per rung on the fresh cache, whose rows are all dead
        (an admission rewrites a slot's rows whole), so that no step builds
        or loads a program for them."""
        for b in self.ladder:
            rows = np.zeros((2, b), np.int32)
            toks, self.cache = self._decode(self.params, self._tokens,
                                            jnp.asarray(rows), self.cache)
            np.asarray(toks)

    # ---------------------------------------------------------------- intake
    def submit(self, req: ServeRequest) -> None:
        req.submit_ns = time.perf_counter_ns()
        self.queue.append(req)

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s.req is not None)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.active > 0 \
            or self._pending is not None

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
            req.tokens.append(next_tok)
            req.ttft_step = self.step_count
            if req.max_new_tokens <= 1:
                req.done_step = self.step_count
                return
            st = self.slots[slot]
            st.req = req
            st.pos = S + offset
            st.remaining = req.max_new_tokens - 1
            st.fresh = next_tok

    def step(self) -> None:
        """One engine iteration: admit waiting requests, launch a decode of
        one token for every active slot, then emit the previous decode's
        tokens; with no slot active, only emit them."""
        self.step_count += 1
        with step_span("engine.step", self.step_count, engine=self.index):
            self._admit()
            rows = self.active
            if rows:
                b = self.decode_batch()
                ahead = int(self._pending is not None)
                with span("engine.decode", rows=rows, batch=b, ahead=ahead):
                    self._decode_one(b)
            elif self._pending is not None:
                with span("engine.drain"):
                    pending, self._pending = self._pending, None
                    self._emit(*_pull(pending))

    def decode_batch(self) -> int:
        """Rows the next decode launches: the smallest rung that holds the
        highest live slot."""
        top = max(i for i, s in enumerate(self.slots) if s.req is not None)
        return next(b for b in self.ladder if b > top)

    def _decode_one(self, b: int) -> None:
        """Launch a decode of one token for every active slot, all among
        the first ``b``; then pull and emit the previous decode's tokens.
        A slot whose last decode this is frees for the next admission."""
        with span("engine.decode.inputs"):
            rows = np.zeros((2, b), np.int32)     # positions, fresh tokens
            rows[1] = -1
            launched = []
            for i, s in enumerate(self.slots[:b]):
                if s.req is None:
                    continue
                rows[:, i] = s.pos, s.fresh
                s.pos += 1
                s.remaining -= 1
                s.fresh = -1
                last = s.remaining <= 0 or s.pos >= self.max_seq - 1
                launched.append((i, s.req, last))
                if last:
                    self.slots[i] = _Slot()
            rows = jnp.asarray(rows)
        with span("engine.decode.launch"):
            self._tokens, self.cache = self._decode(
                self.params, self._tokens, rows, self.cache)
            self.decode_count += 1
            self.decode_rows_launched += b
        previous, self._pending = self._pending, (self._tokens, launched)
        if previous is None:
            return
        self.decodes_ahead += 1
        with span("engine.decode.pull"):
            pulled = _pull(previous)
        with span("engine.decode.emit"):
            self._emit(*pulled)

    def _emit(self, toks: np.ndarray, rows, t: int) -> None:
        """Append a pulled decode's tokens; finish the requests whose last
        token it made."""
        for i, req, last in rows:
            req.tokens.append(int(toks[i]))
            req.token_ns.append(t)
            if last:
                req.done_step = self.step_count

    def run(self, max_steps: int = 10_000) -> None:
        while self.has_work and self.step_count < max_steps:
            self.step()


def _pull(pending):
    """(its tokens on the host, its rows, when they arrived) of a launched
    decode (waits for it)."""
    toks, rows = pending
    return np.asarray(toks), rows, time.perf_counter_ns()


def _greedy_decode(cfg: ModelConfig, params, toks, rows, cache):
    """One greedy decode of the first ``b`` slots: (the slots' next tokens
    (max_batch,), the cache). ``toks`` (max_batch,) holds the last decode's
    tokens; ``rows`` (2, b) each launched row's position and its fresh
    token, the prefill's, which it takes in place of the last decode's
    (-1: none). Rows from ``b`` on keep their tokens."""
    pos, fresh = rows[0], rows[1]
    b = pos.shape[0]
    tok = jnp.where(fresh >= 0, fresh, toks[:b])
    logits, cache = model_mod.decode_step(cfg, params, tok[:, None], cache,
                                          pos)
    nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
    return toks.at[:b].set(nxt), cache


def decode_ladder(max_batch: int) -> List[int]:
    """The decode's batch sizes: powers of two times max(1, max_batch // 4)
    below max_batch, then max_batch (16: 4, 8, 16; 4: 1, 2, 4)."""
    rung, out = max(1, max_batch // 4), []
    while rung < max_batch:
        out.append(rung)
        rung *= 2
    return out + [max_batch]


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
