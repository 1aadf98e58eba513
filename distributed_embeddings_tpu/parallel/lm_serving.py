"""Serving a language model a session at a time: turns of generation over a
per-session cache on the device, in steps of continuous batching.

A sibling of :class:`~.serving.ServingRuntime` that shares its request and
result types (:class:`~.serving.Request`, :class:`~.serving.Served`,
:class:`~.serving.Overloaded`, :class:`~.serving.Expired`), its admission and
deadline rules, its ``serve/*`` spans and the keys of its ``stats()``, and is
driven the same way (``warmup``, ``submit``, ``poll``). What differs is what
a request is and what outlives a flush:

* a **session** is a slot of the device's cache, opened on a document that
  :meth:`SessionRuntime.prefill_document` set up once (several sessions may
  start from one document's cache, each with a copy);
* a request is a **turn** of a session (``Request.session``): prompt ids in
  ``cats[0]``, ``max_new_tokens`` to generate greedily (a fixed count, no
  early stop) and the generated positions whose logits it wants back
  (``logits_at``). Its ``n`` is ``max_new_tokens``. Its answer,
  :class:`~.serving.Served`, carries the generated ids in ``tokens`` and
  those logits in ``predictions``. A turn leaves in its session's cache its
  prompt and every generated token but the last, which no step feeds; the
  session's next turn continues after them;
* turns of one session are served in the order they were submitted: a turn
  whose session is still answering waits, and the wait is in its latency;
* **admission** counts the cache's tokens: a turn whose prompt and
  generation would overflow its session's cache, counting the turns
  already admitted to it, is refused (``Overloaded``, reason
  ``cache_full``), as is one that would put more than ``max_queue`` tokens
  to generate in the queue, the turns admitted and not yet started
  (``queue_full``). A turn still waiting when its deadline passes is
  dropped (``Expired``);
* a **step** (a flush, for ``stats()``) feeds every decoding session its
  next token and, where a prompt waits, at most ``prefill_chunk`` tokens of
  one turn's prompt (turns in submission order). The programs with and
  without a prompt chunk are both compiled by :meth:`warmup`, and the decode
  batch is every session slot (``rungs`` is ``[sessions]``), so no shape
  compiles in steady state. Each step's tokens stay on the device as the
  next step's input; the host reads a step's tokens, and its logits where a
  turn asked for them, once the next step has been dispatched, so the device
  is not left waiting on the host.

Token ids go through the :class:`~.dist_embedding.DistributedEmbedding`'s
forward lookup inside each program. The model is a module with
``prefill_layer``, ``step``, ``cache_shapes``, ``rope_table`` and
``COUNT_KEYS`` (:mod:`..models.mla_lm`).

Spans (:func:`~..utils.obs.span`): ``serve/flush`` around a :meth:`poll`
that has work (a step's dispatch and the read-back of the step before it,
as ``ServingRuntime``'s flush holds its transfer and its fetch),
``serve/step`` around each step's dispatch (args ``decode`` and
``prefill_tokens``), ``serve/prefill`` around a chunk's, ``serve/h2d``
around the transfer of a step's inputs, ``serve/fetch`` around a step's
read-back: the wait for the step to finish, then the copy.
Counters (:func:`~..utils.obs.counter_inc`): ``lm_decode_tokens``,
``lm_decode_context_tokens`` (each decoding slot's context, summed over
steps), ``lm_prefill_context_tokens`` and ``lm_prefill_key_pairs`` (a
chunk's session rows and query-key pairs) and the model's ``COUNT_KEYS``.

**The steps' timeline**, always kept, on the runtime's clock. A step
records ``t_sent``, the clock once its program call returned; its read-back
asks first whether the step is done, then waits for it and records
``t_done``. A read-back that found its step done counts in
``readback_ready``: the host came late, and the device may have idled
since. A step whose read-back waited, whose predecessor's read-back waited
too, and that was sent before the predecessor's ``t_done`` ran on the device
right after it: its device time is ``t_done`` less the predecessor's, summed
by kind (``decode_step_ms``, ``chunk_step_ms`` in :meth:`stats`: a step
that held a prompt chunk is a chunk step). A ready read-back forms no pair
with either neighbour; a :meth:`poll` that only reads back follows the same
rule.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import obs
from .serving import Expired, Overloaded, Request, Served, ServeResult

class LMServeState(NamedTuple):
    """What the programs read: the token table's slabs and the dense
    weights."""
    emb_params: Any
    dense_params: Any


@dataclasses.dataclass
class SessionConfig:
    """``sessions`` cache slots of ``capacity`` tokens each; at most
    ``prefill_chunk`` prompt tokens a step; a turn's deadline and the most
    tokens to generate that the turns waiting to start may hold."""
    sessions: int
    capacity: int
    prefill_chunk: int
    deadline_ms: float = 60_000.0
    max_queue: int = 1 << 30


@dataclasses.dataclass
class Document:
    """A document's latent cache, per layer ``(c [T, ...], pe [T, ...])``,
    as :meth:`SessionRuntime.prefill_document` leaves it."""
    caches: Any
    length: int


def _install(caches, doc, sid):
    """Every layer's cache with slot ``sid`` starting with ``doc``'s rows."""
    return [tuple(jax.lax.dynamic_update_slice(a, d[None], (sid, 0, 0))
                  for a, d in zip(pair, dpair))
            for pair, dpair in zip(caches, doc)]


@dataclasses.dataclass
class _Turn:
    req: Request
    prompt: np.ndarray
    cost: int                       # cache tokens it reserves
    off: int = 0                    # prompt tokens dispatched
    gen: int = 0                    # generated tokens dispatched
    t_start: Optional[float] = None
    t_first: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Step:
    tok: Any
    logits: Any
    counts: Any
    produced: List          # (turn, generated index) this step produces
    chunk: bool             # whether it held a prompt chunk
    t_sent: float           # the clock once its program call returned


class SessionRuntime:
    """Single-threaded, clock-injectable: the caller owns the loop
    (``submit`` + ``poll``)."""

    def __init__(self, de, model, cfg, state: LMServeState,
                 config: SessionConfig,
                 clock: Callable[[], float] = time.monotonic):
        if int(de.world_size) != 1:
            raise ValueError("SessionRuntime serves one chip's share")
        self.de, self.model, self.cfg = de, model, cfg
        self.config = config
        self.rungs = (int(config.sessions),)
        self._clock = clock
        self._state = state
        self._rope = model.rope_table(cfg, config.capacity
                                      + config.prefill_chunk)
        self._caches = None
        self._tok = jnp.zeros((config.sessions,), jnp.int32)
        s = config.sessions
        self._len = np.zeros(s, np.int64)        # tokens dispatched into it
        self._reserved = np.zeros(s, np.int64)   # ... and admitted to it
        self._open = np.zeros(s, bool)
        self._waiting: List[Deque[_Turn]] = [collections.deque()
                                             for _ in range(s)]
        self._running: List[Optional[_Turn]] = [None] * s
        self._inflight: Deque[_Step] = collections.deque()
        self._programs: Dict[int, Callable] = {}
        self._prefill_fns: Dict[bool, Callable] = {}
        self._lookup_fn = jax.jit(self._lookup)
        self._install = jax.jit(_install, donate_argnums=0)
        self._queued_samples = 0
        self._next_rid = 0
        self._warm = False
        self.warmup_compiles = 0
        self._compiles_at_steady = 0
        self._cache_peak = 0
        self._lat: List[float] = []
        self._ttft: List[float] = []
        self._tpot: List[float] = []
        self._counts = {"served": 0, "shed": 0, "deadline_missed": 0,
                        "expired": 0, "flushes": 0,
                        "served_samples": 0, "chunk_steps": 0,
                        "decode_steps": 0, "decode_slots": 0,
                        "prefill_tokens": 0, "cache_full": 0,
                        "readbacks": 0, "readback_ready": 0,
                        "decode_step_pairs": 0, "chunk_step_pairs": 0}
        self._pair_s = {"decode": 0.0, "chunk": 0.0}
        self._prev_done: Optional[float] = None   # None: the chain broke

    # --------------------------------------------------------- the state

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, value) -> None:
        """Setting ``None`` lets go of every device buffer of the runtime:
        the weights, the caches and the tokens in flight."""
        self._state = value
        if value is None:
            self._caches = None
            self._tok = None
            self._inflight.clear()

    # ----------------------------------------------------------- programs

    def _lookup(self, emb_params, ids):
        with obs.scope("embedding_forward"):
            return self.de(emb_params, [ids])[0].astype(jnp.float32)

    def _prefill_fn(self, dense: bool) -> Callable:
        """Layer ``l``'s prefill over a document, compiled once for the dense
        layers and once for the expert layers."""
        fn = self._prefill_fns.get(dense)
        if fn is None:
            l = 0 if dense else self.cfg.num_dense_layers
            fn = jax.jit(lambda x, layer, cs: self.model.prefill_layer(
                x, layer, self.cfg, l, cs), donate_argnums=0)
            self._prefill_fns[dense] = fn
        return fn

    def _program(self, chunk: int) -> Callable:
        """``(state, caches, tok [S], packed, rope) -> (caches, tok [S],
        logits [S, V], counts)``: one step with a prompt chunk of ``chunk``
        tokens (0: none). ``packed`` int32: ``active [S]``, ``pos [S]``,
        then ``chunk`` ids and ``(session, start, valid)``."""
        prog = self._programs.get(chunk)
        if prog is not None:
            return prog
        s, cfg, model = self.config.sessions, self.cfg, self.model

        def step(state, caches, tok, packed, rope):
            active, pos = packed[:s] > 0, packed[s:2 * s]
            ids, positions, meta = tok, pos, None
            if chunk:
                meta = (packed[2 * s + chunk], packed[2 * s + chunk + 1],
                        packed[2 * s + chunk + 2])
                ids = jnp.concatenate([tok, packed[2 * s:2 * s + chunk]])
                positions = jnp.concatenate(
                    [pos, meta[1] + jnp.arange(chunk, dtype=jnp.int32)])
            cs = jnp.take(rope, positions, axis=0, mode="clip")
            x = self._lookup(state.emb_params, ids)
            caches, logits, counts = model.step(
                state.dense_params, x, caches, pos, active, meta, cfg, cs)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return caches, nxt, logits, counts

        prog = jax.jit(step, donate_argnums=1)
        self._programs[chunk] = prog
        return prog

    # -------------------------------------------------------- documents

    def prefill_document(self, tokens: np.ndarray) -> Document:
        """A document's latent cache from scratch (decompressed attention),
        for :meth:`open_session`."""
        t = int(len(tokens))
        params = self._state.dense_params
        x = self._lookup_fn(self._state.emb_params,
                            jnp.asarray(tokens, jnp.int32))
        cs = self._rope[:t]
        caches = []
        for l, layer in enumerate(params["layers"]):
            x, c, pe = self._prefill_fn(l < self.cfg.num_dense_layers)(
                x, layer, cs)
            caches.append((c, pe))
        del x
        return Document(caches=caches, length=t)

    def _new_caches(self):
        return [tuple(jnp.zeros(sh, jnp.bfloat16) for sh in pair)
                for pair in self.model.cache_shapes(
                    self.cfg, self.config.sessions, self.config.capacity)]

    def open_session(self, session: int, doc: Document) -> None:
        """Start session ``session`` from a copy of ``doc``'s cache."""
        if doc.length > self.config.capacity:
            raise ValueError(f"a document of {doc.length} tokens does not "
                             f"fit a session of {self.config.capacity}")
        if self._caches is None:
            self._caches = self._new_caches()
        self._caches = self._install(self._caches, doc.caches,
                                     jnp.int32(session))
        self._len[session] = self._reserved[session] = doc.length
        self._open[session] = True

    # ------------------------------------------------------------ serving

    def warmup(self, template=None) -> int:
        """Compile both step programs (every slot idle; the chunk's rows land
        past a session's length, where nothing reads them before they are
        written) and count the compiles. ``template`` is not needed: every
        shape is the runtime's own."""
        del template
        obs.install_compile_listener()
        if self._caches is None:
            self._caches = self._new_caches()
        before = obs.counters().get("recompiles", 0)
        room = np.flatnonzero(self._len + self.config.prefill_chunk
                              <= self.config.capacity)
        sid = int(room[0]) if len(room) else 0
        for chunk in (0, self.config.prefill_chunk):
            packed = self._packed(np.zeros(self.config.sessions, bool),
                                  chunk, (sid, int(self._len[sid]), 0,
                                          np.zeros(chunk, np.int32)))
            self._caches, tok, logits, _ = self._program(chunk)(
                self._state, self._caches, self._tok, jax.device_put(packed),
                self._rope)
            np.asarray(logits)
        self.warmup_compiles = obs.counters().get("recompiles", 0) - before
        self._compiles_at_steady = obs.counters().get("recompiles", 0)
        self._warm = True
        return self.warmup_compiles

    def steady_recompiles(self) -> int:
        if not self._warm:
            return 0
        return obs.counters().get("recompiles", 0) - self._compiles_at_steady

    @property
    def queued_samples(self) -> int:
        """Tokens to generate of the turns admitted and not yet started."""
        return self._queued_samples

    def _chunked(self, prompt_len: int) -> int:
        c = self.config.prefill_chunk
        return -(-prompt_len // c) * c

    def submit(self, req: Request,
               now: Optional[float] = None) -> Optional[Overloaded]:
        """Admit one turn: ``None`` (queued) or a typed ``Overloaded``."""
        with obs.span("serve/submit"):
            now = self._clock() if now is None else now
            s = req.session
            if s is None or not 0 <= s < self.config.sessions \
                    or not self._open[s]:
                raise ValueError(f"turn for session {s!r}, which is not open")
            prompt = np.asarray(req.cats[0], np.int32).reshape(-1)
            g = int(req.max_new_tokens)
            if not len(prompt) or g < 1:
                raise ValueError("a turn needs a prompt and a token to "
                                 "generate")
            if any(not 0 <= j < g for j in req.logits_at):
                raise ValueError(f"logits_at {tuple(req.logits_at)} outside "
                                 f"the {g} generated positions")
            req.n, req.rid, req.t_submit = g, self._next_rid, now
            self._next_rid += 1
            dl = req.deadline_ms if req.deadline_ms is not None \
                else self.config.deadline_ms
            req.deadline_ms, req.deadline = float(dl), now + dl / 1e3
            cost = len(prompt) + g - 1
            room = self.config.capacity - self._reserved[s]
            reason = None
            if self._queued_samples + g > self.config.max_queue:
                reason = "queue_full"
            elif max(cost, self._chunked(len(prompt))) > room:
                reason = "cache_full"
                self._counts["cache_full"] += 1
            if reason is not None:
                self._counts["shed"] += 1
                obs.counter_inc("serve_shed")
                return Overloaded(rid=req.rid, latency_ms=0.0, reason=reason,
                                  queue_samples=self._queued_samples,
                                  spans={"queue_wait_ms": 0.0})
            self._reserved[s] += cost
            self._queued_samples += g
            self._waiting[s].append(_Turn(req=req, prompt=prompt, cost=cost))
            return None

    def _packed(self, active, chunk: int, meta) -> np.ndarray:
        s = self.config.sessions
        out = np.zeros(2 * s + (chunk + 3 if chunk else 0), np.int32)
        out[:s] = active
        out[s:2 * s] = self._len
        if chunk:
            sid, start, valid, ids = meta
            out[2 * s:2 * s + len(ids)] = ids
            out[2 * s + chunk:] = (sid, start, valid)
        return out

    def _expire(self, t: float, out: List[ServeResult]) -> None:
        for s, q in enumerate(self._waiting):
            keep = collections.deque()
            for turn in q:
                r = turn.req
                if r.deadline < t:
                    self._reserved[s] -= turn.cost
                    self._queued_samples -= r.n
                    self._counts["expired"] += 1
                    self._counts["deadline_missed"] += 1
                    obs.counter_inc("serve_deadline_missed")
                    lat = (t - r.t_submit) * 1e3
                    out.append(Expired(rid=r.rid, latency_ms=lat,
                                       deadline_ms=r.deadline_ms,
                                       spans={"queue_wait_ms": lat}))
                else:
                    keep.append(turn)
            self._waiting[s] = keep

    def _dispatch(self, t: float) -> bool:
        """Start the turns whose sessions are free, then dispatch one step if
        there is work. Returns whether a step was dispatched."""
        for s in range(self.config.sessions):
            if self._running[s] is None and self._waiting[s]:
                self._running[s] = self._waiting[s].popleft()
                self._queued_samples -= self._running[s].req.n
        g_of = lambda turn: turn.req.max_new_tokens  # noqa: E731
        decoding = [s for s, turn in enumerate(self._running)
                    if turn is not None and turn.off == len(turn.prompt)
                    and 0 < turn.gen < g_of(turn)]
        filling = [turn for turn in self._running
                   if turn is not None and turn.off < len(turn.prompt)]
        if not decoding and not filling:
            return False
        active = np.zeros(self.config.sessions, bool)
        active[decoding] = True
        produced = [(self._running[s], self._running[s].gen)
                    for s in decoding]
        ctx_tokens = int(sum(self._len[s] + 1 for s in decoding))
        chunk, ft, meta = 0, None, None
        if filling:
            chunk = self.config.prefill_chunk
            ft = min(filling, key=lambda tr: tr.req.rid)
            sid = ft.req.session
            ids = ft.prompt[ft.off:ft.off + chunk]
            meta = (sid, int(self._len[sid]), len(ids), ids)
            if ft.t_start is None:
                ft.t_start = t
        packed = self._packed(active, chunk, meta)
        with obs.span("serve/step", decode=len(decoding),
                      prefill_tokens=meta[2] if meta else 0):
            with obs.span("serve/h2d"):
                dev = jax.device_put(packed)
            with obs.span("serve/prefill" if ft else "serve/dispatch"):
                self._caches, self._tok, logits, counts = self._program(
                    chunk)(self._state, self._caches, self._tok, dev,
                           self._rope)
        t_sent = self._clock()
        for s in decoding:
            self._len[s] += 1
            self._running[s].gen += 1
        if ft is not None:
            ft.off += meta[2]
            self._len[sid] += meta[2]
            self._counts["chunk_steps"] += 1
            self._counts["prefill_tokens"] += meta[2]
            # what the chunk's attention reads and computes: its session's
            # rows up to its last query, and a query-key pair a key each of
            # its queries sees
            start, valid = meta[1], meta[2]
            obs.counter_inc("lm_prefill_context_tokens", start + valid)
            obs.counter_inc("lm_prefill_key_pairs",
                            valid * start + valid * (valid + 1) // 2)
            if ft.off == len(ft.prompt):
                produced.append((ft, 0))
                ft.gen = 1
        if decoding:
            self._counts["decode_steps"] += 1
            self._counts["decode_slots"] += len(decoding)
            obs.counter_inc("lm_decode_tokens", len(decoding))
            obs.counter_inc("lm_decode_context_tokens", ctx_tokens)
        self._counts["flushes"] += 1
        self._cache_peak = max(self._cache_peak, int(self._len.sum()))
        # a turn whose every step is out frees its session for the next turn
        for s, turn in enumerate(self._running):
            if turn is not None and turn.gen >= g_of(turn):
                self._running[s] = None
        self._inflight.append(_Step(tok=self._tok, logits=logits,
                                    counts=counts, produced=produced,
                                    chunk=ft is not None, t_sent=t_sent))
        return True

    @staticmethod
    def _await(st: _Step) -> bool:
        """Whether step ``st`` was done when asked; returns once it is."""
        ready = st.tok.is_ready()
        jax.block_until_ready(st.tok)
        return ready

    def _time_step(self, st: _Step, ready: bool, t_done: float) -> None:
        """Count a read-back, and a step's device time where it pairs with
        its predecessor's (the module's docstring has the rule)."""
        c = self._counts
        c["readbacks"] += 1
        prev, self._prev_done = self._prev_done, None if ready else t_done
        if ready:
            c["readback_ready"] += 1
        elif prev is not None and st.t_sent < prev:
            kind = "chunk" if st.chunk else "decode"
            c[kind + "_step_pairs"] += 1
            self._pair_s[kind] += t_done - prev

    def _retire(self, out: List[ServeResult]) -> None:
        """Read the oldest step in flight back and answer the turns it
        finished."""
        st = self._inflight.popleft()
        want = any(j in turn.req.logits_at for turn, j in st.produced)
        with obs.span("serve/fetch"):
            ready = self._await(st)
            t_done = self._clock()
            tok, counts = jax.device_get((st.tok, st.counts))  # host-ok: the runtime's read-back, one step behind the device
            logits = np.asarray(st.logits) if want else None
        self._time_step(st, ready, t_done)
        for k, v in zip(self.model.COUNT_KEYS, np.asarray(counts)):
            obs.counter_inc(k, int(v))
        t = self._clock()
        for turn, j in st.produced:
            r = turn.req
            s = r.session
            turn.tokens.append(int(tok[s]))
            if j in r.logits_at:
                turn.logits[j] = logits[s]
            if j == 0:
                turn.t_first = t
                self._ttft.append((t - r.t_submit) * 1e3)
            if len(turn.tokens) == r.max_new_tokens:
                out.append(self._answer(turn, t))

    def _answer(self, turn: _Turn, t_done: float) -> Served:
        r = turn.req
        preds = np.stack([turn.logits[j] for j in r.logits_at]) \
            if r.logits_at else None
        t1 = self._clock()
        lat = (t1 - r.t_submit) * 1e3
        spans = {"queue_wait_ms": (turn.t_start - r.t_submit) * 1e3,
                 "coalesce_ms": (turn.t_first - turn.t_start) * 1e3,
                 "decode_ms": (t_done - turn.t_first) * 1e3,
                 "reply_ms": (t1 - t_done) * 1e3}
        self._lat.append(lat)
        if r.max_new_tokens > 1:
            # the wait for each token after the first
            self._tpot.append(spans["decode_ms"] / (r.max_new_tokens - 1))
        missed = t1 > r.deadline
        self._counts["served"] += 1
        self._counts["served_samples"] += r.n
        obs.counter_inc("serve_served")
        if missed:
            self._counts["deadline_missed"] += 1
            obs.counter_inc("serve_deadline_missed")
        return Served(rid=r.rid, latency_ms=lat, predictions=preds,
                      rung=self.rungs[0], deadline_missed=missed, spans=spans,
                      tokens=np.asarray(turn.tokens, np.int32))

    def poll(self, now: Optional[float] = None) -> List[ServeResult]:
        """Expire the turns whose deadline passed, dispatch one step if there
        is work, then read back every step but the one just dispatched (all
        of them when none was). Returns the answered turns."""
        out: List[ServeResult] = []
        t = self._clock() if now is None else now
        self._expire(t, out)
        if not (self._inflight or any(self._waiting)
                or any(turn is not None for turn in self._running)):
            return out
        with obs.span("serve/flush", flush=self._counts["flushes"]):
            sent = self._dispatch(t)
            while len(self._inflight) > int(sent):
                self._retire(out)
        return out

    # -------------------------------------------------------------- stats

    def stats(self) -> Dict[str, Any]:
        c = self._counts
        lat = np.asarray(self._lat) if self._lat else None
        slots = c["flushes"] * self.config.sessions
        pct = (lambda q: float(np.percentile(lat, q))) if lat is not None \
            else (lambda q: None)
        p50 = lambda xs: float(np.percentile(xs, 50)) if xs else None  # noqa: E731
        step_ms = {k: (1e3 * s / c[k + "_step_pairs"]
                       if c[k + "_step_pairs"] else None)
                   for k, s in self._pair_s.items()}
        return {
            **c,
            "steps": c["flushes"],
            "queued_samples": self._queued_samples,
            "pad_fraction": 1.0 - c["decode_slots"] / slots if slots else 0.0,
            "decode_batch_mean": (c["decode_slots"] / c["decode_steps"]
                                  if c["decode_steps"] else 0.0),
            "latency_p50_ms": pct(50), "latency_p95_ms": pct(95),
            "latency_p99_ms": pct(99),
            "ttft_p50_ms": p50(self._ttft),
            "tpot_p50_ms": p50(self._tpot),
            "readback_ready_share": (100.0 * c["readback_ready"]
                                     / c["readbacks"]
                                     if c["readbacks"] else None),
            "decode_step_ms": step_ms["decode"],
            "chunk_step_ms": step_ms["chunk"],
            "cache_tokens_peak": self._cache_peak,
            "warmup_compiles": self.warmup_compiles,
            "steady_state_recompiles": self.steady_recompiles(),
        }
