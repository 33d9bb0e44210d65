"""Recommendation-as-a-service: a concurrent HTTP serving tier over the
predictor (the ROADMAP's "millions of users" query path).

The loop/fleet (``loop.py``/``fleet.py``) keep the model fresh; this module
answers queries about it, many clients at a time, from one long-running
stdlib-only process (``http.server.ThreadingHTTPServer`` — no new deps):

- ``POST /predict``    — predicted MB/s for one (context, config) pair
- ``POST /recommend``  — ranked top-k configs for a workload context
- ``GET  /explain``    — fitted-model feature importances + knob grid
- ``GET  /healthz``    — liveness, fitted flag, model generation
- ``GET  /stats``      — request/batch/cache counters + loop cycle log

Core mechanics, in the order a request meets them:

1. **Response cache** — a bounded LRU keyed by (endpoint, model generation,
   order-insensitive context hash).  The generation in the key is what makes
   refit invalidation *atomic*: the instant a refit publishes, lookups move
   to the new generation and every stale entry becomes unreachable.
2. **Micro-batching** — cache misses enqueue into a collector that drains
   whatever is concurrently queued (up to ``max_batch``, optionally waiting
   ``batch_window_ms``) and scores the whole batch against ONE model
   snapshot: predict rows stack into a single vectorized
   ``predict_throughput_batch`` call (amortizing per-call dispatch ~10x for
   the paper GBT), and recommend requests sharing a context hash collapse
   into a single cached-grid scoring.  Serializing scoring through one
   worker is also what lets it reuse the ``ConfigSpace`` cached feature
   matrix zero-copy — the unbatched mode must serialize on a lock instead.
3. **Hot swap** — ``OnlineAutotuner.maybe_refit`` builds the new model off
   to the side and publishes (model, generation) in one atomic swap;
   ``snapshot()`` pins that pair per batch, so a response can never mix
   model generations and in-flight batches finish on the model they started
   with.  The embedded continuous loop (``--loop``) drives refits in a
   background thread while requests are served.

Responses are canonical JSON (sorted keys, fixed separators) and scoring is
per-row deterministic for the tree models, so N concurrent batched requests
return byte-identical bodies to N serial ones — asserted by
``tests/test_serve.py``; load numbers live in ``BENCH_serve.json``
(``benchmarks/serve_bench.py``).

CLI::

    python -m repro.service.serve --smoke                   # self-test
    python -m repro.service.serve --warm-from merged.jsonl  # frozen model
    python -m repro.service.serve --loop --fast --cycles 6  # serve + tune
    python -m repro.service.serve --status                  # loop audit log

See ``docs/serving.md``.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import http.server
import json
import os
import pathlib
import queue
import sys
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..compile_cache import use_compile_cache
from ..core.autotune import KNOB_NAMES, ConfigSpace, OnlineAutotuner, recommend
from ..core.ensemble_base import ceil_pow2
from ..core.features import TARGET_NAME
from ._cli import add_chaos_args, add_serve_args, add_tuning_args, \
    chaos_plan_from_args
from .state import LoopState

__all__ = [
    "ServeConfig",
    "RecommendationService",
    "ResponseCache",
    "MicroBatcher",
    "context_key",
    "main",
    "DEFAULT_SERVE_DIR",
]

DEFAULT_SERVE_DIR = pathlib.Path("/tmp/repro_io/serve")


def _json_bytes(obj) -> bytes:
    """Canonical response encoding: key order and separators are fixed so the
    same result is the same bytes — the batched-vs-sequential equivalence
    and cache-hit-vs-cold tests compare raw bodies."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":")).encode()


def context_key(mapping: Optional[dict]) -> tuple:
    """Order-insensitive canonical key for a context/knob dict.

    ``{"a": 1, "b": 2}`` and ``{"b": 2, "a": 1}`` hash identically; numeric
    values are canonicalized through ``float`` so ``1`` and ``1.0`` (JSON
    clients disagree about this constantly) share a cache line."""
    if not mapping:
        return ()
    items = []
    for k, v in mapping.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            items.append((str(k), repr(v)))
        else:
            items.append((str(k), float(v)))
    return tuple(sorted(items))


class ResponseCache:
    """Bounded, thread-safe LRU for serialized response bodies.

    Keys embed the model generation (see ``RecommendationService._cache_key``)
    — a refit makes every previous generation's entries unreachable in the
    same atomic swap that publishes the new model, so a stale response can
    never be served after the swap completes.  The LRU bound then evicts the
    dead generation's bytes as fresh traffic arrives."""

    def __init__(self, capacity: int = 1024):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._data: "OrderedDict[tuple, bytes]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple) -> Optional[bytes]:
        with self._lock:
            body = self._data.get(key)
            if body is None:
                self.misses += 1
                return None
            self._data.move_to_end(key)
            self.hits += 1
            return body

    def put(self, key: tuple, body: bytes) -> None:
        if self.capacity <= 0:
            return
        with self._lock:
            self._data[key] = body
            self._data.move_to_end(key)
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)


class _Pending:
    """One enqueued request: inputs pre-featurized on the handler thread,
    result delivered through an event by the scorer."""

    __slots__ = ("kind", "ctx_key", "row", "filtered", "top_k", "event",
                 "status", "body", "deadline")

    def __init__(self, kind: str, ctx_key: tuple, row=None, filtered=None,
                 top_k: int = 0):
        self.kind = kind
        self.ctx_key = ctx_key
        self.row = row              # predict: [F] feature row
        self.filtered = filtered    # recommend: filtered context dict
        self.top_k = top_k
        self.event = threading.Event()
        self.status = 500
        self.body = b'{"error":"internal"}'
        self.deadline = None        # monotonic budget set by _serve_scored

    def finish(self, status: int, body: bytes) -> None:
        self.status = status
        self.body = body
        self.event.set()


_STOP = object()


class MicroBatcher:
    """Coalesces concurrent requests into single vectorized scoring calls.

    The worker takes the first queued request, drains whatever else is
    already waiting (optionally holding the door open ``window_s``), and
    hands the whole batch to ``score_fn`` — which scores it against exactly
    one model snapshot.  Under load, requests pile up while the worker
    scores, so batches form naturally without adding idle latency.

    ``stop()`` drains: everything submitted before the close wins a result
    before the worker exits (the graceful-shutdown guarantee).

    The queue is **bounded** (``max_queue``): past that depth the service is
    not keeping up, and letting the backlog grow only converts overload into
    unbounded client latency and coordinator memory.  ``submit`` raises
    ``queue.Full`` instead of enqueueing — the caller sheds the request with
    a 503 + ``Retry-After`` so clients back off (``docs/robustness.md``).
    ``max_queue=0`` disables the bound."""

    def __init__(self, score_fn, max_batch: int = 64, window_s: float = 0.0,
                 max_queue: int = 1024):
        self._score_fn = score_fn
        self.max_batch = max(1, int(max_batch))
        self.window_s = float(window_s)
        self.max_queue = max(0, int(max_queue))
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._closed = False
        self.n_batches = 0
        self.n_scored = 0
        self.max_batch_seen = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serve-batcher")
        self._thread.start()

    def submit(self, pending: _Pending) -> bool:
        """Enqueue for scoring.  False = closed (shutting down); raises
        ``queue.Full`` when the admission bound is hit (caller sheds)."""
        with self._lock:
            if self._closed:
                return False
            if self.max_queue and self._q.qsize() >= self.max_queue:
                raise queue.Full
            self._q.put(pending)
            return True

    @property
    def depth(self) -> int:
        """Approximate queued-request count (admission/stats reporting)."""
        return self._q.qsize()

    def _collect(self, first) -> Tuple[List[_Pending], bool]:
        batch = [first]
        saw_stop = False
        deadline = time.monotonic() + self.window_s
        while len(batch) < self.max_batch:
            try:
                if self.window_s > 0:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    item = self._q.get(timeout=remaining)
                else:
                    item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is _STOP:
                saw_stop = True
                break
            batch.append(item)
        return batch, saw_stop

    def _score(self, batch: List[_Pending]) -> None:
        self.n_batches += 1
        self.n_scored += len(batch)
        self.max_batch_seen = max(self.max_batch_seen, len(batch))
        try:
            self._score_fn(batch)
        except Exception as e:  # noqa: BLE001 — a scoring bug must not hang clients
            body = _json_bytes({"error": f"{type(e).__name__}: {e}"})
            for p in batch:
                if not p.event.is_set():
                    p.finish(500, body)

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is _STOP:
                break
            batch, saw_stop = self._collect(item)
            self._score(batch)
            if saw_stop:
                break
        # drain everything enqueued before the close (FIFO: all real items
        # precede the sentinel, so nothing submitted successfully is lost)
        leftover: List[_Pending] = []
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not _STOP:
                leftover.append(item)
        for i in range(0, len(leftover), self.max_batch):
            self._score(leftover[i:i + self.max_batch])

    def stop(self) -> None:
        """Close to new submissions, drain the queue, join the worker."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._q.put(_STOP)
        self._thread.join()

    @property
    def mean_batch(self) -> float:
        return self.n_scored / self.n_batches if self.n_batches else 0.0


@dataclasses.dataclass
class ServeConfig:
    """Serving-tier knobs (CLI flags mirror these; see ``add_serve_args``)."""

    host: str = "127.0.0.1"
    port: int = 0                 # 0 = OS-assigned ephemeral port
    batching: bool = True         # False: score inline per request (baseline)
    max_batch: int = 64
    batch_window_ms: float = 0.0  # >0: hold the batch open for stragglers
    max_queue: int = 1024         # admission bound; past it requests shed 503
    deadline_ms: float = 60000.0  # per-request queue+scoring budget -> 504
    cache_size: int = 1024        # 0 disables the response cache
    top_k: int = 5                # default /recommend depth
    out_dir: Optional[pathlib.Path] = None  # serve_info.json + loop state home

    def __post_init__(self):
        if self.out_dir is not None:
            self.out_dir = pathlib.Path(self.out_dir)


class RecommendationService:
    """The serving tier: HTTP front, cache, micro-batcher, model hot-swap.

    ``tuner`` is the live model source (its ``snapshot()``/``generation`` are
    the swap point); pass ``loop`` (a ``ContinuousTuningLoop`` sharing that
    tuner) to drive collect→refit cycles in a background thread while
    serving.  ``handle()`` is a pure (method, path, body) → (status, bytes)
    function, so the routing/scoring logic is testable without sockets."""

    def __init__(
        self,
        tuner: OnlineAutotuner,
        cfg: Optional[ServeConfig] = None,
        loop=None,
        progress=None,
    ):
        self.cfg = cfg or ServeConfig()
        self.tuner = tuner
        self.loop = loop
        if loop is not None and loop.tuner is not tuner:
            raise ValueError("loop and service must share one OnlineAutotuner "
                             "(pass loop.tuner as tuner)")
        self._progress = progress
        self.cache = ResponseCache(self.cfg.cache_size)
        # Private grid: scoring rewrites the cached feature matrix's context
        # columns in place, and the embedded loop's own ranked() call uses
        # tuner.space concurrently — each side gets its own cache.
        self.space = ConfigSpace(
            **{k: getattr(tuner.space, k) for k in KNOB_NAMES})
        self._score_lock = threading.Lock()
        self._batcher: Optional[MicroBatcher] = None
        self._httpd: Optional[http.server.ThreadingHTTPServer] = None
        self._http_thread: Optional[threading.Thread] = None
        self._loop_thread: Optional[threading.Thread] = None
        self.loop_error: Optional[str] = None
        self._draining = False
        self._started = 0.0
        self._counter_lock = threading.Lock()
        self._requests: Dict[str, int] = {}
        self._errors = 0
        self._shed = 0       # 503s from the admission bound (queue full)
        self._timeouts = 0   # 504s from the per-request deadline budget
        self._active = 0
        self._idle = threading.Condition(self._counter_lock)

    # -- lifecycle ------------------------------------------------------
    def _log(self, msg: str) -> None:
        if self._progress is not None:
            self._progress(msg)

    def start(self) -> None:
        """Bind the port, start the batcher, the HTTP thread, and (if
        configured) the embedded tuning-loop thread."""
        self._started = time.time()
        if self.cfg.batching:
            self._batcher = MicroBatcher(
                self._score_batch, max_batch=self.cfg.max_batch,
                window_s=self.cfg.batch_window_ms / 1e3,
                max_queue=self.cfg.max_queue)
        handler = _make_handler(self)
        self._httpd = _Server((self.cfg.host, self.cfg.port), handler)
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.05},
            daemon=True, name="serve-http")
        self._http_thread.start()
        if self.loop is not None:
            self._loop_thread = threading.Thread(
                target=self._run_loop, daemon=True, name="serve-loop")
            self._loop_thread.start()
        if self.cfg.out_dir is not None:
            self.cfg.out_dir.mkdir(parents=True, exist_ok=True)
            (self.cfg.out_dir / "serve_info.json").write_text(json.dumps({
                "host": self.cfg.host, "port": self.port, "pid": os.getpid(),
            }) + "\n")
        self._log(f"listening on http://{self.cfg.host}:{self.port} "
                  f"(batching={self.cfg.batching}, cache={self.cfg.cache_size})")

    def _run_loop(self) -> None:
        try:
            self.loop.run()
            self._log("embedded loop: all cycles complete")
        except Exception as e:  # noqa: BLE001 — serving outlives a loop crash
            self.loop_error = f"{type(e).__name__}: {e}"
            self._log(f"embedded loop failed: {self.loop_error}")

    @property
    def port(self) -> int:
        assert self._httpd is not None, "start() first"
        return self._httpd.server_address[1]

    def shutdown(self, timeout: float = 10.0) -> None:
        """Graceful: stop accepting, drain queued + in-flight requests (each
        gets its response), then close the socket."""
        self._draining = True
        if self._httpd is not None:
            self._httpd.shutdown()  # stop the accept loop
        if self._batcher is not None:
            self._batcher.stop()  # score everything already queued
        deadline = time.monotonic() + timeout
        with self._idle:
            while self._active > 0 and time.monotonic() < deadline:
                self._idle.wait(timeout=0.1)
        if self._httpd is not None:
            self._httpd.server_close()

    # -- scoring --------------------------------------------------------
    def _snapshot(self):
        return self.tuner.snapshot()

    def _predict_pending(self, context: dict, config: dict) -> _Pending:
        feats = self.tuner.filter_context(context, knobs=config)
        return _Pending(
            "predict",
            ctx_key=(context_key(context), context_key(config)),
            row=self.tuner.spec.row(feats),
        )

    def _recommend_pending(self, context: dict, top_k: int) -> _Pending:
        return _Pending(
            "recommend",
            ctx_key=(context_key(context),),
            filtered=self.tuner.filter_context(context),
            top_k=top_k,
        )

    def _score_batch(self, batch: List[_Pending]) -> None:
        """Score one micro-batch against ONE model snapshot.

        All responses of a batch carry the same ``model_generation`` — a
        refit landing mid-batch affects only later batches (the snapshot
        pins the model; see ``PredictorSnapshot``).  Predict rows become one
        stacked ``predict_throughput_batch`` call; recommend requests with
        equal context hashes share one grid scoring."""
        with self._score_lock:
            snap = self._snapshot()
            if snap is None:
                body = _json_bytes({"error": "model not fitted yet",
                                    "model_generation": 0})
                for p in batch:
                    p.finish(503, body)
                return
            predicts = [p for p in batch if p.kind == "predict"]
            recs = [p for p in batch if p.kind == "recommend"]
            if predicts:
                X = np.stack([p.row for p in predicts])
                # pad to power-of-two row counts: the tree ensembles re-jit
                # per input shape, and free-form batch sizes would recompile
                # (hundreds of ms) on nearly every batch under load; buckets
                # bound the shape set to log2(max_batch).  Per-row outputs
                # are independent, so padding never changes a real row.
                bucket = ceil_pow2(len(predicts))
                if bucket != len(predicts):
                    X = np.concatenate(
                        [X, np.repeat(X[-1:], bucket - len(predicts), axis=0)])
                vals = snap.predict_throughput_batch(X)[: len(predicts)]
                for p, v in zip(predicts, vals):
                    p.finish(200, _json_bytes({
                        "model_generation": snap.generation,
                        "predicted_throughput_mb_s": float(v),
                    }))
            groups: Dict[tuple, List[_Pending]] = {}
            for p in recs:
                groups.setdefault(p.ctx_key + (p.top_k,), []).append(p)
            for group in groups.values():
                lead = group[0]
                top = recommend(snap, lead.filtered, self.space,
                                top_k=lead.top_k)
                body = _json_bytes({"model_generation": snap.generation,
                                    "top": top})
                for p in group:
                    p.finish(200, body)

    def _dispatch(self, pending: _Pending) -> None:
        """Batched mode: admit (or shed), enqueue, and wait out the request's
        remaining deadline budget; unbatched: score inline (still serialized —
        the grid cache is shared scorer state either way)."""
        if self._batcher is not None:
            try:
                admitted = self._batcher.submit(pending)
            except queue.Full:
                # overload: shed instead of queueing unboundedly — clients
                # retry after backoff (Retry-After is set by the HTTP layer)
                with self._counter_lock:
                    self._shed += 1
                pending.finish(503, _json_bytes(
                    {"error": "overloaded: scoring queue full",
                     "retry_after_s": 1}))
                return
            if not admitted:
                pending.finish(503, _json_bytes({"error": "shutting down",
                                                 "retry_after_s": 1}))
                return
            budget = (pending.deadline - time.monotonic()
                      if pending.deadline is not None
                      else self.cfg.deadline_ms / 1e3)
            if not pending.event.wait(timeout=max(0.0, budget)):
                with self._counter_lock:
                    self._timeouts += 1
                pending.finish(504, _json_bytes(
                    {"error": "deadline exceeded before scoring finished"}))
            return
        self._score_batch([pending])

    # -- endpoints ------------------------------------------------------
    def _cache_key(self, endpoint: str, pending: _Pending) -> tuple:
        return (endpoint, self.tuner.generation, pending.top_k) + pending.ctx_key

    def _serve_scored(self, endpoint: str, pending: _Pending) -> Tuple[int, bytes]:
        key = self._cache_key(endpoint, pending)
        if self.cfg.cache_size > 0:
            body = self.cache.get(key)
            if body is not None:
                return 200, body
        if self.cfg.deadline_ms > 0:
            pending.deadline = time.monotonic() + self.cfg.deadline_ms / 1e3
        self._dispatch(pending)
        if pending.status == 200 and self.cfg.cache_size > 0:
            # re-derive the key from the response's generation: a swap racing
            # this request must not file a new-model response under the old
            # generation (the reverse — old result under new key — cannot
            # happen: the snapshot is taken after the lookup's generation read)
            gen = json.loads(pending.body)["model_generation"]
            self.cache.put((endpoint, gen, pending.top_k) + pending.ctx_key,
                           pending.body)
        return pending.status, pending.body

    def _predict(self, payload: dict) -> Tuple[int, bytes]:
        context = payload.get("context", {})
        config = payload.get("config", {})
        if not isinstance(context, dict) or not isinstance(config, dict):
            return 400, _json_bytes({"error": "context/config must be objects"})
        return self._serve_scored("predict", self._predict_pending(context, config))

    def _recommend(self, payload: dict) -> Tuple[int, bytes]:
        context = payload.get("context", {})
        if not isinstance(context, dict):
            return 400, _json_bytes({"error": "context must be an object"})
        top_k = payload.get("top_k", self.cfg.top_k)
        if not isinstance(top_k, int) or top_k < 1:
            return 400, _json_bytes({"error": "top_k must be a positive integer"})
        return self._serve_scored("recommend", self._recommend_pending(context, top_k))

    def _explain(self) -> Tuple[int, bytes]:
        snap = self._snapshot()
        if snap is None:
            return 503, _json_bytes({"error": "model not fitted yet",
                                     "model_generation": 0})
        imp = snap.feature_importances_
        names = list(snap.spec.names)
        features = [
            {"name": n,
             "importance": (float(imp[i]) if imp is not None else None)}
            for i, n in enumerate(names)
        ]
        return 200, _json_bytes({
            "model": snap.model_name,
            "model_generation": snap.generation,
            "n_observations": self.tuner.n_observations,
            "features": features,
            "knobs": {k: list(getattr(self.space, k)) for k in KNOB_NAMES},
        })

    def _healthz(self) -> Tuple[int, bytes]:
        """Liveness + circuit state.  Always 200 (the process is serving);
        ``status`` degrades to "degraded" when the embedded loop thread died
        on an error or the model was rolled back to its previous generation —
        the service still answers, but its freshness pipeline is broken and
        an operator/orchestrator should look (``docs/robustness.md``)."""
        loop_dead = (self._loop_thread is not None
                     and not self._loop_thread.is_alive()
                     and self.loop_error is not None)
        degraded = loop_dead or bool(getattr(self.tuner, "degraded", False))
        status = ("draining" if self._draining
                  else "degraded" if degraded else "ok")
        return 200, _json_bytes({
            "status": status,
            "fitted": self.tuner.fitted,
            "model_generation": self.tuner.generation,
            "circuit": {
                "loop_alive": (self._loop_thread.is_alive()
                               if self._loop_thread is not None else None),
                "loop_error": self.loop_error,
                "model_degraded": bool(getattr(self.tuner, "degraded", False)),
                "rollbacks": int(getattr(self.tuner, "rollbacks", 0)),
            },
        })

    def _loop_stats(self) -> Optional[dict]:
        if self.loop is None and self.cfg.out_dir is None:
            return None
        state_path = (self.loop.state.path if self.loop is not None
                      else self.cfg.out_dir / "loop_state.jsonl")
        # read_complete_records under the hood: safe against the loop thread
        # appending a record mid-read
        cycles = LoopState(state_path).cycles()
        out = {
            "cycles_completed": len(cycles),
            "running": self._loop_thread.is_alive() if self._loop_thread else False,
            "error": self.loop_error,
        }
        if cycles:
            last = cycles[-1]
            out["last_cycle"] = {
                "cycle": last.get("cycle"),
                "n_observations": last.get("n_observations"),
                "refit": last.get("refit"),
                "drift": last.get("drift"),
                "current_config": last.get("current_config"),
            }
        return out

    def _stats(self) -> Tuple[int, bytes]:
        with self._counter_lock:
            requests = dict(self._requests)
            errors = self._errors
            shed = self._shed
            timeouts = self._timeouts
        stats = {
            "uptime_s": round(time.time() - self._started, 3),
            "model_generation": self.tuner.generation,
            "fitted": self.tuner.fitted,
            "n_observations": self.tuner.n_observations,
            "requests": requests,
            "errors": errors,
            "batching": {
                "enabled": self.cfg.batching,
                "n_batches": self._batcher.n_batches if self._batcher else 0,
                "n_scored": self._batcher.n_scored if self._batcher else 0,
                "max_batch": self._batcher.max_batch_seen if self._batcher else 0,
                "mean_batch": round(self._batcher.mean_batch, 3) if self._batcher else 0.0,
            },
            "admission": {
                "max_queue": self.cfg.max_queue,
                "queue_depth": self._batcher.depth if self._batcher else 0,
                "shed": shed,
                "deadline_ms": self.cfg.deadline_ms,
                "deadline_timeouts": timeouts,
            },
            "cache": {
                "capacity": self.cfg.cache_size,
                "size": len(self.cache),
                "hits": self.cache.hits,
                "misses": self.cache.misses,
            },
            "loop": self._loop_stats(),
        }
        return 200, _json_bytes(stats)

    # -- routing --------------------------------------------------------
    def handle(self, method: str, path: str, body: bytes) -> Tuple[int, bytes]:
        """(method, path, body) -> (status, canonical-JSON bytes)."""
        path = path.split("?", 1)[0].rstrip("/") or "/"
        with self._counter_lock:
            self._requests[path] = self._requests.get(path, 0) + 1
            self._active += 1
        try:
            if method == "GET" and path == "/healthz":
                return self._healthz()
            if method == "GET" and path == "/stats":
                return self._stats()
            if method == "GET" and path == "/explain":
                return self._explain()
            if method == "POST" and path in ("/predict", "/recommend"):
                try:
                    payload = json.loads(body or b"{}")
                except json.JSONDecodeError as e:
                    return 400, _json_bytes({"error": f"invalid JSON: {e}"})
                if not isinstance(payload, dict):
                    return 400, _json_bytes({"error": "body must be a JSON object"})
                if path == "/predict":
                    return self._predict(payload)
                return self._recommend(payload)
            return 404, _json_bytes({"error": f"no route for {method} {path}"})
        except Exception as e:  # noqa: BLE001 — one bad request must not kill serving
            with self._counter_lock:
                self._errors += 1
            return 500, _json_bytes({"error": f"{type(e).__name__}: {e}"})
        finally:
            with self._idle:
                self._active -= 1
                if self._active == 0:
                    self._idle.notify_all()


class _Server(http.server.ThreadingHTTPServer):
    daemon_threads = True      # idle keep-alive connections must not pin exit
    block_on_close = False     # draining is explicit (shutdown()), not implicit
    request_queue_size = 128   # a client burst must not overflow the default
    #                            listen(5) backlog into connection resets


def _make_handler(service: RecommendationService):
    class Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"  # keep-alive: load clients reuse sockets
        disable_nagle_algorithm = True  # headers+body are two send()s; Nagle
        #                                 would stall the body ~40ms per
        #                                 response behind the delayed ACK

        def log_message(self, *args):  # quiet: the service logs, not every hit
            pass

        def _respond(self, method: str) -> None:
            try:
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                status, payload = service.handle(method, self.path, body)
            except Exception as e:  # noqa: BLE001
                status, payload = 500, _json_bytes({"error": str(e)})
            try:
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                if status == 503:
                    # shed/unfitted/draining: tell well-behaved clients how
                    # long to back off instead of hammering the queue
                    self.send_header("Retry-After", "1")
                self.end_headers()
                self.wfile.write(payload)
            except (BrokenPipeError, ConnectionResetError):
                pass  # client went away; nothing to do

        def do_GET(self):  # noqa: N802 — http.server API
            self._respond("GET")

        def do_POST(self):  # noqa: N802
            self._respond("POST")

    return Handler


# ---------------------------------------------------------------- warm start

def synthetic_observations(space: ConfigSpace, n_repeats: int = 2,
                           seed: int = 0) -> List[dict]:
    """Deterministic knob-sweep observations (no storage I/O): workers and
    prefetch help with diminishing returns, larger batches amortize overhead.
    Enough cross-config signal for a real fit — the --smoke/--demo warm
    path and the serve benchmark both start from this.  ``seed`` shifts the
    small per-row jitter (seed 0 is the historical set)."""
    rows: List[dict] = []
    for rep in range(n_repeats):
        for i, cand in enumerate(space.candidates()):
            w = cand.get("num_workers", 0)
            pf = cand.get("prefetch_depth", 1)
            b = cand.get("batch_size", 64)
            thr = 80.0 * (1 + 0.9 * w ** 0.7) * (1 + 0.15 * (pf - 1))
            thr *= (b / 64.0) ** 0.2
            thr *= 1 + 0.01 * ((i * 2654435761 + rep * 97 + seed * 7919)
                               % 17 - 8) / 8.0
            rows.append({**cand, "file_size_mb": 64.0, "n_samples": 1000.0,
                         TARGET_NAME: thr})
    return rows


def warm_tuner_from_records(tuner: OnlineAutotuner, path: pathlib.Path) -> int:
    """Ingest a campaign/merged JSONL file and fit once; returns rows added."""
    from ..data.campaign import load_records

    n = tuner.ingest_records(load_records(path))
    tuner.maybe_refit()
    return n


# ---------------------------------------------------------------- smoke

def _http_json(conn: http.client.HTTPConnection, method: str, path: str,
               payload: Optional[dict] = None) -> Tuple[int, dict]:
    body = json.dumps(payload).encode() if payload is not None else None
    conn.request(method, path, body=body,
                 headers={"Content-Type": "application/json"} if body else {})
    resp = conn.getresponse()
    return resp.status, json.loads(resp.read())


def run_smoke(cfg: ServeConfig, progress=print) -> int:
    """Self-contained end-to-end check: warm-fit a synthetic dataset, serve,
    hit every endpoint through real HTTP, verify status + schema, drain."""
    space = ConfigSpace(batch_size=(16, 32, 64), num_workers=(0, 2, 4),
                        block_kb=(64, 256), n_threads=(1,),
                        prefetch_depth=(1, 2))
    tuner = OnlineAutotuner(space=space, min_observations=8, refit_every=8)
    tuner.seed_observations(synthetic_observations(space, n_repeats=1))
    tuner.maybe_refit()
    service = RecommendationService(tuner, cfg, progress=lambda m: progress(f"[serve] {m}"))
    service.start()
    failures: List[str] = []
    n_checks = 0

    def check(name, ok):
        nonlocal n_checks
        n_checks += 1
        progress(f"[smoke] {name}: {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(name)

    try:
        conn = http.client.HTTPConnection(cfg.host, service.port, timeout=10)
        status, h = _http_json(conn, "GET", "/healthz")
        check("healthz", status == 200 and h["fitted"]
              and h["model_generation"] >= 1)
        ctx = {"file_size_mb": 64.0, "n_samples": 1000.0,
               "throughput_mb_s": 120.0}
        status, p = _http_json(conn, "POST", "/predict",
                               {"context": ctx, "config": {"batch_size": 32,
                                                           "num_workers": 2}})
        check("predict", status == 200 and p["predicted_throughput_mb_s"] > 0)
        status, r = _http_json(conn, "POST", "/recommend",
                               {"context": ctx, "top_k": 3})
        check("recommend", status == 200 and len(r["top"]) == 3
              and all("predicted_throughput_mb_s" in t for t in r["top"]))
        status, r2 = _http_json(conn, "POST", "/recommend",
                                {"context": dict(reversed(list(ctx.items()))),
                                 "top_k": 3})
        check("cache_order_insensitive", status == 200 and r2 == r)
        status, e = _http_json(conn, "GET", "/explain")
        check("explain", status == 200 and len(e["features"]) > 0)
        status, s = _http_json(conn, "GET", "/stats")
        cache_ok = (s["cache"]["hits"] >= 1 if cfg.cache_size > 0
                    else s["cache"]["hits"] == 0)
        check("stats", status == 200 and s["requests"].get("/recommend") == 2
              and cache_ok)
        conn.close()
    finally:
        service.shutdown()
    progress(f"[smoke] {'PASSED' if not failures else 'FAILED'} "
             f"({n_checks - len(failures)}/{n_checks} checks ok)")
    return 1 if failures else 0


# ---------------------------------------------------------------- CLI

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.service.serve",
        description="Concurrent recommendation service over the I/O "
                    "predictor: batched /predict + /recommend scoring, "
                    "refit-aware response cache, hot model swap, optional "
                    "embedded tuning loop.",
    )
    add_tuning_args(ap)
    add_serve_args(ap, DEFAULT_SERVE_DIR)
    add_chaos_args(ap)
    args = ap.parse_args(argv)
    chaos_plan_from_args(args)

    cfg = ServeConfig(
        host=args.host, port=args.port, batching=not args.no_batch,
        max_batch=args.max_batch, batch_window_ms=args.batch_window_ms,
        max_queue=args.max_queue, deadline_ms=args.deadline_ms,
        cache_size=0 if args.no_cache else args.cache_size,
        top_k=args.top_k, out_dir=args.out_dir,
    )

    if args.smoke:
        return run_smoke(cfg)

    from .loop import ContinuousTuningLoop, LoopConfig, _format_status, \
        config_kwargs_from_args

    if args.status:
        state = LoopState(args.out_dir / "loop_state.jsonl")
        cycles = state.cycles()
        print(_format_status(cycles, state.corrupt_lines))
        return 0

    loop = None
    if args.loop:
        loop = ContinuousTuningLoop(LoopConfig(**config_kwargs_from_args(args)),
                                    progress=lambda m: print(f"[loop] {m}"))
        if args.force:
            loop.state.path.unlink(missing_ok=True)
            loop.merged_path.unlink(missing_ok=True)
            for p in loop._shard_files():
                p.unlink()
        tuner = loop.tuner
    else:
        tuner = OnlineAutotuner(
            refit_every=args.refit_every,
            min_observations=args.min_observations,
            gain_threshold=args.gain_threshold,
            drift_threshold=args.drift_threshold,
            model=args.model,
        )
    if args.warm_from is not None:
        n = warm_tuner_from_records(tuner, args.warm_from)
        print(f"[serve] warm start: {n} rows from {args.warm_from}, "
              f"fitted={tuner.fitted}")

    service = RecommendationService(tuner, cfg, loop=loop,
                                    progress=lambda m: print(f"[serve] {m}"))
    service.start()
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        print("[serve] draining...")
        service.shutdown()
    return 0


if __name__ == "__main__":
    use_compile_cache()
    sys.exit(main())
