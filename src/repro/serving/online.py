"""Online-learning serving tier: inference under live traffic while MGD
re-trims the plant in the background.

This is the deployment regime the drift study (``benchmarks/
drift_aging.py``) said matters: a deployed analog device ages
continuously, and continuous MGD re-trim holds ~0.9 of drift-free
accuracy where the unmitigated device collapses.  ``OnlineService``
turns that result into a product — the repo's first workload where
inference and MGD training share a device:

* **Serving** — requests are queued and batched into FIXED-SHAPE decode
  slots (the ``serving/decode.py`` static-batch pattern: ``slots``
  request lanes plus an alive mask; dead slots keep cycling zeros so the
  jitted predict program never recompiles under ragged traffic).
* **Feedback logging** — every served request that carries feedback is
  appended to a bounded :class:`ReplayBuffer` as an (input, cost-
  feedback) example; the buffer is the bridge between live traffic and
  the optimizer.
* **Background re-trim** — :class:`OnlineTrimmer` drives any MGD
  algorithm through any ``hardware.Plant`` (including a drifting
  ``ChipFarm`` armed with a ``FaultPolicy``) from replay samples, using
  the same registry drivers and per-step jit dispatch as
  ``training.train_mgd``.  Replay sampling is counter-keyed on the
  global step, so the trim trajectory is a pure function of (buffer
  content, step) — checkpoint/resume replays it bit-exactly while the
  buffer is quiescent.
* **Snapshot-consistent swaps** — the trainer publishes parameters into
  a versioned :class:`ParamStore`; the dispatcher takes ONE snapshot per
  decode batch, so a swap can never tear mid-decode (a response is
  computed entirely under old or entirely under new parameters — the
  torn-swap regression test pins this).  Publishes happen only after
  ``fence()`` drains in-flight pipelined plant writes (the PR 7
  discipline), so the published tree is what actually LANDED on the
  device.
* **Checkpointing** — the trimmer checkpoints the generic
  ``{"params", "state"}`` driver-state tree through
  ``training.checkpoint`` (the PR 3 mechanism), with the replay ring in
  a sidecar ``replay_<step>.npz``; restoring resumes serve→trim
  bit-exactly (f32).

Lifecycle contract (shared with ``ExternalPlant`` and ``ChipFarm``):
``__enter__``/``__exit__``, idempotent ``close()``, and ``fence()``.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.driver import state_step
from repro.training import checkpoint as ckpt
from repro.training.train_loop import resolve_driver

Pytree = Any

#: default bound on any blocking service operation — a serving tier must
#: degrade into a visible timeout, never a silent hang (PR 6 discipline)
DEFAULT_TIMEOUT_S = 60.0


# ---------------------------------------------------------------------------
# Versioned parameter store — the snapshot-consistency mechanism
# ---------------------------------------------------------------------------


class ParamSnapshot(NamedTuple):
    """One immutable (version, params) pair.  Readers that hold a
    snapshot keep a complete, internally consistent tree no matter how
    many publishes happen while they decode with it."""

    version: int
    params: Pytree


class ParamStore:
    """Atomic published-parameter slot.

    ``publish`` swaps a single tuple reference under a lock;
    ``snapshot`` reads that one reference.  Because jax arrays are
    immutable and the whole tree rides one tuple, a reader can never
    observe a mix of old and new leaves — the swap is all-or-nothing by
    construction (tests/test_online_serving.py hammers this from a
    concurrent reader).
    """

    def __init__(self, params: Pytree):
        self._lock = threading.Lock()
        self._snap = ParamSnapshot(0, params)

    def publish(self, params: Pytree) -> int:
        """Install ``params`` as the new serving tree; returns the new
        version.  Callers that drive a pipelined plant must ``fence()``
        first so the published tree is the landed one."""
        with self._lock:
            self._snap = ParamSnapshot(self._snap.version + 1, params)
            return self._snap.version

    def snapshot(self) -> ParamSnapshot:
        # one reference read — atomic; the lock only serializes writers
        return self._snap

    @property
    def version(self) -> int:
        return self._snap.version


# ---------------------------------------------------------------------------
# Bounded replay buffer — served traffic becomes training data
# ---------------------------------------------------------------------------


class ReplayBuffer:
    """Bounded ring of (input, feedback) examples logged from traffic.

    Examples are dicts of fixed-shape numpy rows (no leading batch dim);
    storage is allocated lazily from the first example's shapes/dtypes.
    ``sample`` draws a batch with a generator keyed on (seed, step) —
    counter-keyed like every other noise source in the repo (MGD002), so
    a resumed trimmer replays the identical batch sequence from an
    identical buffer.
    """

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._data: Optional[Dict[str, np.ndarray]] = None
        self._size = 0
        self._cursor = 0
        self._total = 0                 # lifetime adds (telemetry)

    def __len__(self) -> int:
        return self._size

    @property
    def total_added(self) -> int:
        return self._total

    def _allocate(self, example: Dict[str, np.ndarray]) -> None:
        self._data = {
            k: np.zeros((self.capacity,) + np.asarray(v).shape,
                        np.asarray(v).dtype)
            for k, v in example.items()}

    def add(self, example: Dict[str, Any]) -> None:
        """Append one example (dict of rows); oldest entry evicted when
        full."""
        rows = {k: np.asarray(v) for k, v in example.items()}
        with self._lock:
            if self._data is None:
                self._allocate(rows)
            if set(rows) != set(self._data):
                raise ValueError(
                    f"example keys {sorted(rows)} != buffer keys "
                    f"{sorted(self._data)}")
            for k, v in rows.items():
                self._data[k][self._cursor] = v
            self._cursor = (self._cursor + 1) % self.capacity
            self._size = min(self._size + 1, self.capacity)
            self._total += 1

    def add_batch(self, batch: Dict[str, Any]) -> None:
        """Append every row of a [B, ...] batch dict."""
        arrs = {k: np.asarray(v) for k, v in batch.items()}
        n = next(iter(arrs.values())).shape[0]
        for i in range(n):
            self.add({k: v[i] for k, v in arrs.items()})

    def sample(self, batch_size: int, step: int, *,
               seed: int = 0) -> Dict[str, np.ndarray]:
        """Draw ``batch_size`` examples (with replacement), keyed on
        (seed, step) — deterministic for a given buffer content."""
        with self._lock:
            if self._size == 0:
                raise ValueError("cannot sample from an empty replay buffer")
            rng = np.random.default_rng((int(seed), int(step)))
            idx = rng.integers(0, self._size, size=int(batch_size))
            return {k: v[idx].copy() for k, v in self._data.items()}

    # -- sidecar persistence (rides next to the driver-state checkpoint) ----

    def state(self) -> Dict[str, np.ndarray]:
        with self._lock:
            out = {"__size": np.int64(self._size),
                   "__cursor": np.int64(self._cursor),
                   "__total": np.int64(self._total)}
            if self._data is not None:
                out.update({f"data_{k}": v.copy()
                            for k, v in self._data.items()})
            return out

    def load_state(self, tree: Dict[str, np.ndarray]) -> None:
        with self._lock:
            data = {k[len("data_"):]: np.array(tree[k])
                    for k in tree if k.startswith("data_")}
            self._data = data or None
            if self._data is not None:
                cap = next(iter(self._data.values())).shape[0]
                if cap != self.capacity:
                    raise ValueError(
                        f"replay checkpoint capacity {cap} != configured "
                        f"{self.capacity}")
            self._size = int(tree["__size"])
            self._cursor = int(tree["__cursor"])
            self._total = int(tree["__total"])

    def save_sidecar(self, path: str) -> None:
        np.savez(path, **self.state())

    def load_sidecar(self, path: str) -> None:
        with np.load(path) as z:
            self.load_state({k: z[k] for k in z.files})


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServiceConfig:
    """Loop-level knobs of :class:`OnlineService` (the serving twin of
    ``training.TrainLoopConfig``)."""

    slots: int = 8                  # fixed decode-slot batch width
    queue_depth: int = 256          # bounded request queue (backpressure)
    batch_window_s: float = 0.002   # linger filling a slot batch
    jit_predict: bool = True        # jit predict_fn (fixed shapes → 1 compile)
    request_timeout_s: float = DEFAULT_TIMEOUT_S
    replay_capacity: int = 2048     # bounded feedback ring
    trim_batch: int = 8             # replay samples per trim step
    min_fill: int = 8               # examples required before trimming
    publish_every: int = 20         # trim steps between param publishes
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0       # trim steps between checkpoints
    resume: bool = True
    seed: int = 0                   # replay-sampling seed (counter-keyed)

    def replace(self, **kw) -> "ServiceConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class TrimConfig:
    """What the background trimmer trains: an algorithm config (or a
    pre-built ``MGDDriver``) plus the model/device plumbing —
    exactly the arguments ``repro.driver`` takes at construction."""

    cfg: Any                        # DriverConfig | legacy config | MGDDriver
    loss_fn: Optional[Callable] = None
    plant: Any = None               # hardware.Plant (None → implicit ideal)
    algorithm: Optional[str] = None
    probe_fn: Optional[Callable] = None


# ---------------------------------------------------------------------------
# The background trimmer
# ---------------------------------------------------------------------------


class OnlineTrimmer:
    """Step-driven MGD re-trim over replay samples, with fenced
    publishes and generic driver-state checkpointing.

    The trimmer is the serving twin of ``train_mgd``'s inner loop: the
    same registry driver, the same per-step ``jax.jit`` dispatch that
    external plants require, the same ``{"params", "state"}`` checkpoint
    tree, and the same fence-before-boundary discipline.  It is driven
    either synchronously (``step(n)`` — deterministic, what the tests
    and gated benchmark rows use) or from the service's trainer thread.
    """

    def __init__(self, trim: TrimConfig, params: Pytree,
                 replay: ReplayBuffer, store: ParamStore,
                 cfg: ServiceConfig):
        self._drv = resolve_driver(
            trim.loss_fn, trim.cfg, probe_fn=trim.probe_fn,
            plant=trim.plant, algorithm=trim.algorithm)
        self._step_fn = jax.jit(self._drv.step)
        self._replay = replay
        self._store = store
        self._cfg = cfg
        self._lock = threading.RLock()
        self._params = params
        self._state = self._drv.init(params)
        self._last_aux: Dict[str, Any] = {}
        self.steps_done = 0             # steps taken by THIS process
        self.publishes = 0

    @property
    def driver(self):
        return self._drv

    @property
    def plant(self):
        return self._drv.plant

    @property
    def params(self) -> Pytree:
        with self._lock:
            return self._params

    @property
    def global_step(self) -> int:
        with self._lock:
            return int(state_step(self._state))

    def fence(self) -> None:
        """Drain in-flight plant writes (pipelined farms) — the
        precondition for publishes, checkpoints and accuracy readouts.
        A no-op for plants without a fence."""
        plant_fence = getattr(self._drv.plant, "fence", None)
        if callable(plant_fence):
            plant_fence()

    # -- trimming -----------------------------------------------------------

    def ready(self) -> bool:
        return len(self._replay) >= max(self._cfg.min_fill, 1)

    def step(self, n: int = 1) -> int:
        """Run up to ``n`` trim steps; returns how many actually ran
        (0 when the replay buffer is below ``min_fill``).  Publish and
        checkpoint boundaries are pure functions of the global step, so
        a resumed trimmer replays the identical schedule."""
        took = 0
        for _ in range(n):
            with self._lock:
                if not self.ready():
                    break
                gstep = int(state_step(self._state))
                batch = self._replay.sample(
                    self._cfg.trim_batch, gstep, seed=self._cfg.seed)
                jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
                self._params, self._state, self._last_aux = self._step_fn(
                    self._params, self._state, jbatch)
                self.steps_done += 1
                took += 1
                done = gstep + 1
                if self._cfg.publish_every and \
                        done % self._cfg.publish_every == 0:
                    self.publish()
                if self._cfg.checkpoint_dir and self._cfg.checkpoint_every \
                        and done % self._cfg.checkpoint_every == 0:
                    self.save()
        return took

    # -- boundaries (fence first — PR 7 discipline, linted by MGD006) -------

    def publish(self) -> int:
        """Swap the trainer's parameters into the serving store,
        snapshot-consistently: fence the plant so every pipelined write
        has landed, then publish the whole tree in one atomic swap."""
        with self._lock:
            self.fence()
            version = self._store.publish(self._params)
            self.publishes += 1
            return version

    def save(self) -> Optional[str]:
        """Checkpoint the generic driver-state tree (+ replay sidecar)."""
        d = self._cfg.checkpoint_dir
        if not d:
            return None
        with self._lock:
            self.fence()
            step = int(state_step(self._state))
            # sidecar first: a crash between the two writes leaves an
            # orphan npz, never a checkpoint that references a missing one
            self._replay.save_sidecar(_sidecar_path(d, step))
            return ckpt.save(d, step,
                             {"params": self._params, "state": self._state},
                             extra={"algo": self._drv.algorithm,
                                    "service": True,
                                    "seed": int(self._cfg.seed)})

    def restore(self) -> Optional[int]:
        """Resume from the newest checkpoint under ``checkpoint_dir``;
        returns the restored global step (None when there is nothing to
        restore).  Parameters, driver state AND the replay ring come
        back, so the continued trajectory is the uninterrupted one."""
        d = self._cfg.checkpoint_dir
        if not d or ckpt.latest_step(d) is None:
            return None
        with self._lock:
            tree, _, step = ckpt.restore(
                d, {"params": self._params, "state": self._state})
            self._params, self._state = tree["params"], tree["state"]
            try:
                self._replay.load_sidecar(_sidecar_path(d, step))
            except FileNotFoundError:
                pass                     # pre-sidecar checkpoint: keep buffer
            return step

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            aux = {k: float(v) for k, v in self._last_aux.items()
                   if np.ndim(v) == 0}
            return {"global_step": int(state_step(self._state)),
                    "steps_done": self.steps_done,
                    "publishes": self.publishes,
                    "replay_fill": len(self._replay),
                    **{f"aux_{k}": v for k, v in aux.items()}}


def _sidecar_path(ckpt_dir: str, step: int) -> str:
    import os
    os.makedirs(ckpt_dir, exist_ok=True)
    return os.path.join(ckpt_dir, f"replay_{step:012d}.npz")


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------


class _Request(NamedTuple):
    inputs: Dict[str, Any]
    feedback: Optional[Dict[str, Any]]
    future: Future
    t0: float


class ServeResult(NamedTuple):
    """One served response: the output row, the parameter version that
    computed it (whole-tree consistent), and the request latency."""

    output: Any
    version: int
    latency_s: float


class OnlineService:
    """Inference under live traffic with background MGD re-trim.

    ``predict_fn(params, batch) -> outputs`` maps a fixed-shape
    ``[slots, ...]`` batch dict to outputs whose leading dim is the slot
    index (jitted once — the static-batch serving pattern).  ``trim=``
    attaches an :class:`OnlineTrimmer`; without it the service is a
    plain batching inference tier.

    Thread layout: callers ``submit``; a dispatcher thread batches
    requests into slots and decodes them under ONE parameter snapshot
    per batch; an optional trainer thread runs the trimmer.  All
    threads are owned by the service and joined by ``close()``.
    """

    def __init__(self, predict_fn: Callable, params: Pytree,
                 cfg: Optional[ServiceConfig] = None, *,
                 trim: Optional[TrimConfig] = None,
                 name: str = "online-service"):
        self.cfg = cfg or ServiceConfig()
        self.name = name
        self._predict = (jax.jit(predict_fn) if self.cfg.jit_predict
                         else predict_fn)
        self.replay = ReplayBuffer(self.cfg.replay_capacity)
        # store constructed after a possible resume so version 0 is the
        # tree the service actually starts serving
        self._store: Optional[ParamStore] = None
        self.trimmer: Optional[OnlineTrimmer] = None
        self.resumed_step: Optional[int] = None
        if trim is not None:
            # the store reference is installed right below; the trimmer
            # never publishes during construction
            self._store = ParamStore(params)
            self.trimmer = OnlineTrimmer(trim, params, self.replay,
                                         self._store, self.cfg)
            if self.cfg.checkpoint_dir and self.cfg.resume:
                self.resumed_step = self.trimmer.restore()
            self._store = ParamStore(self.trimmer.params)
            self.trimmer._store = self._store
        else:
            self._store = ParamStore(params)
        self._queue: queue.Queue = queue.Queue(maxsize=self.cfg.queue_depth)
        self._stop = threading.Event()
        self._threads: list = []
        self._trim_error: Optional[Exception] = None
        self._started = False
        self._closed = False
        self._lock = threading.Lock()
        self._served = 0
        self._batches = 0
        self._latencies: list = []      # rolling window (host-side floats)

    # -- lifecycle (uniform with ExternalPlant / ChipFarm) ------------------

    def start(self, *, background_trim: bool = True) -> "OnlineService":
        """Start the dispatcher (and, with a trimmer attached, the
        trainer thread).  Idempotent."""
        if self._closed:
            raise RuntimeError(f"{self.name}: service is closed")
        if self._started:
            return self
        self._started = True
        t = threading.Thread(target=self._dispatch_loop,
                             name=f"{self.name}-dispatch", daemon=True)
        t.start()
        self._threads.append(t)
        if self.trimmer is not None and background_trim:
            t = threading.Thread(target=self._trim_loop,
                                 name=f"{self.name}-trim", daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def close(self) -> None:
        """Stop threads, flush the queue (pending requests get a
        RuntimeError, never a hang), fence the plant.  Idempotent.
        Re-raises the error that stopped the background trimmer."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        for t in self._threads:
            t.join(timeout=DEFAULT_TIMEOUT_S)
        self._threads = []
        while True:                     # fail pending futures loudly
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            item.future.set_exception(
                RuntimeError(f"{self.name}: service closed"))
            self._queue.task_done()
        if self.trimmer is not None:
            self.trimmer.fence()
        self._raise_trim_error()

    def __enter__(self) -> "OnlineService":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    @property
    def closed(self) -> bool:
        return self._closed

    def fence(self, timeout: Optional[float] = None) -> None:
        """Drain in-flight serving work (queued + mid-decode requests),
        then fence the trimmer's plant — after this, every submitted
        request has been answered and every parameter write has landed.
        Re-raises the error that stopped the background trimmer."""
        deadline = time.monotonic() + (timeout if timeout is not None
                                       else DEFAULT_TIMEOUT_S)
        with self._queue.all_tasks_done:
            while self._queue.unfinished_tasks:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._queue.all_tasks_done.wait(
                        remaining):
                    raise TimeoutError(
                        f"{self.name}: fence timed out with "
                        f"{self._queue.unfinished_tasks} requests in flight")
        if self.trimmer is not None:
            self.trimmer.fence()
        self._raise_trim_error()

    # -- serving ------------------------------------------------------------

    @property
    def store(self) -> ParamStore:
        """The versioned serving-parameter store (read-mostly; writers
        must follow the fence-before-publish discipline)."""
        return self._store

    @property
    def version(self) -> int:
        return self._store.version

    def snapshot(self) -> ParamSnapshot:
        return self._store.snapshot()

    def submit(self, inputs: Dict[str, Any],
               feedback: Optional[Dict[str, Any]] = None) -> Future:
        """Enqueue one request (dict of per-example rows).  Returns a
        Future resolving to a :class:`ServeResult`.  ``feedback`` (e.g.
        the eventual label/cost target) is logged with the inputs into
        the replay buffer and becomes training signal for the trimmer."""
        if self._closed:
            raise RuntimeError(f"{self.name}: service is closed")
        if not self._started:
            raise RuntimeError(f"{self.name}: call start() (or use the "
                               f"service as a context manager) first")
        fut: Future = Future()
        item = _Request(inputs, feedback, fut, time.perf_counter())
        self._queue.put(item, timeout=self.cfg.request_timeout_s)
        return fut

    def serve(self, inputs: Dict[str, Any],
              feedback: Optional[Dict[str, Any]] = None,
              timeout: Optional[float] = None) -> ServeResult:
        """Synchronous ``submit`` + wait."""
        return self.submit(inputs, feedback).result(
            timeout if timeout is not None else self.cfg.request_timeout_s)

    # -- trimming (synchronous surface; the trainer thread uses the same) ---

    def trim(self, n: int = 1) -> int:
        """Run up to ``n`` trim steps synchronously; returns how many
        ran.  Deterministic — what tests and gated benchmarks drive."""
        if self.trimmer is None:
            raise RuntimeError(f"{self.name}: no trimmer attached "
                               f"(construct with trim=TrimConfig(...))")
        return self.trimmer.step(n)

    def publish(self) -> int:
        if self.trimmer is None:
            raise RuntimeError(f"{self.name}: no trimmer attached")
        return self.trimmer.publish()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            lat = np.asarray(self._latencies[-4096:], np.float64)
            out = {
                "served": self._served,
                "batches": self._batches,
                "version": self.version,
                "queue_depth": self._queue.qsize(),
                "latency_p50_ms": (float(np.percentile(lat, 50)) * 1e3
                                   if lat.size else 0.0),
                "latency_p99_ms": (float(np.percentile(lat, 99)) * 1e3
                                   if lat.size else 0.0),
            }
        if self.trimmer is not None:
            out.update({f"trim_{k}": v
                        for k, v in self.trimmer.stats().items()})
        return out

    # -- internals ----------------------------------------------------------

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            items = [first]
            deadline = time.perf_counter() + self.cfg.batch_window_s
            while len(items) < self.cfg.slots:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    items.append(self._queue.get(timeout=remaining))
                except queue.Empty:
                    break
            self._serve_batch(items)
            for _ in items:
                self._queue.task_done()

    def _pad_slots(self, items):
        """Pack ragged request rows into the fixed [slots, ...] batch
        with an alive mask — dead slots cycle zeros (decode.py's
        static-batch pattern), so the jitted program never re-traces."""
        slots = self.cfg.slots
        keys = list(items[0].inputs)
        batch = {}
        for k in keys:
            rows = [np.asarray(it.inputs[k]) for it in items]
            ref = rows[0]
            arr = np.zeros((slots,) + ref.shape, ref.dtype)
            for i, r in enumerate(rows):
                if r.shape != ref.shape or r.dtype != ref.dtype:
                    raise ValueError(
                        f"request {i}: key {k!r} has shape {r.shape} "
                        f"dtype {r.dtype}, slot expects {ref.shape} "
                        f"{ref.dtype} — fixed-shape serving pads ragged "
                        f"inputs caller-side (see serving.decode)")
                arr[i] = r
            batch[k] = jnp.asarray(arr)
        alive = np.zeros((slots,), bool)
        alive[:len(items)] = True
        return batch, alive

    def _serve_batch(self, items) -> None:
        # ONE snapshot for the whole batch: every response in it was
        # computed under a single complete parameter tree
        snap = self._store.snapshot()
        try:
            batch, _alive = self._pad_slots(items)
            out = jax.device_get(self._predict(snap.params, batch))
        except Exception as e:          # noqa: BLE001 — surfaced per-request
            for it in items:
                it.future.set_exception(e)
            return
        t_done = time.perf_counter()
        lats = []
        for i, it in enumerate(items):
            row = jax.tree_util.tree_map(lambda a: np.asarray(a)[i], out)
            lat = t_done - it.t0
            lats.append(lat)
            if it.feedback is not None:
                self.replay.add({**it.inputs, **it.feedback})
            it.future.set_result(ServeResult(row, snap.version, lat))
        with self._lock:
            self._served += len(items)
            self._batches += 1
            self._latencies.extend(lats)
            if len(self._latencies) > 65536:
                del self._latencies[:-4096]

    def _trim_loop(self) -> None:
        # the thread boundary: an error here would otherwise end the
        # thread silently; fence() and close() re-raise it
        try:
            while not self._stop.is_set():
                took = self.trimmer.step(4)
                if not took:
                    self._stop.wait(0.005)
        except Exception as e:          # noqa: BLE001 — surfaced by fence
            self._trim_error = e

    def _raise_trim_error(self) -> None:
        if self._trim_error is not None:
            raise self._trim_error


# ---------------------------------------------------------------------------
# The front door
# ---------------------------------------------------------------------------


def serve(cfg: Optional[ServiceConfig], predict_fn: Callable,
          params: Pytree, *, trim: Optional[TrimConfig] = None,
          start: bool = True, name: str = "online-service") -> OnlineService:
    """Build (and by default start) an :class:`OnlineService` — the
    canonical serving entry point, re-exported as ``repro.serve``:

        svc = repro.serve(ServiceConfig(slots=8), predict_fn, params,
                          trim=TrimConfig(DriverConfig(...), loss_fn,
                                          plant=farm))
        result = svc.serve({"x": x}, feedback={"y": y})

    Pass ``cfg=None`` for defaults; ``start=False`` to wire threads up
    later (tests that drive the service synchronously do this).
    """
    svc = OnlineService(predict_fn, params, cfg, trim=trim, name=name)
    return svc.start() if start else svc
