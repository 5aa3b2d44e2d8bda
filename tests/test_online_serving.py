"""Online serving tier: snapshot-consistent swaps, serve→trim→resume
bit-exactness, the uniform lifecycle contract, and the consolidated
``TrainLoopConfig`` front door.

Load-bearing contracts:
* **Torn-swap regression**: a decode in flight during a trainer publish
  sees either the old or the new parameter tree IN FULL, never a mix —
  the dispatcher takes one ``ParamStore`` snapshot per slot batch.
* **Bit-exact resume**: interrupt a serve→trim run at a checkpoint,
  restore (params + driver state + replay ring sidecar), continue —
  f32-identical to the uninterrupted trajectory.
* **Uniform lifecycle**: ``ExternalPlant``, ``ChipFarm`` and
  ``OnlineService`` share ``__enter__/__exit__`` + idempotent
  ``close()`` + ``fence()``.
* **TrainLoopConfig**: the consolidated loop config is f32-bit-identical
  to the flat-kwarg path, which fires ONE PendingDeprecationWarning.
"""
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api.driver import DriverConfig
from repro.serving.online import (OnlineService, ParamStore, ReplayBuffer,
                                  ServiceConfig, TrimConfig)

W_TRUE = np.arange(6, dtype=np.float32).reshape(3, 2)


def _predict(p, batch):
    return batch["x"] @ p["w"]


def _loss(p, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)


def _params():
    return {"w": jnp.zeros((3, 2), jnp.float32)}


def _svc(cfg=None, trim=True, **kw):
    if cfg is None:
        base = dict(slots=4, min_fill=4, trim_batch=4, publish_every=5,
                    batch_window_s=0.001)
        base.update(kw)
        cfg = ServiceConfig(**base)
    tc = TrimConfig(DriverConfig(dtheta=5e-2, eta=0.2), _loss) if trim \
        else None
    return repro.serve(cfg, _predict, _params(), trim=tc, start=False)


def _traffic(svc, n=16, seed=0):
    rng = np.random.default_rng(seed)
    futs = []
    for _ in range(n):
        x = rng.normal(size=(3,)).astype(np.float32)
        futs.append(svc.submit({"x": x}, feedback={"y": x @ W_TRUE}))
    return [f.result(30) for f in futs]


# ---------------------------------------------------------------------------
# Snapshot consistency — the torn-swap regression test
# ---------------------------------------------------------------------------


def test_param_swap_never_tears_mid_decode():
    """Two leaves are always published with EQUAL fill values; any
    response whose leaves disagree, or whose output doesn't match its
    stamped version, caught a torn swap."""
    def paired_predict(p, batch):
        # per-slot [a-b, a]: a-b != 0 would mean a mixed tree
        a = jnp.sum(batch["x"] * 0) + p["a"][0]
        b = p["b"][0]
        return jnp.stack([jnp.broadcast_to(a - b, batch["x"].shape[:1]),
                          jnp.broadcast_to(a, batch["x"].shape[:1])], -1)

    params = {"a": jnp.zeros((64,)), "b": jnp.zeros((64,))}
    svc = OnlineService(paired_predict, params,
                        ServiceConfig(slots=4, batch_window_s=0.0005))
    svc.start()
    stop = threading.Event()

    def publisher():
        v = 0
        while not stop.is_set():
            v += 1
            fill = jnp.full((64,), float(v))
            svc.store.publish({"a": fill, "b": fill})

    pub = threading.Thread(target=publisher, daemon=True)
    pub.start()
    try:
        futs = [svc.submit({"x": np.zeros(3, np.float32)})
                for _ in range(200)]
        for f in futs:
            r = f.result(30)
            assert float(r.output[0]) == 0.0, "torn swap: leaves disagree"
            # the value decoded must be the version the snapshot stamped
            assert float(r.output[1]) == float(r.version)
    finally:
        stop.set()
        pub.join(timeout=10)
        svc.close()


def test_store_snapshot_is_atomic_reference():
    store = ParamStore({"w": jnp.zeros(3)})
    assert store.version == 0
    v = store.publish({"w": jnp.ones(3)})
    snap = store.snapshot()
    assert v == 1 and snap.version == 1
    store.publish({"w": jnp.full((3,), 2.0)})
    # a held snapshot is immutable — later publishes don't touch it
    np.testing.assert_array_equal(np.asarray(snap.params["w"]), np.ones(3))


# ---------------------------------------------------------------------------
# Serve → trim → resume bit-exactness (f32)
# ---------------------------------------------------------------------------


def test_serve_trim_resume_bit_exact(tmp_path):
    def make(d=None):
        cfg = ServiceConfig(slots=4, min_fill=4, trim_batch=4,
                            publish_every=5, checkpoint_dir=d,
                            checkpoint_every=5, batch_window_s=0.001)
        return repro.serve(cfg, _predict, _params(),
                           trim=TrimConfig(DriverConfig(dtheta=5e-2,
                                                        eta=0.2), _loss),
                           start=False)

    d = str(tmp_path / "ck")
    a = make(d).start(background_trim=False)
    _traffic(a)
    assert a.trim(10) == 10
    a.close()

    b = make(d).start(background_trim=False)
    assert b.resumed_step == 10
    assert len(b.replay) == 16          # the ring came back via sidecar
    b.trim(5)
    w_resumed = np.asarray(b.trimmer.params["w"])
    assert b.trimmer.global_step == 15
    b.close()

    c = make(None).start(background_trim=False)
    _traffic(c)
    c.trim(15)
    w_straight = np.asarray(c.trimmer.params["w"])
    c.close()
    np.testing.assert_array_equal(w_resumed, w_straight)


def test_trim_improves_served_cost():
    svc = _svc().start(background_trim=False)
    try:
        _traffic(svc)
        x = np.ones(3, np.float32)
        before = float(np.abs(svc.serve({"x": x}).output - x @ W_TRUE).sum())
        svc.trim(200)
        after = float(np.abs(svc.serve({"x": x}).output - x @ W_TRUE).sum())
        assert after < before * 0.5, (before, after)
        assert svc.version == 40        # 200 steps / publish_every=5
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Replay buffer
# ---------------------------------------------------------------------------


def test_replay_buffer_bounded_and_counter_keyed():
    buf = ReplayBuffer(capacity=8)
    for i in range(12):
        buf.add({"x": np.full(3, float(i), np.float32)})
    assert len(buf) == 8 and buf.total_added == 12
    # oldest entries evicted: fills 4..11 remain
    s = buf.sample(64, step=3, seed=7)
    assert set(np.unique(s["x"])) <= set(float(i) for i in range(4, 12))
    # counter-keyed: same (seed, step) → same batch; different step differs
    np.testing.assert_array_equal(buf.sample(16, step=3, seed=7)["x"],
                                  buf.sample(16, step=3, seed=7)["x"])
    assert not np.array_equal(buf.sample(16, step=3, seed=7)["x"],
                              buf.sample(16, step=4, seed=7)["x"])


def test_replay_buffer_rejects_bad_shapes():
    buf = ReplayBuffer(capacity=4)
    buf.add({"x": np.zeros(3, np.float32)})
    with pytest.raises(ValueError, match="keys"):
        buf.add({"y": np.zeros(3, np.float32)})
    with pytest.raises(ValueError, match="empty"):
        ReplayBuffer(capacity=4).sample(1, step=0)


def test_feedback_flows_into_replay_only_when_given():
    svc = _svc(trim=False).start()
    try:
        svc.serve({"x": np.zeros(3, np.float32)})
        assert len(svc.replay) == 0     # no feedback, no logging
        svc.serve({"x": np.zeros(3, np.float32)},
                  feedback={"y": np.zeros(2, np.float32)})
        assert len(svc.replay) == 1
        with pytest.raises(RuntimeError, match="no trimmer"):
            svc.trim(1)
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Uniform lifecycle contract
# ---------------------------------------------------------------------------


def _lifecycle_objects():
    from repro.hardware import ExternalPlant, SimulatedAnalogChip
    from repro.hardware.farm import ChipFarm
    yield ExternalPlant(SimulatedAnalogChip((2, 2, 1)))
    yield ChipFarm([SimulatedAnalogChip((2, 2, 1), seed=s)
                    for s in range(2)])
    yield _svc(trim=False)


@pytest.mark.parametrize("obj_factory", [_lifecycle_objects],
                         ids=["plants_and_service"])
def test_uniform_lifecycle_contract(obj_factory):
    for obj in obj_factory():
        name = type(obj).__name__
        assert callable(getattr(obj, "fence", None)), name
        assert callable(getattr(obj, "close", None)), name
        with obj as entered:
            assert entered is obj, name
            entered.fence()
        obj.close()                      # second close: idempotent
        obj.close()


def test_service_rejects_use_after_close():
    svc = _svc(trim=False).start()
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit({"x": np.zeros(3, np.float32)})
    with pytest.raises(RuntimeError, match="closed"):
        svc.start()


def test_service_requires_start_before_submit():
    svc = _svc(trim=False)
    with pytest.raises(RuntimeError, match="start"):
        svc.submit({"x": np.zeros(3, np.float32)})
    svc.close()


def test_fence_drains_inflight_requests():
    svc = _svc(trim=False).start()
    try:
        futs = [svc.submit({"x": np.zeros(3, np.float32)})
                for _ in range(32)]
        svc.fence()
        assert all(f.done() for f in futs)
    finally:
        svc.close()


def test_trimmer_error_surfaces_from_fence_and_close():
    """A background trim step that raises ends the trim thread; fence()
    and close() re-raise its error instead of the service carrying on as
    if trimming were healthy."""
    def broken_loss(p, b):
        raise ValueError("broken cost oracle")

    cfg = ServiceConfig(slots=4, min_fill=4, trim_batch=4, publish_every=5,
                        batch_window_s=0.001)
    trim = TrimConfig(DriverConfig(dtheta=5e-2, eta=0.2), broken_loss)
    svc = repro.serve(cfg, _predict, _params(), trim=trim)
    _traffic(svc, n=8)          # fills the replay buffer past min_fill
    with pytest.raises(ValueError, match="broken cost oracle"):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            svc.fence()
            time.sleep(0.01)
    with pytest.raises(ValueError, match="broken cost oracle"):
        svc.close()
    assert svc.closed


def test_ragged_request_shape_is_loud():
    svc = _svc(trim=False, slots=4, batch_window_s=0.05).start()
    try:
        f1 = svc.submit({"x": np.zeros(3, np.float32)})
        f2 = svc.submit({"x": np.zeros(5, np.float32)})
        with pytest.raises(ValueError, match="fixed-shape"):
            f2.result(30)
        with pytest.raises(ValueError):
            f1.result(30)               # whole batch fails loudly
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# TrainLoopConfig — consolidated loop front door
# ---------------------------------------------------------------------------


BATCH_W = jnp.asarray(W_TRUE)


def _train_loss(p, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)


def _sample_fn(step):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3)) + step * 0.01
    return {"x": x, "y": x @ BATCH_W}


def test_trainloopconfig_bit_identical_to_flat_kwargs():
    cfg = DriverConfig(dtheta=1e-2, eta=0.5)
    p0 = {"w": jnp.zeros((3, 2), jnp.float32)}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PendingDeprecationWarning)
        r_flat = repro.train(_train_loss, p0, cfg, _sample_fn, 20,
                             chunk=10, log=None)
    r_loop = repro.train(_train_loss, p0, cfg, _sample_fn, 20,
                         loop=repro.TrainLoopConfig(chunk=10, log=None))
    for a, b in zip(jax.tree_util.tree_leaves(r_flat.params),
                    jax.tree_util.tree_leaves(r_loop.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flat_kwargs_fire_single_pending_deprecation():
    from repro.api.driver import _WARNED
    _WARNED.discard("train_mgd's flat loop keywords")
    cfg = DriverConfig(dtheta=1e-2, eta=0.5)
    p0 = {"w": jnp.zeros((3, 2), jnp.float32)}
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        repro.train(_train_loss, p0, cfg, _sample_fn, 2, chunk=1, log=None)
        repro.train(_train_loss, p0, cfg, _sample_fn, 2, chunk=1, log=None)
    pend = [w for w in rec
            if issubclass(w.category, PendingDeprecationWarning)
            and "TrainLoopConfig" in str(w.message)]
    assert len(pend) == 1, [str(w.message) for w in rec]


def test_trainloopconfig_rejects_mixes_and_unknowns():
    cfg = DriverConfig(dtheta=1e-2, eta=0.5)
    p0 = {"w": jnp.zeros((3, 2), jnp.float32)}
    with pytest.raises(TypeError, match="TrainLoopConfig"):
        repro.train(_train_loss, p0, cfg, _sample_fn, 1, bogus=1)
    with pytest.raises(ValueError, match="one place"):
        repro.train(_train_loss, p0, cfg, _sample_fn, 1,
                    loop=repro.TrainLoopConfig(), chunk=5)


def test_lazy_front_door_exports():
    import importlib
    import sys
    for name in ("train", "serve", "driver", "TrainLoopConfig",
                 "ServiceConfig", "TrimConfig", "OnlineService"):
        assert name in repro.__all__, name
        assert getattr(repro, name) is not None
    # a fresh import of repro must not drag jax in
    saved = {k: sys.modules.pop(k) for k in list(sys.modules)
             if k == "repro" or k.startswith("repro.")}
    jax_mods = {k: sys.modules.pop(k) for k in list(sys.modules)
                if k == "jax" or k.startswith("jax.")}
    try:
        importlib.import_module("repro")
        assert "jax" not in sys.modules
    finally:
        sys.modules.update(saved)
        sys.modules.update(jax_mods)
