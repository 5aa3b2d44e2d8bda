"""The MGD loop's host spans as the profiler records them: for each device
program, ``repro.loop.dispatch`` around its call and then
``repro.loop.read`` around the host read of its metrics."""
import jax
import jax.numpy as jnp
import pytest

import repro
from repro.api.driver import DriverConfig

STEPS, CHUNK = 6, 2
SPANS = ("repro.loop.dispatch", "repro.loop.read")


def _loss(p, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)


def _sample(step):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3)) + step * 0.01
    return {"x": x, "y": x @ jnp.ones((3, 2))}


def _train():
    loop = repro.TrainLoopConfig(
        chunk=CHUNK, log=None, eval_fn=lambda p: {"w_sum": jnp.sum(p["w"])},
        eval_every=CHUNK)
    return repro.train(_loss, {"w": jnp.zeros((3, 2), jnp.float32)},
                       DriverConfig(dtheta=1e-2, eta=0.5), _sample, STEPS,
                       loop=loop)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(result, [(name, start_ns, end_ns, args)] of the repro.* spans)."""
    from jax.profiler import ProfileData
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out / "trace")):
        res = _train()
    path = sorted((out / "trace").rglob("*.xplane.pb"))[-1]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return res, sorted(spans, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_loop_records_only_its_two_spans(traced):
    _, spans = traced
    assert {s[0] for s in spans} == set(SPANS)
    assert all(s[3] == {} for s in spans)


@pytest.mark.parametrize("name", SPANS)
def test_one_span_per_program(traced, name):
    _, spans = traced
    assert len(_named(spans, name)) == STEPS // CHUNK


def test_each_read_follows_its_dispatch(traced):
    """dispatch, read, dispatch, read, ...: one after another on the
    loop's thread, never nested."""
    _, spans = traced
    order = [s for s in spans if s[0] in SPANS]
    assert [s[0] for s in order] == list(SPANS) * (STEPS // CHUNK)
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))


def test_spans_change_no_result(traced):
    res, _ = traced
    plain = _train()
    assert plain.history == res.history
    assert jnp.array_equal(plain.params["w"], res.params["w"])
