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


def _fused_train(arch, out):
    """A traced fused central-pair run of a smoke transformer: (history,
    [(name, stats)] of the repro.* host spans)."""
    from jax.profiler import ProfileData

    from repro.configs import get_smoke_config
    from repro.models import make_transformer_probe_fn, model_init, model_loss
    cfg = get_smoke_config(arch).replace(dtype="float32")
    params = model_init(cfg, jax.random.PRNGKey(0))

    def sample(i):
        toks = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(1), i),
                                  (2, 32), 0, cfg.vocab)
        return {"tokens": toks, "labels": toks}

    with jax.profiler.trace(str(out)):
        res = repro.train(
            None, params, repro.driver(
                "discrete", DriverConfig(fused=True, mode="central",
                                         kernel_impl="interpret",
                                         dtheta=2 ** -6, eta=2e-3),
                lambda p, b: model_loss(p, cfg, b),
                probe_fn=make_transformer_probe_fn(cfg)),
            sample, 4, loop=repro.TrainLoopConfig(chunk=CHUNK, log=None))
    path = sorted(out.rglob("*.xplane.pb"))[-1]
    spans = sorted(((e.start_ns, e.name, dict(e.stats))
                    for plane in ProfileData.from_file(str(path)).planes
                    for line in plane.lines for e in line.events
                    if e.name.startswith("repro.")))
    return res.history, [(n, st) for _, n, st in spans]


def test_dense_config_reads_no_expert_counter(tmp_path):
    """A dense config's loop reads its four metrics and writes no
    ``repro.moe.rows`` span: no extra metric, no extra read."""
    history, spans = _fused_train("qwen3-14b", tmp_path / "dense")
    assert {n for n, _ in spans} == set(SPANS)
    for _, rec in history:
        assert set(rec) == {"cost", "c_tilde", "updated", "grad_norm_proxy"}


def test_expert_counter_rides_the_loops_read(tmp_path):
    """DeepSeek-V3's dropless layer hands back the rows each probe stream
    routed to each held expert: the loop takes them in its one read per
    program and writes them as the span ``repro.moe.rows``'s stats."""
    import numpy as np
    history, spans = _fused_train("deepseek-v3-671b", tmp_path / "moe")
    rows = [st for n, st in spans if n == "repro.moe.rows"]
    assert len(rows) == len(history) == 4 // CHUNK
    for st, (_, rec) in zip(rows, history):
        shape = tuple(int(x) for x in st["shape"].split("x"))
        values = np.array([int(x) for x in st["values"].split()])
        assert shape == (1, 2, 8)
        np.testing.assert_array_equal(values.reshape(shape),
                                      np.array(rec["moe_rows"]))
    order = [n for n, _ in spans]
    assert order == ["repro.loop.dispatch", "repro.loop.read",
                     "repro.moe.rows"] * (4 // CHUNK)
