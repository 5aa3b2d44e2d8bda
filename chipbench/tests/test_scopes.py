"""scopes.py against two traces recorded on a TPU v5e, four fused MGD
steps of mistral-nemo-12b (4 layers, 1x128 tokens), one step per program:
``online-4steps`` from a program without scopes or spans, and
``online-4steps-scoped`` from one with them, recorded as a ``--trace 1``
run records its window (``python3 -m chipbench.tests.record_trace``)."""
import dataclasses
import gzip
import shutil
from pathlib import Path

import pytest

from chipbench import bench, scopes
from chipbench import trace as tr
from chipbench.context import KERNELS, Context

DATA = Path(__file__).parent / "data"
STEPS = 4
CLOCK = 2e-4        # s: slack of the clock check
CELL = "mistral-nemo-12b.online-1x128"
SCOPE_METRICS = {"model.embed_ms_per_step": "repro.model.embed",
                 "model.layer_slice_ms_per_step": "repro.model.layers",
                 "model.block_ms_per_step": "repro.model.block",
                 "model.attn_ms_per_step": "repro.model.attn",
                 "model.mlp_ms_per_step": "repro.model.mlp",
                 "model.loss_ms_per_step": "repro.model.loss",
                 "kernels.prep_ms_per_step": "repro.kernels.prep"}
LOOP_METRICS = ["loop.read_ms_per_step.online",
                "loop.host_reads_per_step.online",
                "loop.dispatch_ms_per_step.online"]
NEW_METRICS = [f"{m}.{k}" for m in SCOPE_METRICS for k in ("train", "online")
               ] + LOOP_METRICS


def _unzip(tmp_path_factory, name):
    pytest.importorskip("jax")
    path = tmp_path_factory.mktemp(name) / "trace" / CELL / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def _context(trace, t0, t1):
    cell = bench.resolve(CELL)
    peaks = bench.load_json("", "peaks")["devices"]["TPU v5 lite"]
    return Context(trace, t0, t1, STEPS, 1, cell.config, cell.traffic,
                   peaks["bf16_flops"], peaks["hbm_bytes_per_s"])


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    """(path, Program) of the trace without program scopes."""
    path = _unzip(tmp_path_factory, "online-4steps")
    trace = tr.load(path)
    ops = trace.devices[0]
    ctx = _context(trace, min(e.start for e in ops), max(e.end for e in ops))
    return path, scopes.Program(ctx, path)


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """(path, Program) of the scoped trace, over its traced window."""
    path = _unzip(tmp_path_factory, "online-4steps-scoped")
    trace = tr.load(path)
    return path, scopes.Program(_context(trace, *trace.window()), path)


def test_innermost():
    assert scopes.innermost(
        "jit(run)/while/body/repro.model.layers/while/body/"
        "repro.model.block/repro.model.attn/repro.kernels.prep/pad:") \
        == "repro.kernels.prep"
    assert scopes.innermost("jit(run)/while/body/closed_call/squeeze:") == ""
    assert scopes.innermost("transpose(jvp(repro.model.embed))/mul:") \
        == "repro.model.embed"


def test_op_scopes_decode_the_name_stacks(plain):
    path, prog = plain
    known = scopes.op_scopes(path)[0]
    ops = prog.ctx.trace.devices[0]
    named = [e for e in ops if e.text in known]
    assert len(named) > 0.9 * len(ops)
    for e in named:
        if e.name in KERNELS:
            assert known[e.text].endswith(f"jit({e.name})/pallas_call:")
    assert not any("repro." in s for s in known.values())


def test_new_metrics_find_nothing_in_the_unscoped_trace(plain, monkeypatch):
    _, prog = plain
    assert prog.parts and all(not k.startswith("repro.") for k in prog.parts)
    monkeypatch.setattr(scopes, "of", lambda ctx: prog)
    for m in NEW_METRICS:
        assert bench.metric_reader(m)(prog.ctx) is None, m


def test_no_trace_found_reads_none(plain, monkeypatch, tmp_path):
    _, prog = plain
    monkeypatch.setattr(scopes, "OUT", tmp_path)
    monkeypatch.setattr(scopes, "_last", [])
    assert scopes.trace_path(prog.ctx) is None
    assert scopes.of(prog.ctx).path is None
    for m in NEW_METRICS:
        assert bench.metric_reader(m)(prog.ctx) is None, m


def test_parts_account_for_busy_time(scoped):
    _, prog = scoped
    parts, ctx = prog.parts, prog.ctx
    assert sum(parts.values()) == pytest.approx(ctx.busy_s, abs=1e-6 * STEPS)
    assert set(KERNELS) <= set(parts)
    for key in parts:
        assert key in KERNELS or key == "" or key.startswith("repro.")
    for scope in SCOPE_METRICS.values():
        assert scope in parts or scope == "repro.kernels.prep"
    # the kernels' share is what the HLO names say it is
    for k in KERNELS:
        assert parts[k] == pytest.approx(ctx.op_seconds((k,))[0], rel=1e-9)


def _programs(prog):
    """Device ops of each program of the window, split at the idle gaps
    between programs (over a millisecond; those inside a step are tens
    of microseconds)."""
    ops = prog.ctx.trace.devices[0]
    gaps = [g for g in tr.idle_gaps(ops, ops[0].start, ops[-1].end)
            if g[1] - g[0] > 1e-3]
    cuts = [ops[0].start] + [b for _, b in gaps] + [float("inf")]
    return [[e for e in ops if a <= e.start < b]
            for a, b in zip(cuts, cuts[1:])]


def test_host_spans_and_device_ops_share_a_clock(scoped):
    """The clock check: each program's first op starts after its dispatch
    span opens, and its host read returns after its last op and before
    the next program's first, to within ``CLOCK``.  The profiler aligns
    the TPU's timestamps with the host's only roughly: here each
    program's first op lies 1.2-1.3 ms before its dispatch span closes,
    which is when the host has enqueued it, and 0.14-0.24 ms before in
    other chip runs.  So the loop's metrics take whole gaps or host time
    alone."""
    _, prog = scoped
    ctx = prog.ctx
    dispatches = scopes.window_spans(ctx, "repro.loop.dispatch")
    reads = scopes.window_spans(ctx, "repro.loop.read")
    programs = _programs(prog)
    assert len(dispatches) == len(reads) == len(programs) == STEPS
    for i, (d, r) in enumerate(zip(dispatches, reads)):
        assert d.end <= r.start
        assert programs[i][0].start >= d.start - CLOCK
        assert r.end >= max(e.end for e in programs[i]) - CLOCK
        if i + 1 < len(programs):
            assert r.end <= dispatches[i + 1].start
            assert r.end <= programs[i + 1][0].start + CLOCK


def test_gaps_between_steps_are_the_loops(scoped):
    """The harness's own gap labels name the loop's spans."""
    _, prog = scoped
    ops = prog.ctx.trace.devices[0]
    gaps = tr.idle_gaps(ops, min(e.start for e in ops),
                        max(e.end for e in ops))
    between = sorted(gaps, key=lambda g: g[0] - g[1])[:STEPS - 1]
    assert all(b - a > 1e-3 for a, b in between)
    for a, b in between:
        assert tr.label_gap(prog.ctx.trace.host, a, b) == "repro.loop.read"


@pytest.mark.parametrize("shift_ms", [-1.3, -0.6, 0.6, 1.3])
def test_read_gaps_hold_under_a_clock_offset(scoped, shift_ms):
    """Placed ``shift_ms`` off the host's clock, as chip runs place the
    device's ops up to ~1.2 ms early, each between-step gap is still
    the read's whole: ``loop.read_ms_per_step`` takes whole gaps."""
    _, prog = scoped
    trace, shift = prog.ctx.trace, shift_ms * 1e-3
    ops = [dataclasses.replace(e, start=e.start + shift, end=e.end + shift)
           for e in trace.devices[0]]
    gaps = tr.idle_gaps(ops, ops[0].start, ops[-1].end)
    between = sorted(gaps, key=lambda g: g[0] - g[1])[:STEPS - 1]
    assert all(b - a > 1e-3 for a, b in between)
    for a, b in between:
        assert tr.label_gap(trace.host, a, b) == "repro.loop.read"


def test_trace_path_is_where_run_writes_it(scoped, monkeypatch):
    path, prog = scoped
    monkeypatch.setattr(scopes, "OUT", path.parent.parent.parent)
    monkeypatch.setattr(scopes, "_last", [])
    assert scopes.trace_path(prog.ctx) == path
    assert scopes.of(prog.ctx).path == path
    assert scopes.of(prog.ctx) is scopes.of(prog.ctx)


def test_metrics_read_the_scoped_trace(scoped, monkeypatch):
    path, prog = scoped
    monkeypatch.setattr(scopes, "OUT", path.parent.parent.parent)
    monkeypatch.setattr(scopes, "_last", [])
    ctx = prog.ctx
    read = {m: bench.metric_reader(m)(ctx) for m in NEW_METRICS}
    assert read["loop.host_reads_per_step.online"] == 4
    gap = bench.metric_reader("loop.gap_ms_per_step")(ctx)
    # every gap after a program is the read's; the window's first gap,
    # before its first program, is the dispatch's
    assert (STEPS - 1) / STEPS * gap < read["loop.read_ms_per_step.online"] \
        <= gap
    dispatch = [e.end - e.start
                for e in scopes.window_spans(ctx, "repro.loop.dispatch")]
    assert read["loop.dispatch_ms_per_step.online"] == pytest.approx(
        1e3 * sum(dispatch) / STEPS)
    assert 0 < read["loop.dispatch_ms_per_step.online"] < gap
    for m, scope in SCOPE_METRICS.items():
        assert read[f"{m}.train"] == read[f"{m}.online"] == pytest.approx(
            1e3 * prog.parts.get(scope, 0.0) / STEPS)
        assert read[f"{m}.online"] >= 0
    assert read["model.embed_ms_per_step.online"] > 0
    assert read["model.layer_slice_ms_per_step.online"] > 0
