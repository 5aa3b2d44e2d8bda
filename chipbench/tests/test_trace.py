"""trace.py against a small trace recorded on a TPU v5e: four fused MGD
steps of mistral-nemo-12b (4 layers, 1x128 tokens) through repro.train,
one step per program."""
import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as tr

DATA = Path(__file__).parent / "data" / "online-4steps.xplane.pb.gz"
STEPS = 4
PAIR_CALLS_PER_STEP = 4 * 7 + 1        # 7 weight products per layer + head
UPDATE_CALLS_PER_STEP = 12 - 1         # every leaf but the 1-D final norm


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    pytest.importorskip("jax")
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.load(path)


@pytest.fixture(scope="module")
def window(trace):
    ops = trace.devices[0]
    return min(e.start for e in ops), max(e.end for e in ops)


def test_planes(trace):
    assert list(trace.devices) == [0]
    assert trace.host and all(a.start <= b.start for a, b in
                              zip(trace.host, trace.host[1:]))


def test_busy_is_the_union_of_op_intervals(trace, window):
    t0, t1 = window
    busy = tr.busy_seconds(trace.devices[0], t0, t1)
    # the same union on a 100 ns grid
    res = 1e-7
    grid = np.zeros(int((t1 - t0) / res) + 2, bool)
    for e in trace.devices[0]:
        grid[int((e.start - t0) / res):int(np.ceil((e.end - t0) / res))] = True
    assert busy == pytest.approx(grid.sum() * res, rel=2e-3)
    assert 0 < busy < t1 - t0


def test_kernel_time_and_calls(trace, window):
    t0, t1 = window
    ops = trace.devices[0]
    s, calls = tr.op_seconds(ops, t0, t1, ("perturbed_matmul_pair",))
    assert calls == STEPS * PAIR_CALLS_PER_STEP
    assert s == pytest.approx(sum(e.end - e.start for e in ops
                                  if e.text.startswith("%perturbed_matmul_pair.")))
    _, calls = tr.op_seconds(ops, t0, t1, ("mgd_update_window",))
    assert calls == STEPS * UPDATE_CALLS_PER_STEP


def test_idle_gaps_fill_what_busy_leaves(trace, window):
    t0, t1 = window
    ops = trace.devices[0]
    gaps = tr.idle_gaps(ops, t0, t1)
    busy = tr.busy_seconds(ops, t0, t1)
    assert sum(b - a for a, b in gaps) == pytest.approx(t1 - t0 - busy)
    for e in ops:
        for a, b in gaps:
            assert e.end <= a + 1e-12 or e.start >= b - 1e-12
    # the three longest gaps are the host's turns between the programs
    longest = sorted(b - a for a, b in gaps)[-3:]
    assert all(g > 1e-3 for g in longest)


def test_self_times_add_up_to_busy(trace, window):
    t0, t1 = window
    own = tr.self_seconds(trace.devices[0], t0, t1)
    assert sum(own.values()) == pytest.approx(
        tr.busy_seconds(trace.devices[0], t0, t1), rel=1e-6)
    assert max(own, key=own.get) == "perturbed_matmul_pair"


def test_op_name():
    assert tr.op_name("%perturbed_matmul_pair.88 = (bf16[128,4096]) "
                      "custom-call(u32[1] %b)") == "perturbed_matmul_pair"
    assert tr.op_name("%while.42 = (s32[]) while(...)") == "while"


@pytest.mark.parametrize("kind", ["online", "train"])
def test_metric_readers_on_the_recorded_trace(trace, window, kind):
    from chipbench import bench
    from chipbench.context import Context
    cell = bench.resolve("mistral-nemo-12b.online-1x128")
    peaks = bench.load_json("", "peaks")["devices"]["TPU v5 lite"]
    t0, t1 = window
    ctx = Context(trace, t0, t1, STEPS, 1, cell.config, cell.traffic,
                  peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
    split = ("perturbed_matmul_pair_roofline", "mgd_update_window_roofline",
             "step.mfu", "model.other_ms_per_step")
    read = {m: bench.metric_reader(f"{m}.{kind}")(ctx) for m in split}
    read.update({m: bench.metric_reader(m)(ctx) for m in (
        "device.idle_frac", "loop.gap_ms_per_step")})
    for m in ("perturbed_matmul_pair_roofline", "mgd_update_window_roofline",
              "step.mfu"):
        assert 0 < read[m] < 100, (m, read[m])
    assert 0 <= read["device.idle_frac"] < 100
    assert read["model.other_ms_per_step"] > 0
    assert read["loop.gap_ms_per_step"] == pytest.approx(
        1e3 * (t1 - t0) * read["device.idle_frac"] / 100 / STEPS)


def test_readers_find_nothing_in_a_trace_without_the_kernels(trace):
    from chipbench import bench
    from chipbench.context import Context
    cell = bench.resolve("qwen3-14b.train-4x512")
    empty = tr.Trace(devices={0: []}, host=trace.host)
    ctx = Context(empty, 0.0, 1.0, STEPS, 1, cell.config, cell.traffic,
                  1.0, 1.0)
    assert ctx.pair_roofline() is None
    assert ctx.update_roofline() is None
    assert ctx.other_ms_per_step() is None
