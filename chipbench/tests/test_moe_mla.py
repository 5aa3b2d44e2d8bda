"""The DeepSeek-V3 cell at a tiny size on the CPU: a run reads ``correct``;
the reference with each planted fault of ``references/moe_mla.planted``,
and in float8, does not; the counts and the counter's readers."""
import copy

import jax
import numpy as np
import pytest

from chipbench import bench, check

pytest.importorskip("repro")
from chipbench import counts_moe_mla as cm  # noqa: E402
from chipbench import run  # noqa: E402
from chipbench.references import moe_mla  # noqa: E402

CELL = "deepseek-v3.train-1x8192"
SEED = 2 ** 31 + 11
# At the tiny size both sides compute in float32, so the program and the
# reference agree to ~1e-6 nats, and a fault moves a number far more than
# that.  (The cell's own limits are set for bfloat16 at the published
# widths, where only change_gap is compared: PERF.md section 4.)
TINY_LIMITS = {"loss_gap_nats": 0.004, "grad_gap_nats": 0.004,
               "change_gap": 0.5}


def tiny_cell():
    """The cell at widths the CPU runs: 1 dense and 2 MoE layers, 16
    experts in 4 groups (2 kept), top-4, this share holding experts
    4-7 and a 128-id slice of the vocabulary; two steps per program as
    in the cell, so the check follows 4 steps."""
    cell = copy.deepcopy(bench.resolve(CELL))
    cell.config.update(
        hidden_size=64, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=64,
        n_routed_experts_published=16, n_routed_experts=4,
        first_held_expert=4, n_group=4, topk_group=2, num_experts_per_tok=4,
        vocab_held=128, num_hidden_layers=3, torch_dtype="float32")
    cell.traffic.update(batch=2, seq_len=32)
    cell.limits = dict(TINY_LIMITS)
    return cell


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)


@pytest.fixture(scope="module")
def sound():
    """(the cell, its seeds, the sound reference's run)."""
    cell = tiny_cell()
    seeds = run.derive_seeds(SEED)
    return cell, seeds, check.follow(cell, seeds)


def test_sound_run_is_correct():
    res = run.run_cell(tiny_cell(), SEED, 0.2, False, jax.devices()[:1],
                       kernel_impl="interpret")
    assert res["correct"], res["checks"]
    assert res["checks"]["loss_gap_nats"]["value"] < 1e-5


@pytest.mark.parametrize("fault", moe_mla.FAULTS + ("control_fp8",))
def test_each_planted_fault_fails(sound, fault):
    """In the program's place, the reference with the fault fails at
    least one compared number under the cell's limits."""
    cell, seeds, ref = sound
    if fault == "control_fp8":
        bad = check.follow(cell, seeds, compute="float8_e4m3fn")
    else:
        with moe_mla.planted(fault):
            bad = check.follow(cell, seeds)
    nums = check.numbers(cell, bad, ref)
    assert not check.passed(nums), nums


def test_reference_leaves_are_the_programs():
    """Paths, shapes and order of the reference's leaves are the
    program's parameter tree's (the leaf ids both sides hash)."""
    from repro.models import model_init

    cell = tiny_cell()
    cfg = bench.program(cell.config).arch_config(cell.config)
    arch = moe_mla.Arch.from_config(cell.config)
    shapes = jax.eval_shape(lambda: model_init(cfg, jax.random.PRNGKey(0)))
    flat = [("/".join(k.key for k in path), leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert flat == [(p, s) for p, s, _ in moe_mla.leaves(arch)]


def test_counts_at_published_widths():
    """41 probe-pair calls (8 per layer: 5 MLA projections and an MLP or
    shared expert; the head), 12 grouped calls, and the update's leaves
    add up to the configuration's parameters."""
    c = bench.resolve(CELL).config
    assert len(cm.pair_calls(c, 8192)) == 41
    rows = np.full((4, 2, 8), 256)
    calls = cm.grouped_calls(c, rows)
    assert len(calls) == 12
    assert calls[0][0] == 2 * 2 * 8 * 256 * 7168 * 2048
    elems = sum(n for n, _ in cm.update_leaves(c))
    d = cm.dims(c)
    assert elems == 3156433920 - d["d"]            # all but ln_f
    assert cm.model_flops(c, 1, 8192, rows) == pytest.approx(82.8e12,
                                                            rel=0.02)


class _Ctx:
    """What a reader is handed, for a traced window [t0, t1]."""

    def __init__(self, cell, t0, t1):
        self.config, self.traffic = cell.config, cell.traffic
        self.t0, self.t1 = t0, t1


def test_counter_readers(tmp_path, monkeypatch):
    """The readers parse the loop's ``repro.moe.rows`` spans from a trace,
    and read None from a trace without them."""
    from chipbench import scopes

    cell = tiny_cell()
    spans = [(1.0, "4 0 2 2  0 1 3 4"), (2.0, "1 1 1 1  2 2 2 2")]

    class Event:
        def __init__(self, t, values):
            self.name, self.start_ns = cm.ROWS_SPAN, int(t * 1e9)
            self.stats = [("shape", "1x2x4"), ("values", values)]

    class Data:
        planes = [type("P", (), {"name": "/host:CPU", "lines": [
            type("L", (), {"events": [Event(*s) for s in spans]})]})]

    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda _: Data))
    monkeypatch.setattr(scopes, "trace_path", lambda ctx: tmp_path / "t")
    rows = cm.window_rows(_Ctx(cell, 0.5, 2.5))
    assert rows.shape == (2, 1, 2, 4)
    read = bench.metric_reader("moe.rows_max_over_mean.dsv3")
    assert read(_Ctx(cell, 0.5, 2.5)) == pytest.approx(
        np.mean([4 / 2, 4 / 2, 1, 1]))
    assert read(_Ctx(cell, 2.5, 3.0)) is None
    monkeypatch.setattr(scopes, "trace_path", lambda ctx: None)
    assert read(_Ctx(cell, 0.5, 2.5)) is None
