"""A run with the timed path broken underneath reads ``correct`` false.

Each test skips only the harness's look for a chip: it drives the rest
of a run (set-up, window, reference, comparison) on the CPU at a small
size, with the Pallas kernels interpreted, once sound and once for each
fault the cells can have."""
import jax
import pytest

from chipbench.tests.tiny import tiny_cell

pytest.importorskip("repro")
from chipbench import run  # noqa: E402


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)


def _run(cell, **kw):
    return run.run_cell(cell, 2 ** 31 + 11, 0.2, False, jax.devices()[:1],
                        kernel_impl="interpret", **kw)


def test_sound_run_is_correct():
    res = _run(tiny_cell("qwen3-14b"))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def test_state_left_unchanged(monkeypatch):
    import repro.core.mgd as core

    real = core.build_mgd_step

    def frozen(*a, **k):
        step = real(*a, **k)

        def step_fn(params, state, batch):
            _, state, m = step(params, state, batch)
            return params, state, m
        return step_fn

    monkeypatch.setattr(core, "build_mgd_step", frozen)
    res = _run(tiny_cell("mistral-nemo-12b"))
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out():
    from repro.models import make_transformer_probe_fn
    cell = tiny_cell("qwen3-14b")
    real = make_transformer_probe_fn(run.arch_config(cell.config))

    def half(params, batch, probe):
        rows = batch["tokens"].shape[0] // 2
        return real(params, jax.tree_util.tree_map(lambda x: x[:rows], batch),
                    probe)

    res = _run(cell, probe_fn=half)
    assert not res["correct"], res["checks"]
