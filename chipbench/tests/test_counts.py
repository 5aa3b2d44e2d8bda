"""counts.py against numbers worked out by hand from the published shapes."""
import pytest

from chipbench import bench, counts


@pytest.fixture(scope="module")
def qwen():
    return bench.load_json("configs", "qwen3-14b")


@pytest.fixture(scope="module")
def nemo():
    return bench.load_json("configs", "mistral-nemo-12b")


def test_qwen_layer_pair_flops_at_2048_tokens(qwen):
    # one layer: wq 5120x5120, wk/wv 5120x1024, wo 5120x5120, three
    # 5120x17408 MLP products = 330,301,440 weights; 2 probes x 2 FLOP
    calls = counts.pair_calls(qwen, 2048)
    layer = sum(f for f, _ in calls[:7])
    assert layer == 2 * 2 * 2048 * 330_301_440
    assert layer == pytest.approx(2.706e12, rel=1e-3)


def test_nemo_online_step_flops(nemo):
    # 4 layers of 272,629,760 weights plus the 5120x131072 head, 128 tokens
    weights = sum(f for f, _ in counts.pair_calls(nemo, 128))
    assert weights == 2 * 2 * 128 * (4 * 272_629_760 + 5120 * 131072)
    assert weights == pytest.approx(9.02e11, rel=1e-3)
    attn = counts.model_flops(nemo, 1, 128) - weights
    # QK^T and PV on the causal half, 32 heads of 128, 4 layers, 2 probes
    assert attn == 2 * 2 * 2 * 32 * 128 * (128 * 129 // 2) * 4


def test_nemo_update_bytes(nemo):
    # every bf16 leaf read and written once: 2 x 4.86 GB
    total = sum(b for _, b in counts.update_calls(nemo))
    assert total == pytest.approx(9.73e9, rel=1e-3)


def test_pair_bytes_read_w_once(qwen):
    f, b = counts.pair_calls(qwen, 2048)[-1]        # the head
    assert b == 5120 * 151936 * 2 + 2 * 2048 * 5120 * 2 + 2 * 2048 * 151936 * 2


def test_roofline_bound_names():
    (t, bound), = counts.roofline_seconds([(197e12, 1.0)], 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    (t, bound), = counts.roofline_seconds([(1.0, 819e9)], 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")


@pytest.mark.parametrize("chunk,steps,observed", [
    (1, 3, [0, 1, 2]), (2, 4, [1, 3]), (3, 3, [2]), (4, 4, [3]), (5, 5, [4])])
def test_checked_steps_are_whole_chunks(chunk, steps, observed):
    from chipbench import check
    assert check.check_steps({"chunk": chunk}) == steps
    assert check.observed_steps({"chunk": chunk}) == observed
