"""The MLA flash-attention kernel's roofline reader
(``metrics/mla_flash_attention_roofline.dsv3.py``): its counts against
``counts_moe_mla`` and the published shapes, and its reading of a trace
without the kernel."""
import gzip
import importlib.util
import shutil
from pathlib import Path

import numpy as np
import pytest

from chipbench import bench
from chipbench import counts_moe_mla as cm

READER = bench.HERE / "metrics" / "mla_flash_attention_roofline.dsv3.py"
DATA = Path(__file__).parent / "data" / "online-4steps.xplane.pb.gz"
CELL = "deepseek-v3.train-1x8192"


def _reader():
    spec = importlib.util.spec_from_file_location("mla_reader", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_calls_hold_the_attention_term():
    """10 calls a step (5 layers × 2 probe streams) whose FLOPs add up to
    the attention term of ``counts_moe_mla.model_flops``, and whose bytes
    read q, k_nope, the shared k_rope and v once and write the output
    once, in bf16."""
    c = bench.load_json("configs", "deepseek-v3")
    t = bench.load_json("traffic", "train-1x8192")
    calls = _reader().calls(c, t)
    assert len(calls) == 10
    d, tokens = cm.dims(c), 8192
    rows = np.zeros((d["moe"], 2, d["held"]))
    weights = sum(2 * tokens * k * n for k, n in cm.pair_matmuls(c))
    router = 2 * tokens * d["d"] * d["experts"] * d["moe"]
    attn = cm.model_flops(c, 1, tokens, rows) - 2 * (weights + router)
    assert sum(f for f, _ in calls) == attn
    assert attn == pytest.approx(2.75e13, rel=1e-3)
    per_token = 128 * 192 + 128 * 128 + 64 + 128 * 128 + 128 * 128
    assert calls[0][1] == tokens * per_token * 2


def test_reads_nothing_without_the_kernel(tmp_path):
    """A trace with no ``mla_flash_attention`` call (a dense program, or
    a DeepSeek-V3 program from before the kernel) reads None, not an
    error."""
    pytest.importorskip("jax")
    from chipbench import trace as tr
    from chipbench.context import Context

    path = tmp_path / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = tr.load(path)
    ops = trace.devices[0]
    cell = bench.resolve(CELL)
    ctx = Context(trace, min(e.start for e in ops), max(e.end for e in ops),
                  4, 1, cell.config, cell.traffic, 1.0, 1.0)
    assert bench.metric_reader("mla_flash_attention_roofline.dsv3")(ctx) is None
