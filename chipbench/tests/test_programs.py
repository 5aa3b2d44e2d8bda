"""A configuration builds its own program: ``programs/<reference>.py``
gives the program's model configuration, and a new architecture is new
files only."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import bench

pytest.importorskip("repro")
from repro.models.config import ArchConfig  # noqa: E402

from chipbench.programs import dense_gqa  # noqa: E402

COMMON = dict(
    family="dense", d_model=5120, n_kv_heads=8, d_head=128, qkv_bias=False,
    rope_theta=1000000.0, mrope_sections=None, n_experts=0,
    n_experts_active=0, n_shared_experts=0, moe_group_size=256,
    moe_capacity_factor=1.25, use_mla=False, q_lora_rank=0, kv_lora_rank=0,
    qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0, ssm_state=0,
    ssm_expand=2, ssm_head_dim=64, attn_every=0, n_codebooks=0,
    dtype="bfloat16", attn_q_block=512, attn_kv_block=512,
    attn_impl="masked", la_chunk=32, fsdp=False, seq_parallel=False,
    scan_layers=True, tie_embeddings=False)

# the ArchConfig that run.py's arch_config built for each file, field for
# field
EXPECTED = {
    "qwen3-14b": ArchConfig(
        name="qwen3-14b", n_layers=4, n_heads=40, d_ff=17408, vocab=151936,
        qk_norm=True, norm_eps=1e-06, **COMMON),
    "mistral-nemo-12b": ArchConfig(
        name="mistral-nemo-12b", n_layers=4, n_heads=32, d_ff=14336,
        vocab=131072, qk_norm=False, norm_eps=1e-05, **COMMON),
}


@pytest.mark.parametrize("config", sorted(EXPECTED))
def test_dense_gqa_builds_the_old_arch_config(config):
    c = bench.load_json("configs", config)
    assert bench.program(c) is dense_gqa
    assert dense_gqa.arch_config(c) == EXPECTED[config]


def test_run_names_no_model_key():
    text = (bench.HERE / "run.py").read_text()
    for key in ("head_dim", "intermediate_size", "model_type", "hidden_size",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "num_hidden_layers", "rms_norm_eps", "rope_theta",
                "torch_dtype", "registry", "get_config"):
        assert key not in text, key


NEW_ARCH = r"""
import json, sys
import jax
from chipbench import bench, run
from chipbench.tests.tiny import tiny_cell
run.use_compile_cache = lambda: None
cell = tiny_cell("dense-copy", "dense-copy.train-4x512")
assert bench.program(cell.config).__name__ == "chipbench.programs.dense_copy"
assert bench.reference(cell.config).__name__ == \
    "chipbench.references.dense_copy"
res = run.run_cell(cell, 2 ** 31 + 29, 0.2, False, jax.devices()[:1],
                   kernel_impl="interpret")
print(json.dumps(res))
"""


def test_a_new_architecture_is_new_files_only(tmp_path):
    """A copy of the checkout gains a reference and a program module under
    a new architecture name, a configuration that names them, its limits
    and a cell; with no harness file changed, a run of the cell at a tiny
    size on the CPU reads correct."""
    root = tmp_path / "checkout"
    shutil.copytree(bench.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    base = root / "chipbench"
    shutil.copy(base / "references" / "dense_gqa.py",
                base / "references" / "dense_copy.py")
    shutil.copy(base / "programs" / "dense_gqa.py",
                base / "programs" / "dense_copy.py")
    cfg = json.loads((base / "configs" / "qwen3-14b.json").read_text())
    cfg["reference"] = "dense_copy"
    (base / "configs" / "dense-copy.json").write_text(json.dumps(cfg))
    (base / "limits" / "dense-copy.train-4x512.json").write_text(
        (base / "limits" / "qwen3-14b.train-4x512.json").read_text())
    data = bench.load_benchmark()
    data["configs"].append(dict(data["configs"][0], name="dense-copy",
                                file="chipbench/configs/dense-copy.json"))
    data["workloads"].append({"name": "dense-copy.train-4x512",
                              "config": "dense-copy", "traffic": "train-4x512",
                              "chips": 1, "why": "a new architecture"})
    for m in data["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("dense-copy.train-4x512")
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    for path in bench.HERE.rglob("*.py"):
        rel = path.relative_to(bench.HERE)
        if "__pycache__" not in rel.parts:
            assert (base / rel).read_bytes() == path.read_bytes(), rel
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(bench.ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", NEW_ARCH], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
