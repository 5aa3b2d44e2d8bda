"""A cell of the benchmark cut to a size the CPU tests can run."""
import copy

from chipbench import bench

CELLS = {"qwen3-14b": "qwen3-14b.train-4x512",
         "mistral-nemo-12b": "mistral-nemo-12b.online-1x128"}


def tiny_cell(config: str, workload: str | None = None, **traffic):
    cell = copy.deepcopy(bench.resolve(workload or CELLS[config]))
    cell.traffic.update(traffic)
    cell.config.update(hidden_size=64, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16,
                       intermediate_size=128, vocab_size=128,
                       num_hidden_layers=2, torch_dtype="float32")
    cell.traffic.update(batch=2, seq_len=32)
    return cell
