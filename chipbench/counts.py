"""Operations and bytes that one MGD step needs, from shapes alone.

These count what the algorithm needs, whatever implements it: a central
probe pair multiplies each token's activations by W ± Δθ·s in bfloat16
products (2 probes × 2·M·K·N operations) and needs W read once, both
activation streams read and both outputs written; the update needs W read
and written once in the weights' dtype.  Model FLOPs are those of the
two probe forwards: weight products and causal attention (MGD has no
backward pass; the update is not counted).

Its functions are a dense GQA decoder's (the ``dense_gqa`` architecture);
a new architecture brings its counts and readers as new files, and its
readers use ``Context.roofline(kernel, calls)``, which is generic.
"""
from __future__ import annotations

ACT_BYTES = 2      # bfloat16 activations between the kernels
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(config: dict) -> dict:
    c = config
    return {"d": c["hidden_size"], "q": c["num_attention_heads"] * c["head_dim"],
            "kv": c["num_key_value_heads"] * c["head_dim"],
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "layers": c["num_hidden_layers"], "heads": c["num_attention_heads"],
            "head_dim": c["head_dim"], "qk_norm": c["model_type"] == "qwen3",
            "wbytes": DTYPE_BYTES[c["torch_dtype"]]}


def weight_matmuls(config: dict) -> list:
    """(K, N) of every weight product of one forward, per layer then head."""
    d = dims(config)
    layer = [(d["d"], d["q"]), (d["d"], d["kv"]), (d["d"], d["kv"]),
             (d["q"], d["d"]), (d["d"], d["ff"]), (d["d"], d["ff"]),
             (d["ff"], d["d"])]
    return layer * d["layers"] + [(d["d"], d["vocab"])]


def pair_calls(config: dict, tokens: int) -> list:
    """(flops, bytes) of each probe-pair kernel call of one step."""
    wb = dims(config)["wbytes"]
    return [(2 * 2 * tokens * k * n,
             k * n * wb + 2 * tokens * k * ACT_BYTES + 2 * tokens * n * ACT_BYTES)
            for k, n in weight_matmuls(config)]


def update_leaves(config: dict) -> list:
    """Element counts of the leaves the update kernel writes (ndim ≥ 2)."""
    d = dims(config)
    L = d["layers"]
    leaves = [d["d"] * d["vocab"], d["vocab"] * d["d"], L * d["d"], L * d["d"]]
    if d["qk_norm"]:
        leaves += [L * d["head_dim"], L * d["head_dim"]]
    leaves += [L * k * n for k, n in weight_matmuls(config)[:7]]
    return leaves


def update_calls(config: dict) -> list:
    """(flops, bytes) of each update kernel call: W read + W written, and
    one multiply-add per element."""
    wb = dims(config)["wbytes"]
    return [(2 * n, 2 * n * wb) for n in update_leaves(config)]


def model_flops(config: dict, batch: int, seq_len: int) -> float:
    """FLOPs of the two probe forwards of one step."""
    d = dims(config)
    tokens = batch * seq_len
    weights = sum(2 * tokens * k * n for k, n in weight_matmuls(config))
    # QKᵀ and PV over the causal half: S(S+1)/2 pairs per head and sequence
    attn = (2 * 2 * batch * d["heads"] * d["head_dim"] * seq_len * (seq_len + 1)
            // 2 * d["layers"])
    return 2 * (weights + attn)


def roofline_seconds(calls: list, peak_flops: float, peak_bw: float):
    """Least time of each call and which bound sets it."""
    out = []
    for flops, nbytes in calls:
        tc, tm = flops / peak_flops, nbytes / peak_bw
        out.append((max(tc, tm), "compute" if tc >= tm else "memory"))
    return out
