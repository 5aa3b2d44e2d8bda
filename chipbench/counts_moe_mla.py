"""Operations and bytes of one MGD step of the DeepSeek-V3 chip share
(``moe_mla``), from shapes and the program's routed-row counter.

As ``counts.py`` counts them for a dense decoder: a central probe pair
multiplies each row by W ± Δθ·s in bfloat16 products (2·M·K·N operations
per probe) and needs W read once, both activation streams read and both
outputs written; the update reads and writes each ndim ≥ 2 leaf once in
its dtype.  Three kinds of call:

* ``pair_calls``: the probe-pair kernel over every token: each layer's
  five MLA projections, the dense layers' MLP, the MoE layers' shared
  expert, and the head;
* ``grouped_calls``: the grouped probe-pair kernel, three calls per MoE
  layer (gate, up, down): the held experts' W read once per pair, and the
  bf16 products of the rows each stream actually routed to each held
  expert (the counter);
* ``update_calls``: every ndim ≥ 2 leaf, the expert banks and the
  float32 router among them.

Model FLOPs are those of the two probe forwards: weight products over
every token (the router's included), the routed rows' expert products,
and causal MLA attention (q·k over qk_nope + qk_rope dims, p·v over v).

The counter is the span ``repro.moe.rows`` that the training loop writes
on the host timeline after each device program while a profiler records:
its stats ``shape`` (MoE layers × streams × held experts) and ``values``
(space-separated), the rows of the program's last step.  ``window_rows``
reads it from the run's trace file; where a program runs several steps,
the readers take its last step's rows for each of them.
"""
from __future__ import annotations

import numpy as np

from . import scopes
from .counts import ACT_BYTES, DTYPE_BYTES

ROWS_SPAN = "repro.moe.rows"


def dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "heads": c["num_attention_heads"],
            "q_lora": c["q_lora_rank"], "kv_lora": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "v": c["v_head_dim"], "ff": c["intermediate_size"],
            "expert_ff": c["moe_intermediate_size"],
            "shared_ff": c["moe_intermediate_size"] * c["n_shared_experts"],
            "experts": c["n_routed_experts_published"],
            "held": c["n_routed_experts"], "top_k": c["num_experts_per_tok"],
            "vocab": c["vocab_held"], "dense": c["first_k_dense_replace"],
            "moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "layers": c["num_hidden_layers"],
            "wbytes": DTYPE_BYTES[c["torch_dtype"]]}


def attn_matmuls(c: dict) -> list:
    """(K, N) of one layer's MLA projections."""
    d = dims(c)
    qd = d["nope"] + d["rope"]
    return [(d["d"], d["q_lora"]), (d["q_lora"], d["heads"] * qd),
            (d["d"], d["kv_lora"] + d["rope"]),
            (d["kv_lora"], d["heads"] * (d["nope"] + d["v"])),
            (d["heads"] * d["v"], d["d"])]


def pair_matmuls(c: dict) -> list:
    """(K, N) of every probe-pair kernel call of one forward."""
    d = dims(c)
    mlp = [(d["d"], d["ff"]), (d["d"], d["ff"]), (d["ff"], d["d"])]
    shared = [(d["d"], d["shared_ff"]), (d["d"], d["shared_ff"]),
              (d["shared_ff"], d["d"])]
    return ((attn_matmuls(c) + mlp) * d["dense"]
            + (attn_matmuls(c) + shared) * d["moe"] + [(d["d"], d["vocab"])])


def _pair(tokens, k, n, wb):
    return (2 * 2 * tokens * k * n,
            k * n * wb + 2 * tokens * k * ACT_BYTES + 2 * tokens * n * ACT_BYTES)


def pair_calls(c: dict, tokens: int) -> list:
    """(flops, bytes) of each probe-pair kernel call of one step."""
    wb = dims(c)["wbytes"]
    return [_pair(tokens, k, n, wb) for k, n in pair_matmuls(c)]


def expert_matmuls(c: dict) -> list:
    d = dims(c)
    return [(d["d"], d["expert_ff"]), (d["d"], d["expert_ff"]),
            (d["expert_ff"], d["d"])]


def grouped_calls(c: dict, rows) -> list:
    """(flops, bytes) of each grouped kernel call of one step, from
    ``rows`` [MoE layers, 2 streams, held experts]: each stream's routed
    rows through its experts, the held W read once per pair."""
    d = dims(c)
    out = []
    for layer in np.asarray(rows, np.float64):
        routed = float(layer.sum())        # rows of both streams
        for k, n in expert_matmuls(c):
            out.append((2 * routed * k * n,
                        d["held"] * k * n * d["wbytes"]
                        + routed * (k + n) * ACT_BYTES))
    return out


def update_leaves(c: dict) -> list:
    """(elements, bytes per element) of each leaf the update kernel
    writes (ndim ≥ 2)."""
    d = dims(c)
    wb = d["wbytes"]
    out = [(d["d"] * d["vocab"], wb), (d["vocab"] * d["d"], wb)]
    for n, mlp in ((d["dense"], [(d["d"], d["ff"])] * 2 + [(d["ff"], d["d"])]),
                   (d["moe"], [(d["d"], d["shared_ff"])] * 2
                    + [(d["shared_ff"], d["d"])])):
        if not n:
            continue
        out += [(n * d["kv_lora"], wb), (n * d["q_lora"], wb),
                (n * d["d"], wb), (n * d["d"], wb)]
        out += [(n * k * m, wb) for k, m in attn_matmuls(c) + mlp]
    out += [(d["moe"] * d["held"] * k * n, wb) for k, n in expert_matmuls(c)]
    out.append((d["moe"] * d["d"] * d["experts"], 4))     # router, float32
    return out


def update_calls(c: dict) -> list:
    """(flops, bytes) of each update kernel call: W read + W written, and
    one multiply-add per element."""
    return [(2 * n, 2 * n * b) for n, b in update_leaves(c)]


def model_flops(c: dict, batch: int, seq_len: int, rows) -> float:
    """FLOPs of the two probe forwards of one step; ``rows`` as for
    ``grouped_calls``."""
    d = dims(c)
    tokens = batch * seq_len
    weights = sum(2 * tokens * k * n for k, n in pair_matmuls(c))
    router = 2 * tokens * d["d"] * d["experts"] * d["moe"]
    routed = sum(2 * float(np.sum(rows)) * k * n for k, n in
                 expert_matmuls(c))           # both streams' rows
    pairs = batch * seq_len * (seq_len + 1) // 2
    attn = (2 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * pairs
            * d["layers"])
    return 2 * (weights + router + attn) + routed


def window_rows(ctx):
    """[programs, MoE layers, streams, held experts] of the counter spans
    in the traced window; None where the run's trace holds none."""
    from jax.profiler import ProfileData

    path = scopes.trace_path(ctx)
    if path is None:
        return None
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                t = e.start_ns * 1e-9
                if e.name == ROWS_SPAN and ctx.t0 <= t <= ctx.t1:
                    stats = dict(e.stats)
                    shape = [int(x) for x in str(stats["shape"]).split("x")]
                    vals = [int(x) for x in str(stats["values"]).split()]
                    found.append((t, np.asarray(vals).reshape(shape)))
    if not found:
        return None
    return np.stack([r for _, r in sorted(found, key=lambda x: x[0])])


def mean_rows(ctx):
    """The counter averaged over the window's programs; None without
    it."""
    rows = window_rows(ctx)
    return None if rows is None else rows.mean(axis=0)
