"""Kernel layer (kernels/flash_attention.py): the MLA flash-attention
kernel's roofline share in the DeepSeek-V3 cells — for each call (one per
layer and probe stream) the larger of its FLOPs (q·kᵀ over the nope and
rope dims and p·v over the value dims, on the causal half with its
diagonal) over the bf16 peak and its bytes (q, k_nope, the shared k_rope
and v read once, the output written once, bf16) over the HBM bandwidth,
over its device time; moves train_tokens_per_s.  None without the
kernel."""
from chipbench import counts_moe_mla as cm
from chipbench.counts import ACT_BYTES

KERNEL = "mla_flash_attention"


def calls(c: dict, traffic: dict) -> list:
    """(flops, bytes) of each kernel call of one step."""
    d = cm.dims(c)
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    h, nope, rope, v = d["heads"], d["nope"], d["rope"], d["v"]
    flops = 2 * h * (nope + rope + v) * b * s * (s + 1) // 2
    elems = b * s * (h * (nope + rope) + h * nope + rope + 2 * h * v)
    streams = 2 if traffic["mode"] == "central" else 1
    return [(flops, elems * ACT_BYTES)] * (streams * d["layers"])


def read(ctx):
    return ctx.roofline(KERNEL, calls(ctx.config, ctx.traffic))
