"""DeepSeek-V3 on the fused MGD path: the grouped probe-pair kernel over a
bank of held experts, the published routing, the dropless chip-share
expert layer, YaRN, and the fused ≡ materializing contract for the whole
step (f32, interpret-mode kernels)."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs import get_smoke_config
from repro.core import perturbations as pert
from repro.core.utils import leaf_id_tree
from repro.kernels import ops
from repro.kernels.grouped_matmul import group_layout
from repro.models import (make_transformer_probe_fn, model_init, model_loss,
                          supports_fused_probe)
from repro.models import moe
from repro.models.rope import yarn_freqs, yarn_softmax_scale

SMOKE = get_smoke_config("deepseek-v3-671b")


def _laid_out(sizes, bm, k, key):
    """Per-stream buffers of ``sizes`` [S, E] rows of random data laid out
    by ``group_layout`` (zeros elsewhere), and each group's rows."""
    sizes = jnp.asarray(sizes, jnp.int32)
    start, nblk, last = group_layout(sizes, bm)
    rows = bm * int(jnp.max(jnp.sum(nblk, axis=1)) + 1)
    bufs, groups = [], []
    for s in range(sizes.shape[0]):
        buf = np.zeros((rows, k), np.float32)
        g = []
        for e in range(sizes.shape[1]):
            r0 = int(start[s, e]) * bm
            n = int(sizes[s, e])
            buf[r0:r0 + n] = np.asarray(jax.random.normal(
                jax.random.fold_in(key, 100 * s + e), (n, k)))
            g.append(slice(r0, r0 + n))
        bufs.append(jnp.asarray(buf))
        groups.append(g)
    return bufs, (start, nblk, last), groups


@pytest.mark.parametrize("sizes", [
    [[5, 0, 130, 17], [0, 33, 1, 200]],     # ragged, empty, 2-block groups
    [[0, 0, 0, 9], [128, 0, 0, 0]],         # one stream's group only
])
def test_grouped_kernel_bit_identical_to_per_expert_products(sizes):
    """f32, interpret mode: each stream's rows of expert e times
    W[e] ± Δθ·s_e, the signs of the stacked [L, E, K, N] leaf's layer-1
    slice, materialized by the host generator."""
    n_e, k, n, bm, dtheta, layer = 4, 64, 96, 128, 0.01, 1
    bank = jax.random.normal(jax.random.PRNGKey(1), (2, n_e, k, n)) * 0.1
    bufs, lay, groups = _laid_out(sizes, bm, k, jax.random.PRNGKey(2))
    seed, step, lid = 9, 4, 3
    lseed = pert.shifted_leaf_seed(pert.leaf_seed(seed, step, lid),
                                   layer * n_e * k * n)
    blocks = -(-max(max(r) for r in sizes) // bm)
    ys = ops.perturbed_grouped_matmul(
        bufs, bank[layer], lseed, *lay, signs=(1.0, -1.0), dtheta=dtheta,
        bm=bm, blocks=blocks, impl="interpret")
    signs = pert.rademacher_signs(              # the host generator's
        pert.leaf_seed(seed, step, lid),
        jax.lax.iota(jnp.uint32, bank.size)).reshape(bank.shape)
    for s, sigma in enumerate((1.0, -1.0)):
        for e in range(n_e):
            rows = groups[s][e]
            w = bank[layer, e] + (sigma * dtheta) * signs[layer, e]
            # the product at the kernel's row-block shape (an M-row dot
            # sums in an order that may depend on M)
            for b0 in range(rows.start, rows.stop, bm):
                want = bufs[s][b0:b0 + bm] @ w
                got = ys[s][b0:b0 + bm]
                keep = min(bm, rows.stop - b0)
                np.testing.assert_array_equal(np.asarray(got[:keep]),
                                              np.asarray(want[:keep]))


def test_grouped_kernel_signs_are_the_stacked_leafs():
    """With W = 0 and identity rows, each expert's output is its slice of
    the host generator's signs for the stacked [L, E, K, N] leaf: the
    kernel's per-expert offset e·K·N on top of the layer shift."""
    n_e, k, n, bm, layer = 3, 32, 48, 128, 1
    shape = (2, n_e, k, n)
    sizes = jnp.asarray([[k, k, k]], jnp.int32)
    start, nblk, last = group_layout(sizes, bm)
    buf = jnp.zeros((bm * 4, k), jnp.float32)
    for e in range(n_e):
        buf = buf.at[int(start[0, e]) * bm:int(start[0, e]) * bm + k].set(
            jnp.eye(k))
    step, seed = 5, 17
    th = pert.generate({"w": jax.ShapeDtypeStruct(shape, jnp.float32)},
                       ptype="rademacher", step=step, seed=seed,
                       dtheta=1.0)["w"]
    lseed = pert.shifted_leaf_seed(pert.leaf_seed(seed, step, 0),
                                   layer * n_e * k * n)
    (y,) = ops.perturbed_grouped_matmul(
        [buf], jnp.zeros(shape[1:], jnp.float32), lseed, start, nblk, last,
        signs=(1.0,), dtheta=1.0, bm=bm, blocks=1, impl="interpret")
    for e in range(n_e):
        r0 = int(start[0, e]) * bm
        np.testing.assert_array_equal(np.asarray(y[r0:r0 + k]),
                                      np.asarray(th[layer, e]))


def test_smoke_deepseek_v3_has_the_fused_path():
    assert supports_fused_probe(SMOKE)
    assert not supports_fused_probe(
        get_smoke_config("llama4-scout-17b-a16e"))       # capacity MoE


@pytest.mark.parametrize("mode", ["central", "forward"])
def test_fused_bit_identical_whole_deepseek_step(mode):
    """Three MGD steps of the smoke DeepSeek-V3 (1 dense + 1 MoE layer,
    MLA, YaRN, the published routing) in f32: C̃ and every parameter
    bitwise equal between the fused kernels and the materializing path."""
    cfg = SMOKE
    p0 = model_init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

    def run(fused):
        d = repro.driver(
            "discrete", repro.DriverConfig(
                fused=fused, mode=mode, kernel_impl="interpret",
                dtheta=2 ** -6, eta=2e-3, seed=5),
            lambda p, b: model_loss(p, cfg, b),
            probe_fn=make_transformer_probe_fn(cfg) if fused else None)
        p, s = p0, d.init(p0)
        step = jax.jit(d.step)
        cts = []
        for _ in range(3):
            p, s, m = step(p, s, batch)
            cts.append(np.asarray(m["c_tilde"]))
        return np.array(cts), p, m

    c_mat, p_mat, _ = run(False)
    c_fus, p_fus, m = run(True)
    np.testing.assert_array_equal(c_mat, c_fus)
    for a, b in zip(jax.tree_util.tree_leaves(p_mat),
                    jax.tree_util.tree_leaves(p_fus)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    streams = 2 if mode == "central" else 1
    assert m["moe_rows"].shape == (1, streams, cfg.n_held)
    assert int(m["moe_rows"].sum()) <= 64 * cfg.n_experts_active * streams


def _logit(p):
    return math.log(p / (1.0 - p))


def test_published_routing_hand_worked():
    """8 experts in 4 groups of 2, the best 2 groups kept, top-2, ×2.5.
    Expert 0 scores 0.9 but its group (0.9 + 0.1) loses to groups 1 and
    2; the bias lifts expert 3 over expert 2 in the choice, while the
    weights stay the unbiased scores."""
    cfg = SMOKE.replace(n_experts=8, n_groups=4, topk_groups=2,
                        n_experts_active=2, routed_scale=2.5)
    p = [0.9, 0.1, 0.6, 0.55, 0.2, 0.96, 0.3, 0.3]
    logits = jnp.asarray([[_logit(x) for x in p]], jnp.float32)
    w, idx = moe.route(logits, cfg)
    assert sorted(np.asarray(idx[0]).tolist()) == [2, 5]
    np.testing.assert_allclose(
        np.sort(np.asarray(w[0])), np.sort(np.array([0.96, 0.6]) / 1.56 * 2.5),
        rtol=1e-6)
    bias = jnp.zeros(8).at[3].set(0.1)
    w, idx = moe.route(logits, cfg, bias)
    assert sorted(np.asarray(idx[0]).tolist()) == [3, 5]
    np.testing.assert_allclose(
        np.sort(np.asarray(w[0])),
        np.sort(np.array([0.96, 0.55]) / 1.51 * 2.5), rtol=1e-6)


def _moe_cfg(**kw):
    return SMOKE.replace(n_experts=16, n_experts_active=4, n_groups=4,
                         topk_groups=2, **kw)


def test_no_routed_token_dropped_under_forced_imbalance(monkeypatch):
    """Every token routes to held expert 5 (the router's column 5 is the
    only one that grows with the input): the dropless layer keeps every
    pair, in one pass or in many, and matches the dense per-expert
    oracle."""
    cfg = _moe_cfg()
    p = moe.moe_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    p["router"]["w"] = jnp.zeros_like(p["router"]["w"]).at[:, 5].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64)))
    want = moe.moe_apply_dense_ref(p, x, cfg)
    ids = leaf_id_tree(p)
    (y,), rows = moe.moe_streams(p, (x,), cfg, ids, None)
    assert int(rows[0, 5]) == 64
    assert int(rows.sum()) == 64 * cfg.n_experts_active
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    monkeypatch.setattr(moe, "PASS_LOAD", 0.125)      # 32 rows per pass
    assert moe.share_sizes(cfg, 64)[1] == 32
    (y_many,), _ = moe.moe_streams(p, (x,), cfg, ids, None)
    np.testing.assert_allclose(np.asarray(y_many), np.asarray(want),
                               atol=2e-5)


def test_chip_shares_add_up_to_the_uncut_layer():
    """E = 16 split into 4 shares of 4: each share draws the same experts
    the whole layer does, and the shares' outputs, the shared expert
    counted once, add up to the uncut layer and to the dense oracle."""
    full_cfg = _moe_cfg()
    full = moe.moe_init(jax.random.PRNGKey(7), full_cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    (y_full,), _ = moe.moe_streams(full, (x,), full_cfg, leaf_id_tree(full),
                                   None)
    total = 0.0
    for i in range(4):
        cfg = _moe_cfg(expert_first=4 * i, experts_held=4)
        share = moe.moe_init(jax.random.PRNGKey(7), cfg, jnp.float32)
        for bank in ("gate", "up", "down"):
            np.testing.assert_array_equal(
                np.asarray(share[bank]),
                np.asarray(full[bank][4 * i:4 * i + 4]))
        if i:
            del share["shared"]
        (y,), _ = moe.moe_streams(share, (x,), cfg, leaf_id_tree(share),
                                  None)
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(moe.moe_apply_dense_ref(
            full, x, full_cfg)), atol=2e-5)


def test_yarn_matches_the_published_formula():
    """DeepSeek-V3's rope_scaling: factor 40 over 4096 positions,
    β_fast 32, β_slow 1, θ 1e4, rope dim 64; softmax scale
    192^-½ · (0.1·ln 40 + 1)² ≈ 0.1353."""
    dim, base, factor, orig = 64, 1e4, 40.0, 4096

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(32)), 0)
    high = min(math.ceil(corr_dim(1)), dim - 1)
    extra = [base ** (-2 * i / dim) for i in range(dim // 2)]
    want = []
    for i, fe in enumerate(extra):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(fe / factor * ramp + fe * (1.0 - ramp))
    got = yarn_freqs(dim, base, factor, orig, 32.0, 1.0)
    np.testing.assert_allclose(got, np.asarray(want, np.float32), rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        extra[-1] / factor, rel=1e-6)
    from repro.configs import get_config
    scale = yarn_softmax_scale(get_config("deepseek-v3-671b"), 192)
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert scale == pytest.approx(0.1353, abs=1e-4)


# --- MLA's causal attention through the Pallas kernel ----------------------

def _mla_operands(dtype, b=2, h=3, s=40, dn=32, dr=16, dv=32, key=11):
    """q [B,H,S,dn+dr] heads-major, kv [B,S,H·(dn+dv)], the shared rope
    key [B,S,dr]: q/k width 48 against v width 32."""
    kq, kkv, kr = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(kq, (b, h, s, dn + dr)).astype(dtype),
            jax.random.normal(kkv, (b, s, h * (dn + dv))).astype(dtype),
            jax.random.normal(kr, (b, s, dr)).astype(dtype))


# S = 40 is no multiple of any block here: the sequence is end-padded;
# with 16-row blocks the diagonal cuts blocks and whole blocks are skipped
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32), None],
                         ids=["16x16", "32x16", "16x32", "picked"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mla_flash_attention_matches_oracles(dtype, blocks):
    """The kernel (interpret mode) against the plain f32 oracle and the
    XLA blockwise path (``chunked_causal_attention`` over the rope key
    broadcast to every head).  f32 inputs: 2e-5.  bf16 inputs: the kernel
    rounds p to bf16 for p·v and its output to bf16 (2^-8 each, relative
    to outputs of unit scale), so 3e-2."""
    from repro.models import mla

    q, kv, kr = _mla_operands(jnp.dtype(dtype))
    tol = 2e-5 if dtype == "float32" else 3e-2
    got = ops.mla_flash_attention(q, kv, kr, scale=0.2, impl="interpret",
                                  blocks=blocks)
    assert got.shape == (2, 40, 3 * 32) and got.dtype == q.dtype
    oracle = ops.mla_flash_attention(q, kv, kr, scale=0.2, impl="ref")
    xla = mla._attend_xla(*(x.astype(jnp.float32) for x in (q, kv, kr)),
                          scale=0.2)
    for want in (oracle, xla):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)


def test_mla_flash_attention_is_causal_and_shares_the_rope_key():
    """A later key or value moves no earlier output; the rope key of one
    position moves every head's output from that position on."""
    q, kv, kr = _mla_operands(jnp.float32, b=1, s=48)
    run = functools.partial(ops.mla_flash_attention, scale=0.2,
                            impl="interpret", blocks=(16, 16))
    base = run(q, kv, kr)
    moved = run(q, kv.at[:, 30].add(1.0), kr)
    np.testing.assert_array_equal(np.asarray(moved[:, :30]),
                                  np.asarray(base[:, :30]))
    assert not np.allclose(moved[:, 30:], base[:, 30:])
    moved = np.asarray(run(q, kv, kr.at[:, 20].add(1.0)) - base)
    np.testing.assert_array_equal(moved[:, :20], 0.0)
    for h in range(3):
        assert np.abs(moved[:, 20:, h * 32:(h + 1) * 32]).max() > 1e-4


def test_mla_streams_match_the_xla_path_for_two_streams(monkeypatch):
    """``mla_streams`` under a central probe pair (two streams that the
    perturbed projections set apart) through the kernel against the same
    layer with its attention on the XLA path: each stream, with the rope,
    the heads-major q and the shared rope key."""
    from repro.models import mla

    cfg = SMOKE
    p = mla.mla_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 40, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    probe = pert.Probe(jnp.int32(3), jnp.uint32(5), pert.ProbeCtx(
        signs=(1.0, -1.0), dtheta=0.1, impl="interpret"))
    args = (p, (x, x), pos, cfg, leaf_id_tree(p), probe)
    got, _ = mla.mla_streams(*args)
    monkeypatch.setattr(mla, "_attend", mla._attend_xla)
    want, _ = mla.mla_streams(*args)
    assert len(got) == 2 and not np.allclose(got[0], got[1])
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5,
                                   rtol=2e-5)


def test_mla_attention_differentiates_through_the_xla_path():
    """Backprop through MLA (the baseline ``train_backprop``): the
    kernel's custom VJP is the XLA path's, bit for bit."""
    from repro.models import mla

    q, kv, kr = _mla_operands(jnp.float32, b=1, s=32)
    w = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 96))

    def loss(attend):
        return lambda *a: jnp.sum(w * attend(*a, 0.2))

    got = jax.grad(loss(mla._attend), argnums=(0, 1, 2))(q, kv, kr)
    want = jax.grad(loss(mla._attend_xla), argnums=(0, 1, 2))(q, kv, kr)
    for g, x in zip(got, want):
        assert bool(jnp.any(g != 0))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))


def _jaxpr_text(cfg):
    shapes = jax.eval_shape(lambda: model_init(cfg, jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return str(jax.make_jaxpr(lambda p, b: model_loss(p, cfg, b))(
        shapes, {"tokens": toks, "labels": toks}))


@pytest.mark.parametrize("arch,calls", [
    ("deepseek-v3-671b", True), ("qwen3-14b", False),
    ("mistral-nemo-12b", False)])
def test_only_mla_runs_the_flash_kernel(arch, calls):
    """The DeepSeek-V3 smoke model's forward holds the
    ``mla_flash_attention`` call; the dense GQA configs' do not."""
    text = _jaxpr_text(get_smoke_config(arch))
    assert ("name=mla_flash_attention" in text) == calls
