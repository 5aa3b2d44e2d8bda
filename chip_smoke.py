"""Bring-up smoke of MGD training and decode on a TPU.

    python chip_smoke.py               # one chip: train phase, then decode
    python chip_smoke.py --four-chips  # four chips: the probe-parallel mesh

Runs the system's main path through the entry points a user calls, at the
published widths of ``qwen3-14b`` (d_model 5120, 40/8 heads of 128, d_ff
17408, vocab 151936, bf16) with random weights from a seed and the depth
cut to 4 of 40 layers.  Each phase checks its result against a reference
and raises on disagreement; the last line printed is one JSON object naming
the device.  There is no CPU or interpreter fallback: without a TPU the
script exits non-zero before computing anything.

Everything runs in this one process (a chip belongs to one process).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

# the program lives under src/ of this checkout; without it the imports
# below fail, and so does the script
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

import repro  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import perturbations as pert  # noqa: E402
from repro.core.probe_parallel import pod_seed  # noqa: E402
from repro.core.utils import leaf_meta  # noqa: E402
from repro.data.pipeline import lm_sampler  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.models import (make_transformer_probe_fn, model_decode,  # noqa: E402
                          model_forward, model_init, model_loss,
                          model_prefill)
from repro.serving import greedy_generate  # noqa: E402

# 8 layers do not fit: the un-donated fused step holds the weights twice
# (8.40 GB in, 8.40 GB out) plus 3.12 GB of temporaries — 19.9 GB against
# the chip's 16 GiB.  At 4 layers it is 5.75 + 5.75 + 3.12 = 14.6 GB
# (compiled memory_analysis for a v5e).
N_LAYERS = 4
SEED = 0
BATCH, SEQ = 4, 512          # training batch: 4 sequences of 512 tokens
CHUNK = 3                    # steps per train program; two chunks run
# Δθ = 2^-6 survives bf16 rounding at the init scale: the norm scales
# start at 1.0, where bf16 spacing is 2^-7 above and 2^-8 below, so
# 1 ± 2^-6 is exact; typical weights (std 1/sqrt(d_in) ≈ 0.014,
# embedding 0.02) lie below 2^-5, so w ± 2^-6 lies below 2^-4, where the
# spacing is ≤ 2^-12 — θ̃ spans 64 ulps or more.  The matmul leaves are
# perturbed in f32 in-kernel.
DTHETA = 2.0 ** -6
ETA = 2e-4
# c± are token-mean cross-entropies (≈ 13 nats at this init).  Both paths
# round every activation and logit to bf16 (2^-9 relative), but their f32
# accumulations differ — the kernel sums 128-wide K tiles on the MXU, the
# reference runs one HIGHEST-precision dot — so a share of the values
# round to neighbouring bf16 numbers.  The token-mean cost moved by 1e-4
# to 2e-4 in CPU interpret runs at small widths and by 9.0e-4 on a v5e at
# these widths; 4e-3 is about 2^-12 of the cost, 4x that chip reading.
# A path that drops θ̃ gives c+ = c- = C0, the unperturbed cost: the
# phase requires some reference c± to lie more than 2·tol from C0 (θ̃
# moves it by 0.69 nats here), so that such a path fails the check.
COST_TOL = 4e-3
# Logit agreement, decode phase: prefill and decode run the same bf16
# weights as model_forward through differently fused programs (and the
# decode step through the KV cache); bf16 activations (2^-9 relative)
# rounded in a different order shift logits by a few ulps of their
# magnitude.  2^-5 of the largest |logit| allows that and fails a wrong
# position, a stale cache or a dropped layer, which move logits by O(1)
# of their scale.
LOGIT_RTOL = 2.0 ** -5
PROMPTS, PROMPT_LEN, MAX_NEW = 4, 64, 8


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


def _tpu_devices(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"needs a TPU; JAX found {devs[0].platform!r} devices — "
              f"this script has no CPU or interpreter fallback")
    if len(devs) < n:
        _fail(f"needs {n} TPU chips; JAX found {len(devs)}")
    return devs[:n]


def _config():
    return get_config("qwen3-14b").replace(n_layers=N_LAYERS)


def _driver_config():
    return repro.DriverConfig(fused=True, mode="central",
                              kernel_impl="pallas", dtheta=DTHETA, eta=ETA,
                              seed=SEED)


def _loss(cfg):
    def loss_fn(params, batch):
        return model_loss(params, cfg, batch)

    return loss_fn


def _init(cfg):
    return jax.jit(model_init, static_argnums=0)(cfg,
                                                  jax.random.PRNGKey(SEED))


def _probe_costs(probe_fn, params, batch, *, impl: str, seed):
    """(c+, c-) of the central probe pair at step 0 under ``seed`` — the
    first half of what the fused MGD step computes."""
    ctx = pert.ProbeCtx(signs=(1.0, -1.0), dtheta=DTHETA, impl=impl)
    probe = pert.Probe(jnp.int32(0), jnp.asarray(seed, jnp.uint32), ctx)
    return [float(c) for c in probe_fn(params, batch, probe)]


def _check_costs(name, got, ref, c0):
    """c± of the Pallas path against the materializing reference; ``c0``
    is the reference's unperturbed cost.  A path that drops θ̃ returns
    c+ = c- = c0, so the check can tell it only where some reference c±
    lies further than the tolerance from c0 — required here, with 2×
    margin."""
    err = max(abs(g - r) for g, r in zip(got, ref))
    moved = max(abs(r - c0) for r in ref)
    print(f"[{name}] c± pallas=({got[0]!r}, {got[1]!r}) "
          f"ref=({ref[0]!r}, {ref[1]!r}) max|Δ|={err:.3e} (tol {COST_TOL}); "
          f"unperturbed ref C0={c0!r}, max|c±_ref − C0|={moved:.3e}, "
          f"C̃_ref={0.5 * (ref[0] - ref[1])!r}")
    if not all(math.isfinite(c) for c in got + ref + [c0]):
        raise FloatingPointError(f"{name}: non-finite probe cost {got} {ref}")
    if moved <= 2 * COST_TOL:
        raise AssertionError(
            f"{name}: θ̃ moves the reference cost by only {moved:.3e} ≤ "
            f"2·tol — the check could not tell a path that drops θ̃")
    if err > COST_TOL:
        raise AssertionError(f"{name}: c± disagree with the materializing "
                             f"path by {err:.3e} > {COST_TOL}")


class _CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def train_phase(cfg):
    """Fused central-pair MGD through ``repro.train`` for two chunks."""
    dcfg = _driver_config()
    loss_fn = _loss(cfg)
    probe_fn = make_transformer_probe_fn(cfg)
    sample_fn = lm_sampler(BATCH, SEQ, cfg.vocab, seed=SEED)
    batch = sample_fn(0)

    params = _init(cfg)
    drv = repro.driver("discrete", dcfg, loss_fn, probe_fn=probe_fn)
    text = jax.jit(drv.step).lower(params, drv.init(params), batch).as_text()
    n_kernels = text.count("tpu_custom_call")
    print(f"[train] lowered fused step: {n_kernels} tpu_custom_call sites")
    if n_kernels == 0:
        raise AssertionError("the fused step does not lower to the Pallas "
                             "kernels (no tpu_custom_call)")

    # step 0's probe pair, Pallas against the materializing reference
    probe = jax.jit(probe_fn)
    c_pal = _probe_costs(probe, params, batch, impl="pallas", seed=SEED)
    with jax.default_matmul_precision("highest"):
        c_ref = _probe_costs(probe, params, batch, impl="ref", seed=SEED)
        c0 = float(jax.jit(loss_fn)(params, batch))
    _check_costs("train", c_pal, c_ref, c0)
    del params          # the weights are made again inside the train call

    marks = []

    def mark(p):
        jax.block_until_ready(p)
        marks.append(time.perf_counter())
        return {}

    t0 = time.perf_counter()
    with _CompileClock() as clock:
        res = repro.train(
            loss_fn, _init(cfg), dcfg, sample_fn, 2 * CHUNK,
            loop=repro.TrainLoopConfig(probe_fn=probe_fn, chunk=CHUNK,
                                       eval_fn=mark, eval_every=CHUNK,
                                       log=print))
    jax.block_until_ready(res.params)
    costs = [rec["cost"] for _, rec in res.history]
    if not all(math.isfinite(c) for c in costs):
        raise FloatingPointError(f"non-finite training cost: {costs}")
    if len(marks) != 2 or res.steps_done != 2 * CHUNK:
        raise AssertionError(f"expected two chunks of {CHUNK} steps, got "
                             f"{res.steps_done} steps, {len(marks)} marks")
    steady = (marks[1] - marks[0]) / CHUNK
    tokens = BATCH * SEQ
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[train] compile (trace+lower+backend) {clock.seconds:.3f} s; "
          f"first chunk incl. compile {marks[0] - t0:.3f} s")
    print(f"[train] steady {steady:.4f} s/step over {CHUNK} steps "
          f"({2 * tokens / steady:.1f} probe tokens/s, batch {BATCH}x{SEQ}, "
          f"2 probes/step); costs per chunk {costs}")
    print(f"[train] peak_bytes_in_use {peak}")
    return res.params


def decode_phase(cfg, params):
    """Prefill + KV-cache decode against model_forward's logits."""
    prompts = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                 (PROMPTS, PROMPT_LEN), 0, cfg.vocab)
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        greedy_generate(params, cfg, prompts, MAX_NEW))
    dt = time.perf_counter() - t0
    if out.shape != (PROMPTS, MAX_NEW) or not bool(
            jnp.all((out >= 0) & (out < cfg.vocab))):
        raise AssertionError(f"greedy_generate returned {out.shape} "
                             f"tokens outside [0, {cfg.vocab})")
    print(f"[decode] greedy_generate {PROMPTS}x{PROMPT_LEN} prompts, "
          f"{MAX_NEW} new tokens: {dt:.3f} s incl. compile")

    forward = jax.jit(lambda p, t: model_forward(p, cfg, {"tokens": t}))
    prefill = jax.jit(lambda p, t: model_prefill(p, cfg, {"tokens": t},
                                                  PROMPT_LEN + 1))
    decode = jax.jit(lambda p, t, c: model_decode(p, cfg, t, c))
    logits, cache = prefill(params, prompts)
    nxt = out[:, 0]
    step_logits, _ = decode(params, nxt, cache)
    full = forward(params, jnp.concatenate([prompts, nxt[:, None]], 1))
    for name, got, ref in (("prefill", logits[:, -1], full[:, -2]),
                           ("decode", step_logits, full[:, -1])):
        got = jnp.asarray(got, jnp.float32)
        ref = jnp.asarray(ref, jnp.float32)
        err = float(jnp.max(jnp.abs(got - ref)))
        scale = float(jnp.max(jnp.abs(ref)))
        print(f"[decode] {name} next-token logits vs model_forward: "
              f"max|Δ|={err:.4e}, max|logit|={scale:.4e} "
              f"(tol {LOGIT_RTOL}·max|logit|)")
        if not math.isfinite(err) or err > LOGIT_RTOL * scale:
            raise AssertionError(f"{name} logits disagree with "
                                 f"model_forward: {err} > "
                                 f"{LOGIT_RTOL * scale}")


def four_chip_phase(cfg, devices):
    """Probe-parallel mesh (one probe per chip, scalar gather, replicated
    update) against the four pod probes run one after another on one
    chip."""
    k = len(devices)
    mesh = Mesh(np.array(devices), ("pod",))
    dcfg = _driver_config()
    probe_fn = make_transformer_probe_fn(cfg)
    drv = repro.driver("probe_parallel", dcfg, _loss(cfg), mesh=mesh,
                       probe_fn=probe_fn)
    batch = lm_sampler(k * BATCH, SEQ, cfg.vocab, seed=SEED)(0)
    params = jax.device_put(_init(cfg), NamedSharding(mesh, P()))
    state = drv.init(params)

    t0 = time.perf_counter()
    compiled = jax.jit(drv.step).lower(params, state, batch).compile()
    text = compiled.as_text()
    print(f"[mesh] compiled {k}-pod step in {time.perf_counter() - t0:.3f} s:"
          f" {text.count('tpu_custom_call')} tpu_custom_call, "
          f"{text.count('all-reduce(')} all-reduce, "
          f"{text.count('all-gather(')} all-gather")
    if "tpu_custom_call" not in text:
        raise AssertionError("mesh step has no Pallas kernel")
    if "all-reduce" not in text and "all-gather" not in text:
        raise AssertionError("mesh step has no scalar gather collective")
    t0 = time.perf_counter()
    p1, _, aux = jax.block_until_ready(compiled(params, state, batch))
    print(f"[mesh] first step after compile {time.perf_counter() - t0:.4f} s")

    for leaf in jax.tree_util.tree_leaves(p1):
        if leaf.sharding.device_set != set(devices):
            raise AssertionError(f"updated weights live on "
                                 f"{leaf.sharding.device_set}, not all "
                                 f"{k} chips")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"[mesh] peak_bytes_in_use per chip {peaks}")

    # the same four probes, one after another on the first chip
    dev0 = devices[0]

    def on_dev0(a):
        return next(s.data for s in a.addressable_shards if s.device == dev0)

    p0 = jax.tree_util.tree_map(on_dev0, params)
    shards = [jax.tree_util.tree_map(
        lambda x, i=i: jax.device_put(x[i * BATCH:(i + 1) * BATCH], dev0),
        batch) for i in range(k)]
    probe = jax.jit(probe_fn)
    pods = [_probe_costs(probe, p0, shards[i], impl="pallas",
                         seed=pod_seed(SEED, i)) for i in range(k)]
    c = [0.5 * (cp - cm) for cp, cm in pods]
    for i, (cp, cm) in enumerate(pods):
        print(f"[mesh] pod {i} on one chip: c+={cp!r} c-={cm!r} "
              f"C̃={c[i]!r}")
    # aux: mean|C̃| over the gathered pods, and one pod's mean cost
    mesh_c, mesh_cost = float(aux["c_tilde"]), float(aux["cost"])
    one_c = float(np.mean(np.abs(c)))
    cost_err = min(abs(mesh_cost - 0.5 * (cp + cm)) for cp, cm in pods)
    print(f"[mesh] mean|C̃| mesh={mesh_c!r} one-chip={one_c!r}; mesh cost "
          f"{mesh_cost!r} within {cost_err:.3e} of a pod's (tol {COST_TOL})")
    if not (math.isfinite(mesh_c) and math.isfinite(mesh_cost)) or max(
            abs(mesh_c - one_c), cost_err) > COST_TOL:
        raise AssertionError("mesh probes disagree with one chip")

    # the replicated update, materialized on the first and last rows of
    # every leaf: W + Σ_k (−η/Δθ²·C̃_k/k)·θ̃_k
    coefs = [np.float32(-dcfg.eta / DTHETA ** 2) * np.float32(ci)
             / np.float32(k) for ci in c]
    worst, changed, total = 0.0, 0, 0
    for (lid, _, _), w0, w1 in zip(leaf_meta(p0),
                                   jax.tree_util.tree_leaves(p0),
                                   jax.tree_util.tree_leaves(p1)):
        cols = w0.shape[-1]
        rows = w0.size // cols
        w1 = on_dev0(w1).reshape(rows, cols)
        w0 = w0.reshape(rows, cols)
        n = min(rows, 128)
        for r0 in sorted({0, rows - n}):
            ref = w0[r0:r0 + n].astype(jnp.float32)
            for i in range(k):
                ref = ref + coefs[i] * pert.rademacher_leaf(
                    (n, cols), jnp.float32, lid, step=0,
                    seed=pod_seed(SEED, i), dtheta=DTHETA,
                    offset=r0 * cols)
            ref = np.asarray(ref)
            got = np.asarray(w1[r0:r0 + n].astype(jnp.float32))
            old = np.asarray(w0[r0:r0 + n].astype(jnp.float32))
            # one ulp of the weights' dtype at the reference value: the
            # update rounds once (per pod for 1-D leaves, whose moves
            # here stay under half an ulp and round back)
            ulp = np.exp2(np.floor(np.log2(np.abs(ref) + 1e-30))
                          - jnp.finfo(w0.dtype).nmant)
            bad = np.abs(got - ref) > ulp
            worst = max(worst, float(np.max(np.abs(got - ref))))
            changed += int(np.sum(got != old))
            total += got.size
            if bad.any():
                raise AssertionError(
                    f"leaf {lid} rows {r0}:{r0 + n}: replicated update "
                    f"off the materialized one by {np.max(np.abs(got - ref))}")
    print(f"[mesh] update vs materialized reference: max|Δ|={worst:.3e} "
          f"(≤ 1 ulp each); {changed}/{total} checked weights moved")
    del p1
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(params, state, batch))
    print(f"[mesh] steady step {time.perf_counter() - t0:.4f} s")
    if changed < total // 10:
        raise AssertionError("the update moved under a tenth of the weights "
                             "checked — the comparison would be vacuous")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the probe-parallel mesh on four chips")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1

    devices = _tpu_devices(n_chips)
    print(f"[setup] compile cache: {use_compile_cache()}")
    d = devices[0]
    print(f"[setup] {len(devices)} x {d.device_kind} ({d.platform})")
    cfg = _config()
    print(f"[setup] {cfg.name} at published widths: d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.kv_heads} heads of {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}; depth cut to "
          f"{cfg.n_layers} of 40 layers (8 need 19.9 GB un-donated)")

    if args.four_chips:
        four_chip_phase(cfg, devices)
    else:
        params = train_phase(cfg)
        decode_phase(cfg, params)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
