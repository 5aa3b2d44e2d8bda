#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU this process is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json) names a configuration and a traffic mix.  The
run makes the weights on the device from the seed (``model_init``, one
jitted call) and trains them with fused central-pair MGD through the
program's own loop, ``repro.train``, fed by ``textgen`` rows that are a
pure function of (seed, step):

1. set-up: a first ``repro.train`` call compiles the step (or loads it
   from the compile cache), runs the steps the correctness check
   follows, reads the program's change θ_K − θ_0 after them, and times
   one clean chunk;
2. the window: a second call on the same driver and the weights the
   first left, sized from that chunk to fill ``--seconds``; it runs from
   its first chunk's stamp (so the re-trace of its first chunk stays out)
   to its last.  The loop's ``log`` callback, which follows each chunk's
   host read of its metrics, is the stamp;
3. the peak of device memory, read after the window;
4. ``correct``: the plain reference replays the first steps from the
   same seeds (``check.py``) once the program's state is freed.

``--trace 1`` traces the window (at most 64 steps) and prints the
per-layer metrics instead of the end-to-end ones.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
The last line of standard output is one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    # run as a script: import the benchmark as a package from the root,
    # not its modules as top-level names from its own directory
    sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.models import (make_transformer_probe_fn, model_init,  # noqa: E402
                          model_loss)

from chipbench import bench, check, textgen  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.context import Context  # noqa: E402

OUT = ROOT / "chipbench" / "out"
TRACE_MAX_STEPS = 64
GIB = 2.0 ** 30


class NoChip(RuntimeError):
    pass


def tpu_devices(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} devices")
    if len(devs) < n:
        raise NoChip(f"needs {n} TPU chips; JAX found {len(devs)}")
    return devs[:n]


def derive_seeds(seed: int) -> dict:
    """Three independent seeds under 2**31 from any whole number."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(3)
    return {k: int(w) % 2 ** 31 for k, w in zip(("init", "data", "mgd"), words)}


def arch_config(c: dict):
    """The program's ArchConfig, every size taken from the file."""
    return get_config(c["registry"]).replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_head=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]), qk_norm=c["model_type"] == "qwen3",
        qkv_bias=bool(c.get("attention_bias", False)),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=c["torch_dtype"])


def annotate(name: str):
    return jax.profiler.TraceAnnotation("chipbench." + name)


class Stamps:
    """The loop's ``log`` callback: a host stamp after every chunk, with
    hooks on chosen chunks (1-based)."""

    def __init__(self, hooks=None):
        self.t = []
        self.hooks = hooks or {}

    def __call__(self, _msg):
        with annotate("stamp"):
            self.t.append(time.perf_counter())
            hook = self.hooks.get(len(self.t))
            if hook:
                hook()


def flat_params(params) -> dict:
    """path -> leaf, the paths as the reference names them."""
    return {"/".join(k.key for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def host_counters() -> tuple:
    """(process CPU seconds, involuntary context switches) so far: a
    window whose wall time grows while these do not was held up outside
    the process."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw


def end_to_end(cell, *, setup_s, steps, window_s, step_s, peak) -> dict:
    t = cell.traffic
    tokens = steps * int(t["batch"]) * int(t["seq_len"])
    values = {
        "setup_s": setup_s,
        "train_tokens_per_s": tokens / window_s,
        "step_p95_ms": 1e3 * float(np.percentile(step_s, 95)),
        "peak_hbm_gib": peak / GIB,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        v = bench.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(ctx: Context) -> dict:
    ops: dict = {}
    for i in range(ctx.chips):
        for name, s in tr.self_seconds(ctx.trace.devices.get(i, []),
                                       ctx.t0, ctx.t1).items():
            ops[name] = ops.get(name, 0.0) + s / ctx.chips
    gaps = sorted(tr.idle_gaps(ctx.trace.devices.get(0, []), ctx.t0, ctx.t1),
                  key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": sorted(([n, s] for n, s in ops.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": [[tr.label_gap(ctx.trace.host, a, b), b - a]
                          for a, b in gaps]}


@dataclasses.dataclass
class Setup:
    """The driver, feed and weights that set-up hands to the window, and
    what the program produced over the steps the check follows."""
    drv: object
    sample_fn: object
    program: check.Run
    chunk_s: float          # one clean chunk, timed in set-up
    params: object
    marks: dict             # host clock after each part of set-up


def setup(cell, seeds: dict, *, kernel_impl: str = "pallas",
          probe_fn=None) -> Setup:
    """Weights from the seed, the driver, and a first ``repro.train`` call
    over the checked steps plus one clean chunk to time."""
    t = cell.traffic
    chunk = int(t["chunk"])
    cfg = arch_config(cell.config)
    ref = bench.reference(cell.config)
    arch = ref.Arch.from_config(cell.config)
    init = jax.jit(model_init, static_argnums=0)
    box = [init(cfg, jax.random.PRNGKey(seeds["init"]))]
    jax.block_until_ready(box[0])
    marks = {"weights": time.perf_counter()}
    dcfg = repro.DriverConfig(
        fused=True, mode=t["mode"], kernel_impl=kernel_impl,
        dtheta=float(t["dtheta"]), eta=float(t["eta"]), seed=seeds["mgd"])
    drv = repro.driver(
        "discrete", dcfg, lambda p, b: model_loss(p, cfg, b),
        probe_fn=probe_fn or make_transformer_probe_fn(cfg))
    sample_fn = textgen.sampler(seeds["data"], int(t["batch"]),
                                int(t["seq_len"]), cfg.vocab)
    k = check.check_steps(t)
    seen = {"chunks": 0}

    def after_chunk(params):
        seen["chunks"] += 1
        if seen["chunks"] * chunk == k:
            marks["checked steps"] = time.perf_counter()
            seen["change"] = check.change_stats(
                ref, arch, flat_params(params), seeds, t)
            marks["change norms"] = time.perf_counter()
        return {}

    stamps = Stamps()
    res = repro.train(None, box.pop(), drv, sample_fn,
                      chunk * (-(-k // chunk) + 1),
                      loop=repro.TrainLoopConfig(
                          chunk=chunk, eval_fn=after_chunk, eval_every=chunk,
                          log=stamps))
    hist = dict(res.history)
    obs = check.observed_steps(t)
    change, applied = seen["change"]
    program = check.Run({n: hist[n + 1]["cost"] for n in obs}, applied,
                        change)
    marks["timing chunk"] = stamps.t[-1]
    return Setup(drv, sample_fn, program, stamps.t[-1] - stamps.t[-2],
                 res.params, marks)


def run_cell(cell, seed: int, seconds: float, traced: bool, devices, *,
             t_start: float = T_START, kernel_impl: str = "pallas",
             peaks: dict | None = None, probe_fn=None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = derive_seeds(seed)
    t = cell.traffic
    chunk = int(t["chunk"])
    with annotate("setup"):
        s = setup(cell, seeds, kernel_impl=kernel_impl, probe_fn=probe_fn)
        drv, sample_fn, program, chunk_s, marks = (
            s.drv, s.sample_fn, s.program, s.chunk_s, s.marks)
        box = [s.params]
        del s

    # (2) the window
    n_chunks = max(2, math.ceil(seconds / chunk_s))
    if traced:
        n_chunks = min(n_chunks, max(2, TRACE_MAX_STEPS // chunk))
    trace_dir = OUT / "trace" / cell.name
    window = {}
    compiles = []

    def opened():
        window["open"] = time.perf_counter()
        window["host"] = host_counters()
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            window["span"] = annotate("window")
            window["span"].__enter__()

    def closed():
        window["close"] = time.perf_counter()
        window["host"] = [b - a for a, b in zip(window["host"],
                                                host_counters())]
        if traced:
            window["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    def on_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration" \
                and "open" in window and "close" not in window:
            compiles.append(duration)

    stamps = Stamps({1: opened, 1 + n_chunks: closed})
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        res = repro.train(None, box.pop(), drv, sample_fn,
                          chunk * (1 + n_chunks),
                          loop=repro.TrainLoopConfig(chunk=chunk, log=stamps))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    costs_in_window = [rec["cost"] for _, rec in res.history[1:]]
    del res
    steps = n_chunks * chunk
    window_s = window["close"] - window["open"]
    step_s = np.diff(stamps.t) / chunk
    setup_s = window["open"] - t_start

    # (3) memory, read before anything else allocates
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    gc.collect()

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    result = {"attempted": steps,
              "failed": chunk * sum(not math.isfinite(c)
                                    for c in costs_in_window)}
    if traced:
        peaks = peaks or json.loads((ROOT / "chipbench" / "peaks.json")
                                    .read_text())["devices"]
        if d0.device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {d0.device_kind!r} "
                           f"in chipbench/peaks.json")
        pk = peaks[d0.device_kind]
        trace = tr.load(tr.find_trace(trace_dir))
        t0, t1 = trace.window()
        ctx = Context(trace, t0, t1, steps, cell.chips, cell.config, t,
                      float(pk["bf16_flops"]), float(pk["hbm_bytes_per_s"]))
        result["metrics"] = per_layer(cell, ctx)
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        result["breakdown"] = breakdown(ctx)
    else:
        result["metrics"] = end_to_end(cell, setup_s=setup_s, steps=steps,
                                       window_s=window_s, step_s=step_s,
                                       peak=peak)
    result["device"] = device
    marks["window open"] = window["open"]
    parts = ", ".join(f"{name} {t - t_start:.3f}" for name, t in marks.items())
    print(f"[chipbench] {cell.name}: set-up {setup_s:.3f} s (at s: {parts}), "
          f"window {window_s:.3f} s over {steps} steps, chunk {chunk_s:.4f} s "
          f"in set-up, {len(compiles)} compiles in the window", file=sys.stderr)
    print(f"[chipbench] window steps: median "
          f"{1e3 * float(np.median(step_s)):.3f} ms, p95 "
          f"{1e3 * float(np.percentile(step_s, 95)):.3f} ms, max "
          f"{1e3 * float(np.max(step_s)):.3f} ms; host CPU "
          f"{window['host'][0]:.3f} s, {window['host'][1]} involuntary "
          f"context switches", file=sys.stderr)

    # (4) correctness, with the program's state freed
    reference = check.follow(cell, seeds)
    nums = check.numbers(cell, program, reference)
    result["correct"] = check.passed(nums) and result["failed"] == 0
    result["checks"] = nums
    print(f"[chipbench] program cost {program.cost} C̃ {program.applied}; "
          f"reference cost {reference.cost} C̃ {reference.applied}",
          file=sys.stderr)
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    return {key: result[key] for key in order if key in result}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = bench.resolve(args.workload)
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
