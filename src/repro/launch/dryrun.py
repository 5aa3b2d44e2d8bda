"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set the host-device override before ANY other import — jax locks the
device count on first init.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import functools         # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.api import driver  # noqa: E402
from repro.configs import ARCH_IDS, SHAPES, get_config, runnable_cells  # noqa: E402
from repro.core import MGDConfig, mgd_init  # noqa: E402
from repro.core.mgd import MGDState  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import specs  # noqa: E402
from repro.launch.hlo_collectives import collective_bytes  # noqa: E402
from repro.launch.jaxpr_cost import jaxpr_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import init_cache, model_decode, model_loss, model_prefill  # noqa: E402


def default_mgd_config(mode: str = "forward") -> MGDConfig:
    """Paper-faithful baseline: Algorithm 1, τ_p = τ_θ = τ_x = 1
    (C₀ refresh + perturbed forward = 2 forwards/step)."""
    return MGDConfig(ptype="rademacher", dtheta=1e-3, eta=1e-2,
                     tau_p=1, tau_theta=1, tau_x=1, mode=mode)


def count_params(aparams) -> int:
    return sum(int(math.prod(x.shape))
               for x in jax.tree_util.tree_leaves(aparams))


def active_params(cfg, aparams) -> int:
    n = count_params(aparams)
    if cfg.n_experts:
        per_expert = 3 * cfg.d_model * cfg.d_ff
        n -= cfg.n_moe_layers * (cfg.n_held - cfg.n_experts_active) * per_expert
    return n


def model_flops(cfg, shape, kind: str, n_forwards: int) -> float:
    """Analytic useful FLOPs per step (the roofline's MODEL_FLOPS)."""
    aparams = specs.abstract_params(cfg)
    n_active = active_params(cfg, aparams)
    n_embed = cfg.vocab * max(cfg.n_codebooks, 1) * cfg.d_model
    n_mm = n_active - n_embed          # embedding lookup is a gather
    b, s = shape.global_batch, shape.seq_len
    if kind == "train" or kind == "prefill":
        tokens = b * s
        flops = 2.0 * n_mm * tokens
        if cfg.family not in ("ssm",):
            # causal attention: 2 matmuls × 2 flops × S²/2 × heads·dh (+GQA)
            attn_layers = (cfg.n_layers if cfg.family != "hybrid"
                           else cfg.n_layers // (cfg.attn_every + 1))
            d_attn = cfg.n_heads * cfg.head_dim
            if cfg.use_mla:
                d_attn = cfg.n_heads * (cfg.qk_nope_head_dim
                                        + cfg.qk_rope_head_dim
                                        + cfg.v_head_dim) / 2
            flops += attn_layers * b * s * s * d_attn * 2.0  # ≈2·2·S²/2·d
    else:  # decode: one token per sequence
        tokens = b
        flops = 2.0 * n_mm * tokens
        if cfg.family not in ("ssm",):
            attn_layers = (cfg.n_layers if cfg.family != "hybrid"
                           else cfg.n_layers // (cfg.attn_every + 1))
            if cfg.use_mla:
                # absorbed decode: scores+values vs the r-dim latent cache
                d_attn = cfg.n_heads * (cfg.kv_lora_rank
                                        + cfg.qk_rope_head_dim)
            else:
                d_attn = cfg.n_heads * cfg.head_dim
            flops += attn_layers * b * s * d_attn * 2.0 * 2.0
    return flops * n_forwards


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------


def build_train(cfg, shape, mesh, mgd_mode="forward"):
    mgd_cfg = default_mgd_config(mgd_mode)
    loss_fn = lambda p, b: model_loss(p, cfg, b)          # noqa: E731
    step_fn = driver("discrete", mgd_cfg, loss_fn).step
    aparams = specs.abstract_params(cfg)
    astate = jax.eval_shape(functools.partial(mgd_init, cfg=mgd_cfg), aparams)
    abatch = specs.train_input_specs(cfg, shape)
    p_shard = specs.param_shardings(cfg, mesh)
    rep = NamedSharding(mesh, P())
    g_shard = None if astate.g is None else jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s.spec), p_shard)
    st_shard = MGDState(step=rep, c0=rep, g=g_shard, replay_c=None, m=None,
                        metric_cost=rep)
    b_shard = specs.batch_shardings(abatch, mesh)
    n_forwards = 2
    return (step_fn, (aparams, astate, abatch),
            (p_shard, st_shard, b_shard), n_forwards)


def build_prefill(cfg, shape, mesh):
    abatch = specs.prefill_input_specs(cfg, shape)
    fn = functools.partial(model_prefill, cfg=cfg, max_len=shape.seq_len)
    aparams = specs.abstract_params(cfg)
    p_shard = specs.param_shardings(cfg, mesh)
    b_shard = specs.batch_shardings(abatch, mesh)

    def prefill_fn(params, batch):
        return fn(params, batch=batch)

    return prefill_fn, (aparams, abatch), (p_shard, b_shard), 1


def build_decode(cfg, shape, mesh):
    """serve_step: ONE new token against a seq_len-deep cache."""
    tok, acache = specs.decode_input_specs(cfg, shape, mesh)
    aparams = specs.abstract_params(cfg)
    p_shard = specs.param_shardings(cfg, mesh)
    c_shard = specs.cache_shardings(cfg, acache, mesh)
    t_shard = specs.batch_shardings(tok, mesh)

    if "embeds" in tok:
        def serve_step(params, tok_in, cache):
            return model_decode(params, cfg, None, cache,
                                embeds=tok_in["embeds"])
    else:
        def serve_step(params, tok_in, cache):
            return model_decode(params, cfg, tok_in["tokens"], cache)

    return serve_step, (aparams, tok, acache), (p_shard, t_shard, c_shard), 1


def build_cell(cfg, shape, mesh, mgd_mode="forward"):
    if shape.kind == "train":
        return build_train(cfg, shape, mesh, mgd_mode)
    if shape.kind == "prefill":
        return build_prefill(cfg, shape, mesh)
    return build_decode(cfg, shape, mesh)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = "artifacts/dryrun", mgd_mode: str = "forward",
             cfg_overrides=None, tag: str = "", pure_dp: bool = False,
             rule_set=None, verbose=True) -> dict:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.shape.values())
    t0 = time.time()
    result = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "multi_pod": multi_pod, "chips": chips, "tag": tag,
        "mgd_mode": mgd_mode if shape.kind == "train" else None,
        "overrides": {k: str(v) for k, v in (cfg_overrides or {}).items()},
        "pure_dp": pure_dp,
    }
    if rule_set:
        rules = shd.RULE_SETS[rule_set]
    else:
        rules = shd.PURE_DP_RULES if pure_dp else None
    with shd.use_mesh(mesh, rules):
        fn, args, shardings, n_fwd = build_cell(cfg, shape, mesh, mgd_mode)
        # scan-aware logical cost from the jaxpr (global, all chips)
        jx = jax.make_jaxpr(fn)(*args)
        jcost = jaxpr_cost(jx)
        t_trace = time.time() - t0
        # donate params (+ optimizer state / cache): the production step
        # updates in place, so the dry-run must account buffers that way
        # too.  Donation needs matching out_shardings on the updated
        # outputs, so pin them.
        donate = ((0, 1) if shape.kind == "train"
                  else (2,) if shape.kind == "decode" else ())
        out_shardings = None
        if shape.kind == "train":
            rep = NamedSharding(mesh, P())
            metrics_shard = {"cost": rep, "c_tilde": rep, "updated": rep}
            out_shardings = (shardings[0], shardings[1], metrics_shard)
        elif shape.kind == "decode":
            out_shardings = (None, shardings[2])
        lowered = jax.jit(fn, in_shardings=shardings,
                          out_shardings=out_shardings,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0 - t_trace
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_trace - t_lower
        mem = compiled.memory_analysis()
        xca = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text(), default_trip=1)

    result.update({
        "params": count_params(args[0]),
        "params_active": active_params(cfg, args[0]),
        "jaxpr_flops": jcost["flops"],
        "jaxpr_bytes": jcost["bytes"],
        "unknown_while": jcost["unknown_while"],
        "model_flops": model_flops(cfg, shape, shape.kind, n_fwd),
        "xla_flops_per_device": xca.get("flops"),
        "xla_bytes_per_device": xca.get("bytes accessed"),
        "collective_bytes_per_device": coll["total_bytes"],
        "collective_by_type": coll["by_type"],
        "n_collectives": len(coll["ops"]),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "seconds": {"trace": round(t_trace, 2), "lower": round(t_lower, 2),
                    "compile": round(t_compile, 2)},
    })
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "multipod" if multi_pod else "singlepod"
        tag_s = f"_{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch}_{shape_name}_{suffix}{tag_s}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × "
              f"{'2x16x16' if multi_pod else '16x16'}: "
              f"compile {result['seconds']['compile']}s, "
              f"args {mem.argument_size_in_bytes/2**30:.2f} GiB/dev, "
              f"temp {mem.temp_size_in_bytes/2**30:.2f} GiB/dev, "
              f"coll {coll['total_bytes']/2**20:.1f} MiB/dev/step")
    return result


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mgd-mode", default="forward",
                    choices=["forward", "central"])
    ap.add_argument("--out", default="artifacts/dryrun")
    # hillclimb variants
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--pure-dp", action="store_true")
    ap.add_argument("--rules", default=None,
                    choices=[None, "pure_dp", "dp_fsdp", "moe_ep"])
    ap.add_argument("--attn", default=None, choices=[None, "balanced"])
    ap.add_argument("--moe-group", type=int, default=None)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    overrides = {}
    if args.seq_parallel:
        overrides["seq_parallel"] = True
    if args.attn:
        overrides["attn_impl"] = args.attn
    if args.moe_group:
        overrides["moe_group_size"] = args.moe_group

    cells = [(a, s) for a, s, ok in runnable_cells() if ok]
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                         mgd_mode=args.mgd_mode, cfg_overrides=overrides,
                         tag=args.tag, pure_dp=args.pure_dp,
                         rule_set=args.rules)
            except Exception as e:   # noqa: BLE001
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] FAIL {arch} × {shape} mp={mp}: {e}")
                traceback.print_exc(limit=5)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(cells) * len(meshes)} cells compiled clean")


if __name__ == "__main__":
    main()
