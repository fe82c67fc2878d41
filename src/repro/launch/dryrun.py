import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

DOC = """Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against 512 placeholder host devices, and extract the roofline
terms (deliverables (e) and (g)).

For each combination this produces:
  * compiled.memory_analysis()   -> bytes per device (proves it fits),
  * compiled.cost_analysis()     -> HLO FLOPs / bytes accessed,
  * collective bytes parsed from the optimized HLO text (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute),
  * derived roofline terms for TPU v5e (197 TFLOP/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI).

Results are cached to JSON (one file per combo) under --out so the roofline
report and perf iterations never recompile unchanged combos.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import base as cfgbase
from repro.launch import mesh as meshlib
from repro.launch import shardings
from repro.models import registry
from repro.models import transformer as T

# ---------------------------------------------------------------------------
# Input specs: ShapeDtypeStruct stand-ins for every model input.
# ---------------------------------------------------------------------------
def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: T.ModelCfg, shape: cfgbase.InputShape):
    """ShapeDtypeStructs for one (arch, input-shape) combination.

    Returns dict with keys depending on shape.kind:
      train/prefill: {"batch": {tokens[, modal_embeds]}}
      decode:        {"token", "pos", "cache_len", "window", ...}
    """
    b, s = shape.global_batch, shape.seq_len
    out: dict = {}
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": sds((b, s), jnp.int32)}
        if cfg.family == "enc_dec":
            batch["modal_embeds"] = sds((b, cfg.enc_seq, cfg.d_model), cfg.dtype)
        elif cfg.family == "vlm":
            batch["modal_embeds"] = sds((b, cfg.n_modal_tokens, cfg.d_model), cfg.dtype)
        out["batch"] = batch
    else:
        out["token"] = sds((b, 1), jnp.int32)
        out["pos"] = sds((), jnp.int32)
    return out


def decode_plan(cfg: T.ModelCfg, shape: cfgbase.InputShape):
    """(cache_len, window, full_cache) for a decode shape.

    long_500k: SSM decodes natively (state only); attention families use the
    sliding-window cache (DESIGN.md §4) — cache length = window, wrapped.
    """
    if shape.name == "long_500k":
        if cfg.family == "ssm":
            return 1, None, False  # no kv cache at all (state only)
        w = cfgbase.LONG_CONTEXT_WINDOW
        return w, w, True
    return shape.seq_len, None, False


# ---------------------------------------------------------------------------
# Collective-bytes parser (optimized HLO text).
# ---------------------------------------------------------------------------
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")
_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _wire_factor(kind: str, group_size: int) -> float:
    """Ring wire bytes per chip / RESULT-shape bytes (HLO prints results).

    all-gather: result = gathered (N x input), wire = (N-1)/N x result ~ 1.
    reduce-scatter: result = input/N, wire = (N-1)/N x input ~ N x result.
    all-reduce: result = buffer, wire = 2(N-1)/N x buffer ~ 2.
    all-to-all / permute: wire ~ result.
    """
    g = max(group_size, 1)
    if kind == "all-reduce":
        return 2.0 * (g - 1) / g
    if kind == "reduce-scatter":
        return float(g - 1)
    if kind == "all-gather":
        return (g - 1) / g
    return (g - 1) / g if kind == "all-to-all" else 1.0


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum estimated WIRE bytes of every collective op, by op kind.

    Result-shape bytes x a replica-group-aware ring factor ('-done' ops
    skipped — their '-start' twin is already counted).
    """
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        shape_str, kind = m.group(1), m.group(2)
        gm = _GROUPS_RE.search(line)
        if gm:
            group_size = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            group_size = int(gi.group(2)) if gi else 2
        total = 0.0
        for sm in _SHAPE_RE.finditer(shape_str):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * _BYTES[dt]
        out[kind] = out.get(kind, 0.0) + total * _wire_factor(kind, group_size)
    return out


# ---------------------------------------------------------------------------
# Lower + compile one combination.
# ---------------------------------------------------------------------------
def model_flops(cfg: T.ModelCfg, n_tokens: float, *, train: bool) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); 2*N*D for inference."""
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
    total = 0.0
    active = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = float(np.prod(leaf.shape))
        total += n
        in_moe = any(getattr(p, "key", "") == "moe" for p in path)
        name = [getattr(p, "key", "") for p in path]
        if in_moe and any(k in ("w_up", "w_down", "w_gate") for k in name):
            n = n * cfg.top_k / cfg.n_experts
        active += n
    mult = 6.0 if train else 2.0
    return mult * active * n_tokens


def to_shardings(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree (None leaves preserved)."""
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


def _lower_and_compile(cfg, shape, mesh, dax, n_chips, profile="fsdp",
                       kv_shard="heads"):
    """Lower + compile one (cfg, shape) on `mesh`. Returns compiled exec."""
    bundle = registry.build(cfg)
    specs = input_specs(cfg, shape)
    with mesh:
        if shape.kind == "train":
            params_shape = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
            opt_shape = jax.eval_shape(bundle.optimizer.init, params_shape)
            state_shape = {"params": params_shape, "opt": opt_shape}
            state_spec = {
                "params": shardings.param_specs(params_shape, data_axes=dax),
                "opt": shardings.param_specs(opt_shape, data_axes=dax),
            }
            batch_spec = shardings.batch_specs(specs["batch"], data_axes=dax,
                                               shard_batch=True)
            metrics_spec = {"loss": P(), "aux": P()}
            fn = jax.jit(
                bundle.train_step,
                in_shardings=to_shardings(mesh, (state_spec, batch_spec)),
                out_shardings=to_shardings(mesh, (state_spec, metrics_spec)),
                donate_argnums=(0,),
            )
            lowered = fn.lower(state_shape, specs["batch"])
        elif shape.kind == "prefill":
            params_shape = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
            param_spec = shardings.param_specs(params_shape, data_axes=dax,
                                               profile=profile)
            batch_spec = shardings.batch_specs(specs["batch"], data_axes=dax,
                                               shard_batch=True)
            window = cfg.sliding_window
            fn = jax.jit(
                lambda p, b: bundle.prefill_step(p, b, window=window),
                in_shardings=to_shardings(mesh, (param_spec, batch_spec)),
            )
            lowered = fn.lower(params_shape, specs["batch"])
        else:  # decode
            cache_len, window, full_cache = decode_plan(cfg, shape)
            params_shape = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
            param_spec = shardings.param_specs(params_shape, data_axes=dax,
                                               profile=profile)
            b = shape.global_batch
            cache_shape = jax.eval_shape(
                lambda: bundle.init_cache(b, cache_len, window=window)
            )
            shard_batch = b >= n_chips // 16 and b > 1
            cache_spec = shardings.cache_specs(cache_shape, data_axes=dax,
                                               shard_batch=shard_batch,
                                               kv_shard=kv_shard)
            token_spec = P(dax, None) if shard_batch else P()

            def step(params, cache, token, pos):
                return bundle.serve_step(
                    params, cache, token, pos, window=window,
                    abs_pos=None, full_cache=full_cache,
                )

            fn = jax.jit(
                step,
                in_shardings=to_shardings(
                    mesh, (param_spec, cache_spec, token_spec, P())),
                out_shardings=to_shardings(mesh, (None, cache_spec)),
                donate_argnums=(1,),
            )
            lowered = fn.lower(params_shape, cache_shape, specs["token"],
                               specs["pos"])
        return lowered.compile()


def _extract_costs(compiled) -> dict:
    """Per-chip flops / bytes / collective bytes of a compiled executable.

    cost_analysis / as_text operate on the post-SPMD module, i.e. one
    device's share: these are already per-chip quantities.
    """
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        "coll_by_kind": coll,
    }


def extrapolated_costs(cfg, shape, mesh, dax, n_chips, profile="fsdp",
                       kv_shard="heads") -> dict:
    """Exact roofline costs via layer-count extrapolation.

    XLA's HLO cost analysis counts a while-loop body ONCE regardless of trip
    count, so the production scanned module undercounts per-layer work.  We
    instead compile tiny UNROLLED variants (scan_unroll=True) at 1 and 2
    repeating units and extrapolate linearly:
        total(U units) = f(1) + (U - 1) * (f(2) - f(1))
    which is exact for homogeneous stacks.  enc-dec solves a 3-point system
    for encoder and decoder layer costs separately.
    """
    rep = dataclasses.replace

    def compile_costs(c):
        return _extract_costs(
            _lower_and_compile(c, shape, mesh, dax, n_chips, profile, kv_shard))

    def compile_costs_for(c, shp):
        return _extract_costs(
            _lower_and_compile(c, shp, mesh, dax, n_chips, profile, kv_shard))

    def lin(f1, f2, units):
        # Per-layer deltas clamp at >= 0: XLA occasionally folds more at one
        # depth than another, and a negative per-layer cost is unphysical.
        out = {}
        for k in ("flops", "bytes", "coll"):
            out[k] = f1[k] + (units - 1) * max(f2[k] - f1[k], 0.0)
        kinds = set(f1["coll_by_kind"]) | set(f2["coll_by_kind"])
        out["coll_by_kind"] = {
            k: f1["coll_by_kind"].get(k, 0.0)
            + (units - 1) * max(f2["coll_by_kind"].get(k, 0.0)
                                - f1["coll_by_kind"].get(k, 0.0), 0.0)
            for k in kinds
        }
        return out

    base = rep(cfg, scan_unroll=True, remat=cfg.remat)

    # Attention-free archs are exactly linear in sequence length, but their
    # inner chunk scan (64-token chunks) makes long-seq unrolled variants
    # expensive to compile: evaluate at seq/8 and scale (exact — rwkv6's
    # chunked algebra does identical per-chunk work).
    if cfg.family == "ssm" and shape.kind != "decode" and shape.seq_len > 8192:
        scale = 8
        small = dataclasses.replace(shape, seq_len=shape.seq_len // scale)
        f1 = compile_costs_for(rep(base, n_layers=1), small)
        f2 = compile_costs_for(rep(base, n_layers=2), small)
        out = lin(f1, f2, cfg.n_layers)
        for k in ("flops", "bytes", "coll"):
            out[k] *= scale
        out["coll_by_kind"] = {k: v * scale for k, v in out["coll_by_kind"].items()}
        return out

    if cfg.family == "vlm":
        ce = cfg.cross_attn_every
        units = cfg.n_layers // ce
        f1 = compile_costs(rep(base, n_layers=ce))
        f2 = compile_costs(rep(base, n_layers=2 * ce))
        return lin(f1, f2, units)
    if cfg.family == "enc_dec":
        f11 = compile_costs(rep(base, n_layers=1, n_enc_layers=1))
        f21 = compile_costs(rep(base, n_layers=1, n_enc_layers=2))
        f12 = compile_costs(rep(base, n_layers=2, n_enc_layers=1))
        out = {}
        for k in ("flops", "bytes", "coll"):
            enc_c = f21[k] - f11[k]
            dec_c = f12[k] - f11[k]
            const = f11[k] - enc_c - dec_c
            out[k] = const + cfg.n_enc_layers * enc_c + cfg.n_layers * dec_c
        kinds = (set(f11["coll_by_kind"]) | set(f21["coll_by_kind"])
                 | set(f12["coll_by_kind"]))
        out["coll_by_kind"] = {}
        for k in kinds:
            a = f11["coll_by_kind"].get(k, 0.0)
            e = f21["coll_by_kind"].get(k, 0.0) - a
            d = f12["coll_by_kind"].get(k, 0.0) - a
            out["coll_by_kind"][k] = (a - e - d) + cfg.n_enc_layers * e + cfg.n_layers * d
        return out
    f1 = compile_costs(rep(base, n_layers=1))
    f2 = compile_costs(rep(base, n_layers=2))
    return lin(f1, f2, cfg.n_layers)


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            cfg_override=None, profile: str = "fsdp",
            kv_shard: str = "heads") -> dict:
    cfg = cfg_override or cfgbase.get(arch)
    shape = cfgbase.INPUT_SHAPES[shape_name]
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    dax = meshlib.data_axes(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()

    result: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "family": cfg.family, "kind": shape.kind,
    }

    # 1) Production module: full depth, scanned — proves lower+compile and
    #    gives the per-device memory analysis.
    compiled = _lower_and_compile(cfg, shape, mesh, dax, n_chips, profile,
                                  kv_shard)
    t_full = time.time() - t0
    mem = compiled.memory_analysis()

    # 2) Roofline costs: layer-extrapolated from unrolled micro-variants.
    costs = extrapolated_costs(cfg, shape, mesh, dax, n_chips, profile,
                               kv_shard)
    t_cost = time.time() - t0 - t_full

    flops, bytes_accessed, coll_total = costs["flops"], costs["bytes"], costs["coll"]
    peaks = meshlib.chip_peaks(meshlib.PRODUCTION_KIND)
    compute_s = flops / peaks["flops_bf16"]
    memory_s = bytes_accessed / peaks["hbm_bw"]
    collective_s = coll_total / peaks["ici_bw"]

    n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = model_flops(cfg, n_tokens, train=shape.kind == "train")

    result.update(
        ok=True,
        compile_s=round(t_full, 1),
        cost_extrapolation_s=round(t_cost, 1),
        n_chips=n_chips,
        hlo_flops=flops,
        hlo_bytes=bytes_accessed,
        collective_bytes=coll_total,
        collectives=costs["coll_by_kind"],
        compute_term_s=compute_s,
        memory_term_s=memory_s,
        collective_term_s=collective_s,
        dominant=max(
            [("compute", compute_s), ("memory", memory_s),
             ("collective", collective_s)], key=lambda kv: kv[1])[0],
        model_flops=mf,
        useful_flops_ratio=(mf / (flops * n_chips) if flops else 0.0),
        bytes_per_device={
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "argument": mem.argument_size_in_bytes,
            "generated_code": mem.generated_code_size_in_bytes,
        },
    )
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-cached", action="store_true")
    ap.add_argument("--profile", default="fsdp", choices=["fsdp", "tp_only"],
                    help="param sharding profile (tp_only: serving, §Perf)")
    ap.add_argument("--kv-shard", default="heads", choices=["heads", "seq"],
                    help="decode cache sharding over 'model' (§Perf)")
    ap.add_argument("--perf", default=None,
                    help="comma list of cfg overrides, e.g. "
                         "attn_impl=chunked,loss_vocab_chunk=16384")
    args = ap.parse_args()

    overrides = {}
    if args.perf:
        for kv in args.perf.split(","):
            k, v = kv.split("=")
            overrides[k] = int(v) if v.isdigit() else v

    os.makedirs(args.out, exist_ok=True)
    combos: list[tuple[str, str, bool]] = []
    archs = cfgbase.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(cfgbase.INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    n_ok = 0
    for arch, shape, mp in combos:
        suffix = ""
        if args.profile != "fsdp":
            suffix += f"__{args.profile}"
        if args.kv_shard != "heads":
            suffix += f"__kv-{args.kv_shard}"
        if overrides:
            suffix += "__" + "_".join(f"{k}-{v}" for k, v in overrides.items())
        tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}{suffix}"
        path = os.path.join(args.out, tag + ".json")
        if args.skip_cached and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("ok"):
                    print(f"[cached] {tag}")
                    n_ok += 1
                    continue
        print(f"[run] {tag} ...", flush=True)
        try:
            cfg_override = None
            if overrides:
                cfg_override = dataclasses.replace(cfgbase.get(arch), **overrides)
            res = run_one(arch, shape, multi_pod=mp, profile=args.profile,
                          cfg_override=cfg_override, kv_shard=args.kv_shard)
            res["profile"] = args.profile
            res["overrides"] = overrides
            n_ok += 1
        except Exception as e:  # record failures — they are bugs to fix
            res = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "ok": False, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            print(f"  FAILED: {res['error']}", flush=True)
        with open(path, "w") as f:
            json.dump(res, f, indent=1, default=str)
        if res.get("ok"):
            print(
                f"  ok compile={res['compile_s']}s "
                f"cost_x={res['cost_extrapolation_s']}s "
                f"dominant={res['dominant']} "
                f"terms(ms)=[{1e3*res['compute_term_s']:.2f} c / "
                f"{1e3*res['memory_term_s']:.2f} m / "
                f"{1e3*res['collective_term_s']:.2f} coll] "
                f"useful={res['useful_flops_ratio']:.2f}",
                flush=True,
            )
    print(f"done: {n_ok}/{len(combos)} ok")
    if n_ok < len(combos):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
