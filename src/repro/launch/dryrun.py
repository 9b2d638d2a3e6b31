import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production meshes, prove memory fit, and extract the
roofline terms (FLOPs / bytes from cost_analysis, collective bytes parsed
from the compiled HLO).

MUST be invoked as its own process (the XLA_FLAGS line above runs before
any jax import): ``PYTHONPATH=src python -m repro.launch.dryrun --arch all
--shape all --mesh both --out results/dryrun``.
"""

import argparse
import json
import re
import time

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, SHAPES, cell_is_skipped, get_config
from ..core.distributed import DistBuildConfig, build_local, query_local
from ..core.summarization import SummarizationConfig
from ..models import shardctx
from ..models.steps import TrainConfig, make_decode_step, make_prefill_step, make_train_step
from ..models.transformer import ModelConfig, init_params, make_cache
from ..train.optimizer import AdamW, AdamWConfig
from .hlo_analysis import analyze_hlo, count_jaxpr_bytes, count_jaxpr_flops
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, dp_axes, make_production_mesh
from .specs import (
    batch_specs,
    cache_specs,
    constrain_tree,
    drop_axis_specs,
    param_specs,
    to_shardings,
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^\s]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device bytes and op counts of every collective in the module."""
    out: dict = {}
    for type_str, op in _COLL_RE.findall(hlo_text):
        b = _shape_bytes(type_str)
        d = out.setdefault(op, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
    return out


def model_flops(cfg: ModelConfig, shape, n_params_active: int) -> float:
    """Analytic MODEL_FLOPS for the useful-compute ratio (see DESIGN.md):
    matmul params x 2 per token (x3 for train), plus attention context and
    recurrent-state terms."""
    kinds = cfg.layer_kinds
    hd, h = cfg.hd, cfg.n_heads
    s = shape.seq_len
    per_tok_attn = 0.0
    for k in kinds:
        if k == "attn":
            ctx = s if shape.kind == "decode" else s / 2
            per_tok_attn += 4 * ctx * h * hd
        elif k == "local":
            ctx = min(cfg.window, s)
            per_tok_attn += 4 * ctx * h * hd
        elif k == "rwkv":
            per_tok_attn += 4 * cfg.d_model * hd  # state outer-products
        elif k == "rec":
            r = cfg.d_rnn or cfg.d_model
            per_tok_attn += 6 * r  # elementwise recurrence
    if shape.kind == "decode":
        tokens = shape.global_batch  # one new token per sequence
    else:
        tokens = shape.global_batch * s
    mult = 3.0 if shape.kind == "train" else 1.0
    return mult * tokens * (2 * n_params_active + per_tok_attn)


def abstract_batch(cfg: ModelConfig, shape) -> dict:
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if cfg.frontend == "audio":
        return {
            "features": sds((b, s, cfg.d_frontend), jnp.float32),
            "targets": sds((b, s), jnp.int32),
            "mask": sds((b, s), jnp.bool_),
        }
    if cfg.frontend == "vision":
        return {
            "tokens": sds((b, s - cfg.n_vis_tokens), jnp.int32),
            "patches": sds((b, cfg.n_vis_tokens, cfg.d_frontend), jnp.float32),
        }
    return {"tokens": sds((b, s), jnp.int32)}


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    batch = abstract_batch(cfg, shape)
    if shape.kind == "decode":
        cache = jax.eval_shape(
            lambda: make_cache(cfg, shape.global_batch, shape.seq_len)
        )
        token = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        return {"cache": cache, "token": token}
    return {"batch": batch}


def _grad_accum_for(cfg: ModelConfig, shape) -> int:
    """Bound per-microbatch tokens so rematted activations fit HBM."""
    tokens = shape.global_batch * shape.seq_len
    target = 131072  # tokens per microbatch (global)
    g = max(1, tokens // target)
    while shape.global_batch % g:
        g -= 1
    return g


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline") -> dict:
    with shardctx.ctx(make_production_mesh(multi_pod=multi_pod), dp_axes(multi_pod)):
        return _lower_cell(arch, shape_name, multi_pod, variant)


def _pad_heads(cfg: ModelConfig, tp: int) -> ModelConfig:
    """TPU adaptation (§Perf): pad the attention head count up to a multiple
    of the TP axis so head-sharded layouts are even. Ragged head counts
    (llava 56H, minicpm 40H vs 16-way TP) force GSPMD into "involuntary full
    rematerialization" gathers and score-matrix partial-sum all-reduces;
    padding trades a few % extra attention FLOPs for their removal."""
    import dataclasses as dc

    h = cfg.n_heads
    hp = -(-h // tp) * tp
    if hp == h or not any(k in ("attn", "local") for k in cfg.layer_kinds):
        return cfg
    if cfg.mla is not None:
        return dc.replace(cfg, n_heads=hp, n_kv=hp, head_dim=cfg.hd)
    if hp % cfg.n_kv:
        return cfg  # GQA grouping wouldn't stay integral; keep as is
    return dc.replace(cfg, n_heads=hp, head_dim=cfg.hd)


def _lower_cell(arch: str, shape_name: str, multi_pod: bool,
                variant: str = "baseline") -> dict:
    """variant: "baseline" = paper-faithful framework defaults (FSDP+TP
    everywhere); "opt" = beyond-baseline §Perf schedule: ZeRO-1 gather-once
    weights for train, TP-only param sharding for serving steps, and
    TP-even head padding."""
    cfg = get_config(arch)
    if variant == "opt":
        cfg = _pad_heads(cfg, 16)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size

    params_abs = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = param_specs(params_abs, mesh)
    psh = to_shardings(pspecs, mesh)
    opt_variant = variant == "opt"
    if opt_variant and shape.kind == "decode":
        # decode re-reads every weight per token -> TP-only params (no FSDP
        # re-gathers). Prefill keeps FSDP: each weight is used once per
        # prompt, and TP-only regressed fine-grained MoE prefill (§Perf).
        pspecs = drop_axis_specs(pspecs, "data")
        psh = to_shardings(pspecs, mesh)

    t0 = time.time()
    if shape.kind == "train":
        opt = AdamW(AdamWConfig())
        ostate_abs = jax.eval_shape(opt.init, params_abs)
        osh = to_shardings({k: pspecs for k in ostate_abs}, mesh)
        tcfg = TrainConfig(grad_accum=_grad_accum_for(cfg, shape), remat=True)
        param_gather = grad_constrain = None
        if opt_variant:
            # ZeRO-1 gather-once, but ONLY for dense (<=3-D incl. the layer
            # stack dim) weights: gathering stacked MoE expert tensors blew
            # the dispatch all-to-all up 70x (refuted iteration, §Perf) —
            # experts stay FSDP-sharded.
            gathered_all = drop_axis_specs(pspecs, "data")
            gathered = jax.tree.map(
                lambda leaf, g_spec, spec: g_spec if leaf.ndim <= 3 else spec,
                params_abs, gathered_all, pspecs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            param_gather = lambda p: constrain_tree(p, gathered, mesh)
            grad_constrain = lambda g: constrain_tree(g, pspecs, mesh)
        step = make_train_step(cfg, tcfg, opt, param_gather, grad_constrain)
        batch_abs = abstract_batch(cfg, shape)
        bsh = to_shardings(batch_specs(batch_abs, mesh, multi_pod), mesh)
        lowered = jax.jit(
            step,
            in_shardings=(psh, osh, bsh, None),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1),
        ).lower(params_abs, ostate_abs, batch_abs, jax.ShapeDtypeStruct((), jnp.int32))
        jaxpr_of = jax.make_jaxpr(step)(
            params_abs, ostate_abs, batch_abs, jax.ShapeDtypeStruct((), jnp.int32)
        )
        extra = {"grad_accum": tcfg.grad_accum}
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg)
        batch_abs = abstract_batch(cfg, shape)
        bsh = to_shardings(batch_specs(batch_abs, mesh, multi_pod), mesh)
        lowered = jax.jit(step, in_shardings=(psh, bsh)).lower(params_abs, batch_abs)
        jaxpr_of = jax.make_jaxpr(step)(params_abs, batch_abs)
        extra = {}
    else:  # decode
        step = make_decode_step(cfg)
        spec = input_specs(arch, shape_name)
        cache_abs, token_abs = spec["cache"], spec["token"]
        csh = to_shardings(cache_specs(cache_abs, mesh, multi_pod), mesh)
        tsh = to_shardings(
            batch_specs({"t": token_abs}, mesh, multi_pod)["t"], mesh
        )
        lowered = jax.jit(
            step, in_shardings=(psh, csh, tsh), out_shardings=(None, csh),
            donate_argnums=(1,),
        ).lower(params_abs, cache_abs, token_abs)
        jaxpr_of = jax.make_jaxpr(step)(params_abs, cache_abs, token_abs)
        extra = {}
    extra["variant"] = variant
    t_lower = time.time() - t0

    # global FLOPs + HBM traffic from the jaxpr (scan-trip-aware)
    jaxpr_flops = count_jaxpr_flops(jaxpr_of)
    jaxpr_bytes = count_jaxpr_bytes(jaxpr_of)

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = analyze_hlo(compiled.as_text())
    colls = hlo["collectives"]
    coll_bytes = hlo["collective_bytes"]

    n_act = cfg.n_params_active()
    n_tot = cfg.n_params()
    mf = model_flops(cfg, shape, n_act)
    flops_dev = jaxpr_flops / n_dev
    bytes_dev = jaxpr_bytes / n_dev

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "n_params": n_tot,
        "n_params_active": n_act,
        "mem_per_device": {
            "args_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 1e9, 3),
        },
        "cost_per_device": {"flops": flops_dev, "bytes": bytes_dev},
        "flops_global_jaxpr": jaxpr_flops,
        "collectives": colls,
        "collective_bytes_per_device": coll_bytes,
        "roofline_s": {
            "compute": flops_dev / PEAK_FLOPS_BF16,
            "memory": bytes_dev / HBM_BW,
            "collective": coll_bytes / ICI_BW,
        },
        "model_flops_total": mf,
        "useful_flops_ratio": round(mf / max(jaxpr_flops, 1.0), 4),
        **extra,
    }
    terms = result["roofline_s"]
    result["bottleneck"] = max(terms, key=terms.get)
    return result


# ---------------------------------------------------------------------------
# Coconut cells: the paper's own pipeline on the production mesh
# ---------------------------------------------------------------------------
COCONUT_CELLS = {
    "coconut-build": {"n_series": 1 << 26, "series_len": 256},
    # §Perf iteration: exchange summaries+ids only (non-materialized), raw
    # series stay put — queries fetch verified candidates by id instead.
    "coconut-build-nonmat": {"n_series": 1 << 26, "series_len": 256,
                             "materialized": False},
    "coconut-query": {"n_series": 1 << 26, "series_len": 256, "m": 16, "k": 10,
                      "verify_budget": 256},
}


def lower_coconut(cell: str, multi_pod: bool) -> dict:
    import functools

    from jax.sharding import PartitionSpec as P

    spec = COCONUT_CELLS[cell]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh.axis_names  # shard the index over ALL axes (one flat range)
    n_dev = mesh.devices.size
    scfg = SummarizationConfig(series_len=spec["series_len"], n_segments=16, card_bits=8)
    dcfg = DistBuildConfig(summarization=scfg, capacity_slack=2.0,
                           materialized=spec.get("materialized", True))
    n, sl = spec["n_series"], spec["series_len"]
    sds = jax.ShapeDtypeStruct
    sh = lambda s: jax.NamedSharding(mesh, s)

    t0 = time.time()
    if cell.startswith("coconut-build"):
        out_specs = {
            "invalid": P(axes), "keys": P(axes), "ids": P(axes),
            "sym": P(axes), "n_valid": P(axes), "overflow": P(),
        }
        if dcfg.materialized:
            out_specs["series"] = P(axes)

        def build(series, ids):
            f = jax.shard_map(
                functools.partial(build_local, cfg=dcfg, axis_names=tuple(axes)),
                mesh=mesh, in_specs=(P(axes), P(axes)),
                out_specs=out_specs,
            )
            return f(series, ids)

        lowered = jax.jit(build, in_shardings=(sh(P(axes)), sh(P(axes)))).lower(
            sds((n, sl), jnp.float32), sds((n,), jnp.int32)
        )
        jaxpr_of = jax.make_jaxpr(build)(sds((n, sl), jnp.float32), sds((n,), jnp.int32))
    else:
        ln = n // n_dev
        cap = int(ln / n_dev * dcfg.capacity_slack)
        rn = n_dev * cap * n_dev  # global rows of the exchanged index

        def query(index, queries):
            f = jax.shard_map(
                functools.partial(
                    query_local, cfg=dcfg, axis_names=tuple(axes),
                    k=spec["k"], verify_budget=spec["verify_budget"],
                ),
                mesh=mesh,
                in_specs=({"invalid": P(axes), "keys": P(axes), "ids": P(axes),
                           "sym": P(axes), "n_valid": P(axes), "overflow": P(),
                           "series": P(axes)}, P()),
                out_specs=(P(), P()), check_vma=False,
            )
            return f(index, queries)

        index_abs = {
            "invalid": sds((rn,), jnp.int32), "keys": sds((rn, 4), jnp.uint32),
            "ids": sds((rn,), jnp.int32), "sym": sds((rn, 16), jnp.int32),
            "n_valid": sds((n_dev,), jnp.int32), "overflow": sds((), jnp.int32),
            "series": sds((rn, sl), jnp.float32),
        }
        ish = jax.tree.map(
            lambda l: sh(P(axes)) if l.ndim else sh(P()), index_abs)
        ish["overflow"] = sh(P())
        lowered = jax.jit(query, in_shardings=(ish, sh(P()))).lower(
            index_abs, sds((spec["m"], sl), jnp.float32)
        )
        jaxpr_of = jax.make_jaxpr(query)(index_abs, sds((spec["m"], sl), jnp.float32))
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    hlo = analyze_hlo(compiled.as_text())
    colls = hlo["collectives"]
    coll_bytes = hlo["collective_bytes"]
    flops_dev = count_jaxpr_flops(jaxpr_of) / n_dev
    bytes_dev = count_jaxpr_bytes(jaxpr_of) / n_dev
    result = {
        "arch": cell, "shape": f"{n>>20}M x {sl}",
        "mesh": "2x16x16" if multi_pod else "16x16", "n_devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "mem_per_device": {
            "args_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "total_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 1e9, 3),
        },
        "cost_per_device": {"flops": flops_dev, "bytes": bytes_dev},
        "collectives": colls,
        "collective_bytes_per_device": coll_bytes,
        "roofline_s": {
            "compute": flops_dev / PEAK_FLOPS_BF16,
            "memory": bytes_dev / HBM_BW,
            "collective": coll_bytes / ICI_BW,
        },
    }
    result["bottleneck"] = max(result["roofline_s"], key=result["roofline_s"].get)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--coconut", action="store_true", help="also run coconut cells")
    ap.add_argument("--variant", default="baseline", choices=["baseline", "opt"])
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    cells = []
    for a in archs:
        for s in shapes:
            reason = cell_is_skipped(a, s)
            if reason:
                print(f"SKIP {a} x {s}: {reason}")
                continue
            cells.append((a, s))
    if args.list:
        for a, s in cells:
            print(f"{a} x {s}")
        return

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for multi_pod in meshes:
        mesh_tag = "multi" if multi_pod else "single"
        if args.variant != "baseline":
            mesh_tag += f"_{args.variant}"
        for a, s in cells:
            tag = f"{a}__{s}__{mesh_tag}"
            try:
                res = lower_cell(a, s, multi_pod, args.variant)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)
                r = res["roofline_s"]
                print(
                    f"OK {tag}: compile={res['compile_s']}s "
                    f"mem={res['mem_per_device']['total_gb']}GB/dev "
                    f"compute={r['compute']:.4f}s memory={r['memory']:.4f}s "
                    f"coll={r['collective']:.4f}s -> {res['bottleneck']}",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 — report and continue
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {str(e)[:300]}", flush=True)
        if args.coconut:
            for cell in COCONUT_CELLS:
                tag = f"{cell}__{mesh_tag}"
                try:
                    res = lower_coconut(cell, multi_pod)
                    with open(os.path.join(args.out, tag + ".json"), "w") as f:
                        json.dump(res, f, indent=1)
                    print(f"OK {tag}: compile={res['compile_s']}s "
                          f"mem={res['mem_per_device']['total_gb']}GB/dev", flush=True)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    print(f"FAIL {tag}: {type(e).__name__}: {str(e)[:300]}", flush=True)
    print(f"dry-run complete; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
