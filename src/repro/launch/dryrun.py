"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. derives ShapeDtypeStruct stand-ins for every input (params, optimizer
     state, batch, KV/SSM caches) — no device allocation anywhere,
  3. resolves NamedShardings from the logical-axes trees,
  4. ``jax.jit(step).lower(...).compile()`` — sharding mismatches, OOM-scale
     layouts and unsupported collectives fail HERE, which is the point,
  5. records memory_analysis / cost_analysis / parsed collective stats to
     ``experiments/dryrun/<cell>.json`` for the roofline table.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k [--multipod]
  python -m repro.launch.dryrun --all [--multipod] [--arch-filter moe]
  python -m repro.launch.dryrun --graph asymp_cc_prod   (paper's own config)
  python -m repro.launch.dryrun --graph asymp_cc_crowded_prod
      (crowded tick: deferred-delivery ring + throttle riders lower on the
       production mesh like the plain and async ticks)
"""
from __future__ import annotations

# A CPU-only structural tool: its 512 placeholder devices are CPU devices,
# and both settings MUST precede any jax import — jax locks the platform
# and the device count on first initialization.
import os  # noqa: E402
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))

import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, get_graph_config, list_archs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import ShardingRules, use_mesh_rules
from repro.launch.mesh import make_production_mesh
from repro.models import encdec as encdec_mod
from repro.models import transformer as transformer_mod
from repro.models.layers import split_params
from repro.roofline import analysis as roofline
from repro.roofline import probes
from repro.serve import engine as serve_engine
from repro.train import optimizer as opt_mod
from repro.train import trainer as trainer_mod

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# ======================================================================
def rules_for(cfg: ModelConfig, mesh=None) -> ShardingRules:
    """Arch-aware rule overrides (all decisions logged for EXPERIMENTS.md).

    Head-count divisibility is decided *semantically* here: sharding the
    flattened H*hd projection when H doesn't divide the model axis would
    split shards across head boundaries (GSPMD reshards every reshape), so
    those archs replicate attention heads instead (hymba: 25 heads;
    granite MQA: kv=1; chatglm/glm4: kv=2; phi/qwen/chameleon: kv=8)."""
    rules = ShardingRules()
    over = {}
    if not cfg.fsdp:
        over["fsdp"] = ((),)
    if mesh is not None and cfg.num_heads:
        tp = mesh.shape.get("model", 1)
        if cfg.num_heads % tp != 0:
            over["q_proj"] = ((),)
            over["act_heads"] = ((),)
            rules.log.append(("rules", "q_proj", cfg.num_heads, (),
                              f"heads {cfg.num_heads} %% model {tp}"))
        if cfg.num_kv_heads % tp != 0 and not cfg.use_mla:
            over["kv_proj"] = ((),)
            over["kv_heads"] = ((),)
            rules.log.append(("rules", "kv_proj", cfg.num_kv_heads, (),
                              f"kv_heads {cfg.num_kv_heads} %% model {tp}"))
    if mesh is not None and cfg.ssm_state:
        tp = mesh.shape.get("model", 1)
        if cfg.ssm_heads % tp != 0:
            over["ssm_heads"] = ((),)
    if over:
        rules = rules.override(**over)
    return rules


def sharding_tree(mesh, rules, axes_tree, shapes_tree, tag: str):
    """axes tree (tuple leaves) x shapes tree -> NamedSharding tree."""
    def mk(a, s):
        spec = rules.resolve(mesh, a, s.shape, tag)
        return NamedSharding(mesh, spec)
    return jax.tree.map(mk, axes_tree, shapes_tree, is_leaf=opt_mod.is_axes)


def state_shapes_and_axes(cfg: ModelConfig):
    """(TrainState shapes, TrainState logical axes) without allocation."""
    box = {}

    def build():
        key = jax.random.PRNGKey(0)
        ptree = (encdec_mod.init_encdec(key, cfg) if cfg.encdec
                 else transformer_mod.init_lm(key, cfg))
        params, axes = split_params(ptree)
        box["axes"] = axes
        opt = opt_mod.get_optimizer(cfg.optimizer)
        return trainer_mod.TrainState(params, opt.init(params),
                                      jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(build)
    opt = opt_mod.get_optimizer(cfg.optimizer)
    axes = trainer_mod.TrainState(box["axes"], opt.state_axes(box["axes"]), ())
    return shapes, axes


def params_shapes_and_axes(cfg: ModelConfig):
    box = {}

    def build():
        key = jax.random.PRNGKey(0)
        ptree = (encdec_mod.init_encdec(key, cfg) if cfg.encdec
                 else transformer_mod.init_lm(key, cfg))
        params, axes = split_params(ptree)
        box["axes"] = axes
        return params

    return jax.eval_shape(build), box["axes"]


def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> tuple[dict, dict]:
    """(shapes, logical axes) for the input batch of a train step."""
    B, S = shape.global_batch, shape.seq_len
    shapes = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
              "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    axes = {"tokens": ("batch", None), "labels": ("batch", None)}
    if cfg.encdec:
        shapes["features"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model),
                                                  jnp.bfloat16)
        axes["features"] = ("batch", None, None)
    return shapes, axes


def cache_specs(cfg: ModelConfig, batch: int, s_max: int):
    if cfg.encdec:
        shapes = jax.eval_shape(
            partial(encdec_mod.init_dec_cache, cfg, batch, s_max))
        axes = encdec_mod.dec_cache_axes(cfg)
    else:
        shapes = jax.eval_shape(
            partial(transformer_mod.init_cache, cfg, batch, s_max))
        axes = transformer_mod.cache_axes(cfg)
    return shapes, axes


# ======================================================================
def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    """Lower+compile one cell; returns (compiled, record dict)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return None, {"arch": arch, "shape": shape_name,
                      "multi_pod": multi_pod, "status": "skip(full-attn)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, mesh)
    t0 = time.time()
    with use_mesh_rules(mesh, rules):
        if shape.kind == "train":
            state_shapes, state_axes = state_shapes_and_axes(cfg)
            b_shapes, b_axes = batch_specs(cfg, shape)
            state_sh = sharding_tree(mesh, rules, state_axes, state_shapes, "state")
            b_sh = sharding_tree(mesh, rules, b_axes, b_shapes, "batch")
            state_in = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                state_shapes, state_sh)
            batch_in = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                b_shapes, b_sh)
            step = trainer_mod.make_train_step(cfg)
            jitted = jax.jit(step, donate_argnums=(0,))
            lowered = jitted.lower(state_in, batch_in)
        else:
            p_shapes, p_axes = params_shapes_and_axes(cfg)
            p_sh = sharding_tree(mesh, rules, p_axes, p_shapes, "params")
            params_in = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                p_shapes, p_sh)
            c_shapes, c_axes = cache_specs(cfg, shape.global_batch, shape.seq_len)
            c_sh = sharding_tree(mesh, rules, c_axes, c_shapes, "cache")
            caches_in = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                c_shapes, c_sh)
            B = shape.global_batch
            bspec = NamedSharding(mesh, rules.resolve(
                mesh, ("batch", None), (B, 1), "tok"))
            if shape.kind == "prefill":
                step = serve_engine.make_prefill_step(cfg)
                b_shapes, b_axes = batch_specs(cfg, shape)
                b_sh = sharding_tree(mesh, rules, b_axes, b_shapes, "batch")
                batch_in = jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                    b_shapes, b_sh)
                batch_in.pop("labels")
                jitted = jax.jit(step, donate_argnums=(2,))
                lowered = jitted.lower(params_in, batch_in, caches_in)
            else:  # decode
                step = serve_engine.make_decode_step(cfg)
                tok_in = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=bspec)
                jitted = jax.jit(step, donate_argnums=(2,))
                lowered = jitted.lower(params_in, tok_in, caches_in)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mf = roofline.model_flops(cfg, shape, shape.kind)
    chips = 512 if multi_pod else 256
    record = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "model_flops_global": mf,
        "model_flops_per_chip": mf / chips,
        "sharding_fallbacks": [
            {"tag": t, "axis": a, "dim": d, "reason": r}
            for (t, a, d, ch, r) in rules.log[:40]],
    }
    # whole-compile roofline (rolled scans: under-counts loop bodies; kept
    # for reference) + probe-composed roofline (authoritative, single-pod)
    roof_rolled = roofline.analyze(compiled)
    record["roofline_rolled"] = roof_rolled.to_dict()
    if not multi_pod:
        try:
            pc = probes.cell_costs(cfg, shape, mesh, rules)
            terms = {
                "compute_s": pc["flops"] / roofline.PEAK_FLOPS,
                "memory_s": pc["bytes"] / roofline.HBM_BW,
                "collective_s": pc["wire"] / (2 * roofline.ICI_BW),
            }
            dom = max(terms, key=terms.get).replace("_s", "")
            record["roofline"] = {
                "flops": pc["flops"], "bytes_accessed": pc["bytes"],
                "collective_wire_bytes": pc["wire"], **terms,
                "dominant": dom, "pieces": pc["pieces"],
            }
            record["useful_flops_ratio"] = (
                (mf / chips) / pc["flops"] if pc["flops"] else 0.0)
        except Exception as e:  # noqa: BLE001
            record["roofline"] = {"error": f"{type(e).__name__}: {e}",
                                  "dominant": roof_rolled.dominant}
            record["probe_traceback"] = traceback.format_exc()[-1500:]
    else:
        record["roofline"] = {"dominant": roof_rolled.dominant,
                              "note": "multi-pod gate only; see pod1 record"}
    return compiled, record


# ======================================================================
def lower_graph_cell(name: str, multi_pod: bool):
    """Dry-run the ASYMP engine tick on the production mesh."""
    from repro.core import engine as ge
    cfg = get_graph_config(name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_workers = 512 if multi_pod else 256
    t0 = time.time()
    compiled, info = ge.lower_tick_for_mesh(cfg, mesh, n_workers)
    t = time.time() - t0
    mem = compiled.memory_analysis()
    roof = roofline.analyze(compiled)
    record = {
        "arch": name, "shape": f"V={cfg.num_vertices} deg={cfg.avg_degree}",
        "multi_pod": multi_pod, "status": "ok", "chips": n_workers,
        "compile_s": round(t, 1),
        "memory": {"argument_bytes": mem.argument_size_in_bytes,
                   "temp_bytes": mem.temp_size_in_bytes},
        "roofline": roof.to_dict(),
        "engine": info,
    }
    return compiled, record


# ======================================================================
def run_cells(cells, multi_pod: bool, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for arch, shape_name in cells:
        tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
        path = os.path.join(out_dir, tag + ".json")
        if os.path.exists(path):
            print(f"[skip-cached] {tag}")
            with open(path) as f:
                results.append(json.load(f))
            continue
        print(f"[lower+compile] {tag} ...", flush=True)
        try:
            compiled, record = lower_cell(arch, shape_name, multi_pod)
            if compiled is not None:
                print(compiled.memory_analysis())
                ca = compiled.cost_analysis()
                flops = (ca[0] if isinstance(ca, (list, tuple)) else ca).get(
                    "flops", 0.0) if ca else 0.0
                print(f"  flops/chip={flops:.3e} "
                      f"dominant={record['roofline']['dominant']} "
                      f"compile={record['compile_s']}s")
        except Exception as e:  # noqa: BLE001 — record failures, keep going
            record = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                      "status": f"FAIL: {type(e).__name__}: {e}",
                      "traceback": traceback.format_exc()[-2000:]}
            print(f"  FAILED: {e}")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        results.append(record)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--graph", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--arch-filter", default="")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    if args.graph:
        os.makedirs(args.out, exist_ok=True)
        compiled, record = lower_graph_cell(args.graph, args.multipod)
        tag = f"graph_{args.graph}__{'pod2' if args.multipod else 'pod1'}"
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(record, f, indent=1)
        print(json.dumps({k: v for k, v in record.items()
                          if k not in ("roofline",)}, indent=1))
        print("dominant:", record["roofline"]["dominant"])
        return

    if args.all:
        cells = [(a, s) for a in list_archs() if args.arch_filter in a
                 for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]
    results = run_cells(cells, args.multipod, args.out)
    ok = sum(1 for r in results if r["status"] == "ok")
    skip = sum(1 for r in results if r["status"].startswith("skip"))
    fail = len(results) - ok - skip
    print(f"\n== dry-run summary: {ok} ok, {skip} skipped(reasoned), {fail} FAILED ==")
    if fail:
        for r in results:
            if r["status"].startswith("FAIL"):
                print(" ", r["arch"], r["shape"], r["status"][:200])


if __name__ == "__main__":
    main()
