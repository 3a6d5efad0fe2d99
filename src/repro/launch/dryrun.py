import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record memory/cost/collective analysis (EXPERIMENTS.md §Dry-run, §Roofline).

MUST be run as its own process (the XLA_FLAGS line above has to execute
before jax initializes devices — do not import this module from a live jax
process):

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out results/dryrun

One JSON per cell is written to --out; existing files are skipped (the
driver is resumable, so a killed run restarts where it left off).
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCHS, SHAPES, get_config, input_specs,
                           shape_applicable)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (ShardingRules, act_constraint,
                                   batch_shardings, cache_shardings,
                                   logit_constraint, opt_shardings,
                                   param_shardings)
from repro.models.config import ModelConfig
from repro.models.transformer import abstract_params
from repro.serving.decode import abstract_caches, decode_step, prefill
from repro.train.optimizer import abstract_opt_state
from repro.train.step import TrainConfig, make_train_step


def model_flops(cfg: ModelConfig, kind: str, batch: int, seq: int) -> float:
    """6*N*D (train) / 2*N*D (prefill) / 2*N*B (decode), N = active params."""
    n = cfg.n_active_params()
    if kind == "train":
        return 6.0 * n * batch * seq
    if kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch


def build_cell(cfg: ModelConfig, shape, mesh, tcfg: TrainConfig,
               *, embed_vocab_shard: bool = True, moe_tp: bool = False):
    """Returns (jitted_fn, abstract_args tuple)."""
    rules = ShardingRules(mesh)
    p_abs = abstract_params(cfg)
    p_sh = param_shardings(cfg, mesh, embed_vocab_shard=embed_vocab_shard)
    batch_abs = input_specs(cfg, shape)
    b_sh = batch_shardings(mesh, batch_abs)
    act = act_constraint(mesh, shape.batch, tp_act=tcfg.tp_act)
    lshard = logit_constraint(mesh, shape.batch, cfg.vocab)
    moe_fn = None
    if moe_tp and cfg.is_moe:
        from repro.launch.sharding import _batch_dim_spec
        from repro.models.layers import make_tp_moe_fn
        moe_fn = make_tp_moe_fn(mesh, _batch_dim_spec(mesh, shape.batch), cfg)

    if shape.kind == "train":
        o_abs = abstract_opt_state(p_abs)
        o_sh = opt_shardings(cfg, mesh, embed_vocab_shard=embed_vocab_shard)
        step = make_train_step(cfg, tcfg, act_shard=act, logit_shard=lshard,
                               moe_fn=moe_fn)
        fn = jax.jit(step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
        return fn, (p_abs, o_abs, batch_abs)

    if shape.kind == "prefill":
        c_sh = cache_shardings(cfg, mesh, shape.batch, shape.seq)
        logits_sh = rules.named(rules.resolve(
            (shape.batch, cfg.vocab), (None, "vocab")))
        def wrapped(params, batch):
            return prefill(params, cfg, batch, q_chunk=tcfg.q_chunk,
                           act_shard=act, moe_fn=moe_fn)
        fn = jax.jit(wrapped, in_shardings=(p_sh, b_sh),
                     out_shardings=(logits_sh, c_sh))
        return fn, (p_abs, batch_abs)

    # decode: one new token against a seq-S cache
    c_abs = abstract_caches(cfg, shape.batch, shape.seq)
    c_sh = cache_shardings(cfg, mesh, shape.batch, shape.seq)
    logits_sh = rules.named(rules.resolve(
        (shape.batch, cfg.vocab), (None, "vocab")))

    def wrapped(params, caches, inputs, pos):
        return decode_step(params, cfg, caches, inputs, pos)

    fn = jax.jit(wrapped,
                 in_shardings=(p_sh, c_sh, b_sh, None),
                 out_shardings=(logits_sh, c_sh),
                 donate_argnums=(1,))
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (p_abs, c_abs, batch_abs, pos_abs)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             tcfg: Optional[TrainConfig] = None,
             hlo_path: Optional[str] = None,
             mlstm_chunk: int = 0,
             embed_vocab_shard: bool = True,
             moe_tp: bool = False) -> Dict:
    import dataclasses as _dc
    cfg = get_config(arch)
    if mlstm_chunk:
        cfg = _dc.replace(cfg, mlstm_chunk=mlstm_chunk)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec: Dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "kind": shape.kind, "batch": shape.batch, "seq": shape.seq}
    if not ok:
        rec["status"] = "skipped"
        rec["skip_reason"] = why
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    tcfg = tcfg or TrainConfig()
    t0 = time.time()
    with mesh:
        fn, args = build_cell(cfg, shape, mesh, tcfg,
                              embed_vocab_shard=embed_vocab_shard,
                              moe_tp=moe_tp)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    if hlo_path:
        import gzip
        try:
            with gzip.open(hlo_path, "wt") as f:
                f.write(compiled.as_text())
        except Exception as e:
            rec["hlo_save_error"] = repr(e)
    info = hlo_analysis.analyze_compiled(compiled, lowered)
    # the dry run models a v5e pod on virtual CPU devices
    terms = hlo_analysis.roofline_from_info(
        info, hlo_analysis.CHIP_PEAKS["TPU v5 lite"])
    mf = model_flops(cfg, shape.kind, shape.batch, shape.seq)
    hlo_total = terms.device_flops * n_chips
    rec.update({
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "info": info,
        "roofline": terms.as_dict(),
        "model_flops_total": mf,
        "hlo_flops_total": hlo_total,
        "useful_flops_ratio": (mf / hlo_total) if hlo_total else None,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
    })
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="baseline",
                    help="experiment tag appended to output filenames")
    ap.add_argument("--causal-skip", action="store_true",
                    help="enable the causal-skip flash attention variant")
    ap.add_argument("--q-chunk", type=int, default=512)
    ap.add_argument("--kv-chunk", type=int, default=512)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--tp-act", action="store_true",
                    help="shard [B,S,d] activations over the model axis")
    ap.add_argument("--mlstm-chunk", type=int, default=0,
                    help="chunkwise-parallel mLSTM chunk size (§Perf-A)")
    ap.add_argument("--embed-replicated", action="store_true",
                    help="vocab-replicated embedding table (§Perf-C)")
    ap.add_argument("--moe-tp", action="store_true",
                    help="expert-parallel MoE dispatch over model (§Perf-B)")
    ap.add_argument("--attn-remat", action="store_true",
                    help="recompute attention tiles in backward (§Perf-C4)")
    ap.add_argument("--flash-cv", action="store_true",
                    help="custom-VJP flash attention (§Perf-C8)")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [
        a.replace("-", "_") for a in args.arch.split(",")]
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    tcfg = TrainConfig(remat=not args.no_remat, causal_skip=args.causal_skip,
                       q_chunk=args.q_chunk, kv_chunk=args.kv_chunk,
                       tp_act=args.tp_act, attn_remat=args.attn_remat,
                       flash_cv=args.flash_cv)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tagm = "multi" if mp else "single"
                path = os.path.join(
                    args.out, f"{arch}__{shape}__{tagm}__{args.tag}.json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip existing] {path}")
                    continue
                print(f"[cell] {arch} x {shape} x {tagm} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mp, tcfg,
                                   hlo_path=path.replace(".json", ".hlo.gz"),
                                   mlstm_chunk=args.mlstm_chunk,
                                   embed_vocab_shard=not args.embed_replicated,
                                   moe_tp=args.moe_tp)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": tagm,
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2, default=str)
                status = rec.get("status")
                if status == "ok":
                    r = rec["roofline"]
                    print(f"  ok: dominant={r['dominant']} "
                          f"t_comp={r['t_compute_s']:.4f}s "
                          f"t_mem={r['t_memory_s']:.4f}s "
                          f"t_coll={r['t_collective_s']:.4f}s "
                          f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)",
                          flush=True)
                elif status == "skipped":
                    print(f"  skipped: {rec['skip_reason']}")
                else:
                    print(f"  ERROR: {rec.get('error')}")


if __name__ == "__main__":
    main()
