"""§Perf hillclimb driver: run named experiment variants of one
(arch × shape × mesh) cell and log the three roofline terms per variant.

  PYTHONPATH=src python -m repro.launch.hillclimb --arch qwen2.5-14b \
      --shape train_4k --variants baseline,vp_embed,vp_embed+dots

Variants compose rule overrides + config/settings tweaks (see VARIANTS).
Results are appended to results/hillclimb.json.
"""

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402

from repro.configs import get_config       # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.dryrun import run_cell    # noqa: E402

# each variant: (rule_overrides, cfg_overrides, settings_overrides)
VARIANTS: dict[str, tuple[dict, dict, dict]] = {
    "baseline": ({}, {}, {}),
    # vocab-parallel embedding: table sharded on vocab only — kills the
    # SPMD involuntary-full-remat on the token gather
    "vp_embed": ({"table_embed": None}, {}, {}),
    # remat policy: save matmul outputs (incl. post-collective tensors) so
    # the backward recompute repeats no collectives
    "dots": ({}, {"remat_policy": "dots"}, {}),
    "noremat": ({}, {"remat": False}, {}),
    # Megatron-SP: residual stream sequence-sharded on "model" between
    # blocks (AR -> RS+AG pairs, 1/16th resident activations)
    "seqpar": ({"residual_length": "model"}, {}, {}),
    # microbatched gradient accumulation (memory lever)
    "micro4": ({}, {}, {"microbatches": 4}),
    # int8 gradient compression (pod-axis gradient reduction 4x lighter)
    "int8grad": ({}, {}, {"grad_compression": "int8"}),
    # no FSDP: weights replicated over "data" (for small models the
    # per-layer weight all-gathers cost more than the memory saved)
    "nofsdp": ({"embed": None, "table_embed": None}, {}, {}),
    # combos
    "vp+seqpar": ({"table_embed": None, "residual_length": "model"}, {}, {}),
    "vp+nofsdp": ({"table_embed": None, "embed": None}, {}, {}),
    "vp+seqpar+nofsdp": ({"table_embed": None, "residual_length": "model",
                          "embed": None}, {}, {}),
    "vp+seqpar+micro4": ({"table_embed": None, "residual_length": "model"},
                         {}, {"microbatches": 4}),
    "vp+dots": ({"table_embed": None}, {"remat_policy": "dots"}, {}),
    "vp+seqpar+dots": ({"table_embed": None, "residual_length": "model"},
                       {"remat_policy": "dots"}, {}),
    # replicate GQA kv heads (8 does not divide model=16; uneven sharding
    # makes the attention backward all-gather FULL-BATCH K/V grads)
    "kv_rep": ({"kv_heads": None, "activation_kv_heads": None}, {}, {}),
    "kv_rep+dots": ({"kv_heads": None, "activation_kv_heads": None},
                    {"remat_policy": "dots"}, {}),
    "kv_rep+dots+micro4": ({"kv_heads": None, "activation_kv_heads": None},
                           {"remat_policy": "dots"}, {"microbatches": 4}),
    "kv_rep+micro4": ({"kv_heads": None, "activation_kv_heads": None},
                      {}, {"microbatches": 4}),
    # bf16 cross-shard partial sums / backward ARs (halves AR bytes)
    "kv_rep+bf16comm": ({"kv_heads": None, "activation_kv_heads": None},
                        {"accum_dtype": "bfloat16"}, {}),
    "kv_rep+bf16comm+micro4": (
        {"kv_heads": None, "activation_kv_heads": None},
        {"accum_dtype": "bfloat16"}, {"microbatches": 4}),
    "kv_rep+bf16comm+dots+micro4": (
        {"kv_heads": None, "activation_kv_heads": None},
        {"accum_dtype": "bfloat16", "remat_policy": "dots"},
        {"microbatches": 4}),
    "kv_rep+bf16comm+micro8": (
        {"kv_heads": None, "activation_kv_heads": None},
        {"accum_dtype": "bfloat16"}, {"microbatches": 8}),
    "kv_rep+vp+bf16comm+micro8": (
        {"kv_heads": None, "activation_kv_heads": None, "table_embed": None},
        {"accum_dtype": "bfloat16"}, {"microbatches": 8}),
    "kv_rep+bf16comm+dots+micro8": (
        {"kv_heads": None, "activation_kv_heads": None},
        {"accum_dtype": "bfloat16", "remat_policy": "dots"},
        {"microbatches": 8}),
    "kv_rep+bf16comm+dots+micro4b": (
        {"kv_heads": None, "activation_kv_heads": None},
        {"accum_dtype": "bfloat16", "remat_policy": "dots"},
        {"microbatches": 4}),
    # pure FSDP: batch over data*model (1 seq/device at train_4k), weights
    # stay 2D-sharded and are gathered per layer; NO tensor-parallel
    # activations so the Megatron activation all-reduces vanish entirely
    "pure_fsdp": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None, "activation_vocab": None,
         "kv_heads": None},
        {}, {}),
    "pure_fsdp+vp": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None, "activation_vocab": None,
         "kv_heads": None, "table_embed": None},
        {}, {}),
    "pure_fsdp+vp+bf16comm": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None, "activation_vocab": None,
         "kv_heads": None, "table_embed": None},
        {"accum_dtype": "bfloat16"}, {}),
    # pure FSDP but logits stay vocab-sharded + chunked attention at 4k
    "pure_fsdp+vTP+chunk": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None,
         "kv_heads": None, "table_embed": None},
        {"attn_chunk_threshold": 2048 * 2048}, {}),
    "pure_fsdp+vTP+chunk+bf16comm": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None,
         "kv_heads": None, "table_embed": None},
        {"attn_chunk_threshold": 2048 * 2048, "accum_dtype": "bfloat16"},
        {}),
    "pure_fsdp+fce+chunk": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None, "activation_vocab": None,
         "kv_heads": None, "table_embed": None},
        {"attn_chunk_threshold": 2048 * 2048, "fused_ce": True}, {}),
    "pure_fsdp+fce+chunk+bf16comm": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None, "activation_vocab": None,
         "kv_heads": None, "table_embed": None},
        {"attn_chunk_threshold": 2048 * 2048, "fused_ce": True,
         "accum_dtype": "bfloat16"}, {}),
    "pure_fsdp+fce+oh+chunk": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None, "activation_vocab": None,
         "kv_heads": None, "table_embed": None},
        {"attn_chunk_threshold": 2048 * 2048, "fused_ce": True,
         "embed_onehot": True}, {}),
    # serving layout: weights 2D-TP (mlp over model*data), nothing gathered
    # per step; decode activations are tiny so resharding them is free
    "serve_2dtp": (
        {"embed": None, "table_embed": None, "mlp": ("model", "data")},
        {}, {}),
    "serve_2dtp+bf16comm": (
        {"embed": None, "table_embed": None, "mlp": ("model", "data")},
        {"accum_dtype": "bfloat16"}, {}),
    "serve_bf16comm": ({}, {"accum_dtype": "bfloat16"}, {}),
    # + replicate decode activations (tiny); h replicated x 2D-sharded W
    # has no sharding conflict, so nothing is gathered at all
    "serve_2dtp_repb": (
        {"embed": None, "table_embed": None, "mlp": ("model", "data"),
         "activation_mlp": ("model", "data"), "activation_batch": None,
         "activation_vocab": ("model", "data"), "vocab": ("model", "data")},
        {}, {}),
    "pure_fsdp+vTP+chunk+micro2": (
        {"activation_batch": ("pod", "data", "model"),
         "cache_batch": ("pod", "data", "model"),
         "activation_heads": None, "activation_kv_heads": None,
         "activation_mlp": None,
         "kv_heads": None, "table_embed": None},
        {"attn_chunk_threshold": 2048 * 2048}, {"microbatches": 2}),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--out", default="results/hillclimb.json")
    args = ap.parse_args()

    multi_pod = args.mesh == "multipod"
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for variant in args.variants.split(","):
        rules_ov, cfg_ov, set_ov = VARIANTS[variant]
        key = f"{args.arch}|{args.shape}|{args.mesh}|{variant}"
        if results.get(key, {}).get("ok"):
            print(f"[cached] {key}")
            continue
        print(f"[run] {key}", flush=True)
        cfg = get_config(args.arch)
        if cfg_ov:
            cfg = cfg.replace(**cfg_ov)
        settings = steps_lib.TrainSettings(**set_ov) if set_ov else None
        t0 = time.time()
        try:
            res = run_cell(args.arch, args.shape, multi_pod, cfg=cfg,
                           rule_overrides=rules_ov, settings=settings)
            res["variant"] = variant
            results[key] = res
            print(f"  compute={res['compute_s']*1e3:.1f}ms "
                  f"memory={res['memory_s']*1e3:.1f}ms "
                  f"collective={res['collective_s']*1e3:.1f}ms "
                  f"hbm={res['peak_memory_per_device']/1e9:.1f}GB "
                  f"({time.time()-t0:.0f}s)", flush=True)
        except Exception as e:  # noqa: BLE001
            results[key] = {"ok": False, "variant": variant,
                            "error": f"{type(e).__name__}: {e}"}
            print(f"  FAIL {type(e).__name__}: {e}", flush=True)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
