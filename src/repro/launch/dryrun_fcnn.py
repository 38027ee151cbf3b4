"""Dry-run the paper's own workload: FCNN (NN1-6) training steps on the
production mesh, with PER-LAYER sharding degrees chosen by the ONoC
planner (Lemma 1 snapped to mesh-feasible degrees) — the paper's technique
executing as real per-layer PartitionSpecs, not just as analysis.

  PYTHONPATH=src python -m repro.launch.dryrun_fcnn [--multipod] \
      [--out results/dryrun_fcnn.json]

Unlike the transformer stacks (uniform scanned layers), the FCNN's layers
are heterogeneous, so each layer really does get its own degree — layer 1
at min(n_1, φm), interior layers at interior optima, the 10-neuron output
layer at degree ≤ 10 (Eq. 10), exactly the structure of the paper's
Table 10.
"""

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402

import jax                # noqa: E402
import jax.numpy as jnp   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.nn_benchmarks import NN_BENCHMARKS, onoc_config, workload  # noqa: E402
from repro.core.planner import plan_fcnn  # noqa: E402
from repro.launch.dryrun import _metrics_of  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import fcnn  # noqa: E402
from repro.optim import adam  # noqa: E402


def lower_nn(name: str, batch: int, multi_pod: bool, lambda_max: int = 64,
             kernel_mode: str | None = None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    w = workload(name, batch)
    plan = plan_fcnn(w, onoc_config(lambda_max), dict(mesh.shape),
                     strategy="orrm")
    sizes = NN_BENCHMARKS[name]
    opt = adam(1e-3)

    # per-layer shardings from the plan's degrees
    def layer_sharding(i: int):
        axes = plan.periods[i].axes
        return {
            "w": NamedSharding(mesh, P(None, axes if axes else None)),
            "b": NamedSharding(mesh, P(axes if axes else None)),
        }

    p_sh = {"layers": [layer_sharding(i) for i in range(len(sizes) - 1)]}
    st_sh = {"params": p_sh, "opt": {"m": p_sh, "v": p_sh},
             "step": NamedSharding(mesh, P())}
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_sh = {"x": NamedSharding(mesh, P(data_axes, None)),
            "y": NamedSharding(mesh, P(data_axes))}

    def step(state, batch_):
        loss, grads = jax.value_and_grad(
            lambda p, b: fcnn.loss_fn(p, b, kernel_mode=kernel_mode)
        )(state["params"], batch_)
        params, opt_state = opt.update(grads, state["opt"], state["params"],
                                       state["step"])
        return ({"params": params, "opt": opt_state,
                 "step": state["step"] + 1}, loss)

    state_spec = jax.eval_shape(lambda k: {
        "params": fcnn.init(k, sizes),
        "opt": adam(1e-3).init(fcnn.init(k, sizes)),
        "step": jnp.zeros((), jnp.int32),
    }, jax.random.PRNGKey(0))
    batch_spec = {"x": jax.ShapeDtypeStruct((batch, sizes[0]), jnp.float32),
                  "y": jax.ShapeDtypeStruct((batch,), jnp.int32)}

    jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        lowered = jitted.lower(state_spec, batch_spec)
    return lowered, plan, mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--kernel", default=None,
                    choices=["ref", "pallas", "pallas_interpret"],
                    help="force the fcnn_layer dispatch mode")
    ap.add_argument("--out", default="results/dryrun_fcnn.json")
    args = ap.parse_args()

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    mesh_name = "2x16x16" if args.multipod else "16x16"
    for name in sorted(NN_BENCHMARKS):
        key = f"{name}|train_b{args.batch}|{mesh_name}"
        print(f"[run] {key}", flush=True)
        t0 = time.time()
        try:
            lowered, plan, mesh = lower_nn(name, args.batch, args.multipod,
                                           kernel_mode=args.kernel)
            compiled = lowered.compile()
            m = _metrics_of(compiled)
            mem = compiled.memory_analysis()
            results[key] = {
                "ok": True,
                "degrees": plan.degrees,
                "onoc_cores": [p.onoc_cores for p in plan.periods],
                "flops_per_device": m["flops"],
                "collective_bytes": sum(v for k, v in m.items()
                                        if k.startswith("coll:")),
                "temp_gb": mem.temp_size_in_bytes / 1e9,
                "seconds": round(time.time() - t0, 1),
            }
            print(f"  ok: degrees={plan.degrees} "
                  f"(ONoC m*={[p.onoc_cores for p in plan.periods]}) "
                  f"[{results[key]['seconds']}s]")
        except Exception as e:  # noqa: BLE001
            results[key] = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            print(f"  FAIL: {type(e).__name__}: {e}")
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"{n_ok}/{len(results)} FCNN cells ok -> {args.out}")


if __name__ == "__main__":
    main()
