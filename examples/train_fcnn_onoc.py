"""End-to-end driver: train the paper's FCNN (reduced NN1 by default,
``--nn NN1..NN6`` for a paper network at full width) on the synthetic
fashion-mnist-shaped dataset for a few hundred steps, with the per-layer
parallelism degrees chosen by the ONoC planner and realized as JAX
shardings.

  PYTHONPATH=src python examples/train_fcnn_onoc.py [--steps 300] [--nn NN1]

With ``--program N`` the planner's schedule is *executed* instead of just
priced, through the one-call façade ``repro.exec.compile(...)``: the plan
is compiled to a static RUN/SEND/RECV/FREE period program with residency
annotations (exec/program.py, schema v2), statically validated and
cross-checked against core.simulator.simulate_epoch, and interpreted
under shard_map on a ring of the first N devices of the default backend
(exec/runtime.py; off-TPU, N forced host CPU devices).  The default
``--residency sharded`` keeps each device to ~1/d of the model (its
column chunks, dropped at the Eq.-11 mirror periods); ``--residency
replicated`` runs the full-model oracle:

  PYTHONPATH=src python examples/train_fcnn_onoc.py --program 8 --steps 100
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.configs.nn_benchmarks import NN_BENCHMARKS  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--kernel", default=None,
                    choices=["ref", "pallas", "pallas_interpret"],
                    help="force the fcnn_layer dispatch mode (default: "
                         "fused Pallas fwd+bwd on TPU, jnp oracle elsewhere)")
    ap.add_argument("--program", type=int, default=0, metavar="N",
                    help="compile the plan to a period program and execute "
                         "it under shard_map on a ring of the first N "
                         "devices")
    ap.add_argument("--nn", default=None, choices=sorted(NN_BENCHMARKS),
                    help="paper network at full width (default: NN1 "
                         "reduced to 784-256-128-10 so CPU runs fast)")
    ap.add_argument("--strategy", default="orrm",
                    choices=["fm", "rrm", "orrm"],
                    help="core mapping strategy (program mode)")
    ap.add_argument("--residency", default="sharded",
                    choices=["sharded", "replicated"],
                    help="program-mode params layout: per-device column "
                         "chunks (~1/d resident bytes) or the full-model "
                         "replicated oracle")
    args = ap.parse_args()

    if args.program:
        # must run before any other jax backend touch (forces N CPU devices)
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(args.program)
    else:
        mesh = None

    import jax
    import jax.numpy as jnp

    from repro.core.onoc_model import FCNNWorkload, ONoCConfig
    from repro.core.planner import plan_fcnn
    from repro.data import Batcher, fcnn_classification_dataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import fcnn
    from repro.optim import adam, linear_warmup_cosine

    enable_compile_cache()
    # reduced NN1 (784-1000-500-10 -> 784-256-128-10) so CPU runs fast
    sizes = NN_BENCHMARKS[args.nn] if args.nn else [784, 256, 128, 10]
    workload = FCNNWorkload(sizes, batch_size=args.batch)
    onoc = ONoCConfig(m=1000, lambda_max=64)

    if args.program:
        _run_program_mode(args, workload, onoc, mesh)
        return

    mesh = make_host_mesh()
    plan = plan_fcnn(workload, onoc, dict(mesh.shape), strategy="orrm")
    print("ONoC plan (per layer): "
          + ", ".join(f"L{p.period}: m*={p.onoc_cores} -> degree {p.degree}"
                      for p in plan.periods))

    key = jax.random.PRNGKey(0)
    params = fcnn.init(key, sizes)
    opt = adam(linear_warmup_cosine(3e-3, 20, args.steps))
    opt_state = opt.init(params)

    x, y = fcnn_classification_dataset(4096, input_dim=sizes[0], seed=0)
    batches = Batcher({"x": x, "y": y}, batch_size=args.batch, mesh=mesh)

    @jax.jit
    def step(params, opt_state, batch, i):
        loss, grads = jax.value_and_grad(
            lambda p, b: fcnn.loss_fn(p, b, kernel_mode=args.kernel)
        )(params, batch)
        params, opt_state = opt.update(grads, opt_state, params, i)
        return params, opt_state, loss

    t0 = time.time()
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        for i in range(args.steps):
            batch = next(batches)
            params, opt_state, loss = step(params, opt_state, batch, i)
            if i % 50 == 0 or i == args.steps - 1:
                acc = fcnn.accuracy(params, jnp.asarray(x[:1024]),
                                    jnp.asarray(y[:1024]),
                                    kernel_mode=args.kernel)
                print(f"step {i:4d}  loss {float(loss):.4f}  "
                      f"acc {float(acc):.3f}")
    dt = time.time() - t0
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({1e3 * dt / args.steps:.1f} ms/step)")
    final_acc = float(fcnn.accuracy(params, jnp.asarray(x), jnp.asarray(y),
                                    kernel_mode=args.kernel))
    print(f"final train accuracy: {final_acc:.3f}")
    assert final_acc > 0.8, "training failed to learn"


def _run_program_mode(args, workload, onoc, mesh) -> None:
    """Compile + execute the plan via the ``repro.exec.compile`` façade:
    cross-check the program's cost annotations against the simulator, show
    the residency profile, and train through the Executable."""
    import jax
    import jax.numpy as jnp

    import repro.exec as rexec
    from repro.core.simulator import simulate_epoch
    from repro.data import fcnn_classification_dataset
    from repro.models import fcnn
    from repro.optim import adam, linear_warmup_cosine

    n = args.program
    sizes = list(workload.layer_sizes)
    exe = rexec.compile(workload, onoc, mesh, strategy=args.strategy,
                        residency=args.residency, kernel_mode=args.kernel)
    prog = exe.program
    print(f"compiled {args.strategy.upper()} program (schema v"
          f"{prog.version}, {args.residency} residency): "
          f"{len(prog.instructions)} instructions over {2 * prog.l} periods "
          f"on a {n}-device ring")
    for i in prog.instructions:
        extra = (f" layer={i.layer} {i.phase} m*={i.onoc_cores} "
                 f"degree={i.degree}" if i.opcode.value == "run" else "")
        if i.opcode.value == "free" and i.layer is not None:
            extra = f" layer={i.layer} param_bytes={i.param_bytes:.0f}"
        print(f"  P{i.period:>2} {i.opcode.value.upper():<4} "
              f"devices={list(i.devices)} cost={i.cost_s:.3e}s{extra}")

    trace = simulate_epoch(workload, onoc, mapping=exe.plan.mapping)
    assert prog.compute_s == trace.compute_s
    assert prog.comm_s == trace.comm_s
    print(f"cost contract: program total {prog.total_s:.6e}s == "
          f"simulate_epoch {trace.total_s:.6e}s ✓")

    from repro.exec.residency import replicated_model_bytes
    tr = exe.tracker
    full = replicated_model_bytes(prog)
    print(f"residency ({args.residency}): peak {max(tr.peak_bytes()):.0f} B"
          f"/device vs {full:.0f} B replicated "
          f"(ratio {tr.peak_ratio():.3f}); FREEs release at periods "
          f"{tr.release_periods()}")

    opt = adam(linear_warmup_cosine(3e-3, 20, args.steps))
    state = exe.init_state(jax.random.PRNGKey(0), opt)
    step = exe.train_step(opt)
    x, y = fcnn_classification_dataset(4096, input_dim=sizes[0], seed=0)

    t0 = time.time()
    for i in range(args.steps):
        lo = (i * args.batch) % (len(x) - args.batch + 1)
        batch = {"x": jnp.asarray(x[lo:lo + args.batch]),
                 "y": jnp.asarray(y[lo:lo + args.batch])}
        state, metrics = step(state, batch)
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}")
    dt = time.time() - t0
    print(f"\n{args.steps} program steps in {dt:.1f}s "
          f"({1e3 * dt / args.steps:.1f} ms/step)")
    params = (exe.gather_params(state["params"])
              if args.residency == "sharded" else state["params"])
    final_acc = float(fcnn.accuracy(params, jnp.asarray(x), jnp.asarray(y),
                                    kernel_mode=args.kernel))
    print(f"final train accuracy: {final_acc:.3f}")
    assert final_acc > 0.8, "program-mode training failed to learn"


if __name__ == "__main__":
    main()
