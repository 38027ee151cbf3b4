"""Serving CLI — a thin launcher over the ``repro.serve`` subsystem.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --scenario steady --requests 8 --seed 0

Replays a seeded traffic scenario (see ``repro.serve.traffic`` presets:
steady | burst | drain | device-loss-mid-decode) through the
continuous-batching engine and prints the SLO report.  ``--json PATH``
dumps the report + per-request records for offline analysis.

The old in-module prototype (whole-batch refill SlotManager + inline
serve loop) moved to ``repro.serve.scheduler`` — and the refill path was
fixed on the way: admission now prefills per-slot and merges only that
slot's cache rows, so an in-flight request's KV state is never clobbered
by someone else's admission.  ``SlotManager`` / ``Request`` stay
importable from here as warn-once deprecation shims.
"""

from __future__ import annotations

import argparse
import dataclasses
import json

from repro.configs import get_config, smoke_config

_DEPRECATED = {
    "SlotManager": "launch.serve.SlotManager",
    "Request": "launch.serve.Request",
}


def __getattr__(name: str):
    if name in _DEPRECATED:
        from repro.deprecation import warn_deprecated
        from repro.serve import scheduler

        warn_deprecated(
            _DEPRECATED[name],
            f"repro.launch.serve.{name} is deprecated; import it from "
            f"repro.serve (the promoted serving subsystem)")
        return getattr(scheduler, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def main() -> None:
    from repro.serve import (
        JaxModelRunner,
        SCENARIO_NAMES,
        ServeAutoscaler,
        ServingEngine,
        make_traffic,
        scenario_preset,
        snap_prompt_buckets,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scenario", default="steady", choices=SCENARIO_NAMES)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=None,
                    help="override the preset's request count")
    ap.add_argument("--rate", type=float, default=None,
                    help="override the preset's arrival rate (req/s)")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    overrides = {}
    if args.requests is not None:
        overrides["n_requests"] = args.requests
    if args.rate is not None:
        overrides["rate_rps"] = args.rate
    sc = scenario_preset(args.scenario, **overrides)
    sc = sc.replace(prompt_buckets=snap_prompt_buckets(cfg, sc.prompt_buckets))
    trace = make_traffic(sc, args.seed)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    runner = JaxModelRunner(cfg, n_slots=args.slots, max_len=sc.max_len)
    runner.warmup(sc.prompt_buckets)
    autoscaler = ServeAutoscaler(runner.n_devices, args.slots)
    engine = ServingEngine(runner, n_slots=args.slots, autoscaler=autoscaler)
    result = engine.run(trace, sc)

    slo = result.slo
    print(f"{cfg.name} · scenario={sc.name} seed={args.seed} "
          f"slots={args.slots} devices={runner.n_devices}")
    print(f"  served {slo.n_finished}/{slo.n_submitted} requests "
          f"({result.n_prefills} prefills, {result.n_decode_steps} decode "
          f"steps, {slo.n_restarts} restarts, {len(result.replans)} "
          f"replans) in {slo.makespan_s:.3f}s")
    print(f"  TTFT p50/p99 {slo.p50_ttft_s * 1e3:.1f}/"
          f"{slo.p99_ttft_s * 1e3:.1f} ms · TPOT p50/p99 "
          f"{slo.p50_tpot_s * 1e3:.2f}/{slo.p99_tpot_s * 1e3:.2f} ms · "
          f"e2e p99 {slo.p99_e2e_s * 1e3:.1f} ms")
    print(f"  throughput {slo.throughput_tok_s:.1f} tok/s · goodput "
          f"{slo.goodput_tok_s:.1f} tok/s ({slo.n_slo_ok}/{slo.n_finished} "
          f"within TTFT<={sc.ttft_slo_s}s, TPOT<={sc.tpot_slo_s}s)")
    for rp in result.replans:
        print(f"  replan[{rp.reason}] devices {rp.from_devices}->"
              f"{rp.to_devices} slots {rp.from_slots}->{rp.to_slots} "
              f"(Lemma-1 cores {rp.lemma1_cores}, epoch {rp.epoch_s})")
    for rid in sorted(result.streams)[:3]:
        print(f"  req {rid}: {result.streams[rid][:8]}...")

    if args.json:
        payload = {
            "arch": cfg.name,
            "scenario": dataclasses.asdict(sc),
            "seed": args.seed,
            "slots": args.slots,
            "slo": slo.to_row(),
            "replans": [rp.to_dict() for rp in result.replans],
            "requests": [dataclasses.asdict(r)
                         for r in result.metrics.records.values()],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# json report -> {args.json}")


if __name__ == "__main__":
    main()
