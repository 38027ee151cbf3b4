"""Serve a small LM through the continuous-batching subsystem
(``repro.serve``): seeded open-loop traffic, per-slot admission prefill,
batched decode, SLO report.

  PYTHONPATH=src python examples/serve_lm.py [--arch qwen3-14b]
                                             [--scenario steady]

Runs the chosen traffic preset (steady | burst | drain |
device-loss-mid-decode) on the smoke-sized config so it completes on
CPU; on a TPU mesh the identical code path serves the full config.  The
device-loss preset demonstrates the Lemma-1 elastic replan mid-decode —
in-flight requests restart from their prompts and finish with identical
token streams.
"""

import argparse
import subprocess
import sys

sys.path.insert(0, "src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--scenario", default="steady")
    args = ap.parse_args()
    # the serving loop lives in the launcher; this example drives it the
    # way an operator would.  The child needs the chip, so this parent
    # must never import JAX.
    cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", args.arch,
           "--smoke", "--scenario", args.scenario,
           "--requests", "8", "--slots", "3", "--seed", "0"]
    print("$", " ".join(cmd))
    raise SystemExit(subprocess.call(cmd, env={"PYTHONPATH": "src",
                                               **__import__("os").environ}))


if __name__ == "__main__":
    main()
