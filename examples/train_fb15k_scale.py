"""End-to-end driver: FB15k-scale KGE training (paper Tables 5/8 analogue).

Trains TransE_l2 (or --model) on a synthetic graph with FB15k's exact shape
(14,951 entities / 1,345 relations / 592k triplets) for a few thousand steps
and reports filtered Hit@k / MR / MRR — the paper's evaluation protocol 1.

    PYTHONPATH=src python examples/train_fb15k_scale.py [--steps 3000]
"""

import argparse
import os
import pathlib
import subprocess
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3000)
    ap.add_argument("--model", default="transe_l2")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--trainers", type=int, default=1,
                    help="Hogwild trainer threads (paper §3.1)")
    ap.add_argument("--samplers", type=int, default=1,
                    help="sampler worker threads (paper §3.3)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="periodic MRR every K steps")
    args = ap.parse_args()
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--dataset", "fb15k", "--model", args.model,
        "--steps", str(args.steps), "--scale", str(args.scale),
        "--dim", "128", "--eval", "--eval-n", "1000",
        "--trainers", str(args.trainers), "--samplers", str(args.samplers),
        "--eval-every", str(args.eval_every),
    ]
    print(" ".join(cmd))
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    subprocess.run(cmd, check=True, env=env)


if __name__ == "__main__":
    main()
