"""Serve a small LM with batched requests: prefill-free token-by-token decode
with KV/SSM caches, for any of the 10 assigned architectures (reduced config).

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-2.7b
"""

import os
import pathlib
import subprocess
import sys


def main():
    arch = "mamba2-2.7b"
    for i, a in enumerate(sys.argv):
        if a == "--arch" and i + 1 < len(sys.argv):
            arch = sys.argv[i + 1]
    cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", arch,
           "--batch", "4", "--prompt-len", "16", "--gen", "8"]
    print(" ".join(cmd))
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    subprocess.run(cmd, check=True, env=env)


if __name__ == "__main__":
    main()
