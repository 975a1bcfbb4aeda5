"""Multi-pod dry-run demo: lower + compile one (arch x shape) on the
single-pod (16x16=256) and multi-pod (2x16x16=512) production meshes and
print the roofline terms. Runs in a subprocess so the 512 fake host
devices never leak into the parent; this parent never imports JAX, and
the dry-run pins itself to the CPU, so no chip is taken.

    PYTHONPATH=src python examples/multipod_dryrun_demo.py --arch yi-9b
"""
import argparse
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--fl", choices=["hfl", "afl", "cfl"],
                    help="dry-run the federated trainer instead")
    args = ap.parse_args()

    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--mesh", "both",
           "--arch", args.arch, "--force", "--out",
           os.path.join("experiments", "dryrun", "demo")]
    if args.fl:
        cmd += ["--fl", args.fl]
    else:
        cmd += ["--shape", args.shape]
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    print("+", " ".join(cmd))
    raise SystemExit(subprocess.call(cmd, env=env, cwd=ROOT))


if __name__ == "__main__":
    main()
