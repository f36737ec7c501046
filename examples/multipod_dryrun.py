"""Drive the multi-pod dry-runs: the model data plane, the sharded
control plane, or both.

    # model compile dry-run (512 fake devices), one cell:
    PYTHONPATH=src python examples/multipod_dryrun.py \
        --arch rwkv6-3b --shape long_500k

    # lane-sharded fleet-scoring dry-run (8 fake devices):
    PYTHONPATH=src python examples/multipod_dryrun.py --fleet

Both are thin wrappers around ``repro.launch`` modules
(``dryrun`` / ``fleet_dryrun``) which must own the XLA_FLAGS device-count
env var *before* jax is imported, hence the subprocesses.  The fleet mode
exercises the full sharded decision path of DESIGN.md §6 — lane mesh,
sharded engine, donated sharded filter banks, churn — and exits non-zero
if sharded picks diverge from the single-device engine or churn
re-traces, so CI runs it as a smoke step.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def run_fleet(args) -> int:
    """Sharded fleet-scoring dry-run (repro.launch.fleet_dryrun)."""
    env = dict(os.environ, PYTHONPATH=_SRC)
    code = subprocess.call(
        [sys.executable, "-m", "repro.launch.fleet_dryrun",
         "--devices", str(args.devices), "--streams", str(args.streams),
         "--ticks", str(args.ticks)], env=env)
    return code


def run_model(args) -> int:
    """Model compile dry-run (repro.launch.dryrun); prints roofline
    terms per cell."""
    with tempfile.TemporaryDirectory() as tmp:
        env = dict(os.environ, PYTHONPATH=_SRC)
        code = subprocess.call(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", args.arch, "--shape", args.shape,
             "--mesh", args.mesh, "--out", tmp], env=env)
        if code:
            return code
        for name in sorted(os.listdir(tmp)):
            with open(os.path.join(tmp, name)) as f:
                rec = json.load(f)
            print(f"\n== {name}")
            if rec["status"] != "ok":
                print(f"  {rec['status']}: {rec.get('reason', '')}")
                continue
            print(f"  devices={rec['n_devices']} "
                  f"compile={rec['compile_s']}s")
            print(f"  flops/dev={rec['flops_per_device']:.3e} "
                  f"bytes/dev={rec['bytes_per_device']:.3e}")
            print(f"  collectives/dev="
                  f"{rec['collective_bytes_per_device']['total']:.3e}B "
                  f"{rec['collective_bytes_per_device']['counts']}")
            mem = rec["memory"]
            print(f"  memory: args={mem['argument_size'] / 1e9:.2f}GB "
                  f"temp={mem['temp_size'] / 1e9:.2f}GB")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-3b")
    ap.add_argument("--shape", default="long_500k")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--fleet", action="store_true",
                    help="run the lane-sharded fleet-scoring dry-run "
                         "instead of the model compile dry-run")
    ap.add_argument("--devices", type=int, default=8,
                    help="[--fleet] fake host device count")
    ap.add_argument("--streams", type=int, default=4096,
                    help="[--fleet] lane-pool size")
    ap.add_argument("--ticks", type=int, default=12,
                    help="[--fleet] churning fleet ticks to drive")
    args = ap.parse_args()
    import jax

    if jax.default_backend() == "tpu":
        sys.exit("multipod_dryrun: the dry-runs compile on fake host "
                 "devices in child processes, which cannot share this "
                 "host's TPU; run with JAX_PLATFORMS=cpu")
    sys.exit(run_fleet(args) if args.fleet else run_model(args))


if __name__ == "__main__":
    main()
