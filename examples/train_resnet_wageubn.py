"""The paper's own experiment at CPU scale: ResNet under WAGEUBN.

    PYTHONPATH=src python examples/train_resnet_wageubn.py [--steps 120]

Trains the reduced ResNet on the resolved image task (the real npz
pipeline when REPRO_DATA_DIR / --data-dir points at shards, the learnable
synthetic task otherwise) under the paper's numeric configs plus the
sub-8 / wide-gradient lanes (DESIGN.md §14), and prints the Table-I-style
comparison.  --dr-boundaries drives the paper's CQ dr shrink schedule
(k_gw -> k_gw-1 -> ... at the listed steps).
"""
import argparse
import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import image_task, train_resnet  # noqa: E402
from repro.core import preset  # noqa: E402
from repro.data import resolve_image_task  # noqa: E402
from repro.kernels.ops import dispatch_banner, dispatch_report  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.optim import parse_boundaries  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=120)
    p.add_argument("--data-dir", default="",
                   help="npz shard directory (default: $REPRO_DATA_DIR, "
                        "else the synthetic task)")
    p.add_argument("--dr-boundaries", default="",
                   help="comma-separated steps where the CQ dr width "
                        "shrinks by one bit (e.g. '60,90'); empty = flat "
                        "at k_gw")
    args = p.parse_args()
    use_compile_cache()
    bounds = parse_boundaries(args.dr_boundaries)
    if args.data_dir:
        task, data = resolve_image_task(64, data_dir=args.data_dir)
    else:
        task, data = image_task(64)
    print(dispatch_banner())
    print(f"[data] {data}  dr_boundaries={bounds or '(none)'}")
    print(f"{'config':15s} {'path':15s} {'holdout acc':12s} {'us/step':10s}")
    for name, mode in (("fp32", None), ("e2_16", "sim"), ("full8", "sim"),
                       ("w4a8", "sim"), ("a4", "sim"), ("g16", "sim"),
                       ("full8", "native")):
        qcfg = preset(name, mode)
        r = train_resnet(qcfg, args.steps, task=task, dr_boundaries=bounds)
        label = name if mode in (None, "sim") else f"{name}/{mode}"
        rep = dispatch_report(qcfg)
        path = f"{rep['route']}/" + ("fused" if rep["fused"] else "unfused")
        print(f"{label:15s} {path:15s} {r['acc']:<12.4f} "
              f"{r['wall_s'] / args.steps * 1e6:<10.0f}")


if __name__ == "__main__":
    main()
