"""Aggregate experiments/dryrun/*.json into the EXPERIMENTS.md roofline table.

Usage: PYTHONPATH=src python -m benchmarks.roofline_report [--dir experiments/dryrun] [--mesh pod]
"""

from __future__ import annotations

import argparse
import glob
import json
import os


def load(dirname: str, mesh: str):
    recs = []
    for path in sorted(glob.glob(os.path.join(dirname, f"*__{mesh}.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def fmt(x, unit=""):
    if x == 0:
        return "0"
    for scale, suffix in [(1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")]:
        if abs(x) >= scale:
            return f"{x/scale:.2f}{suffix}{unit}"
    return f"{x:.3g}{unit}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default="pod")
    ap.add_argument("--csv", action="store_true")
    args = ap.parse_args()

    recs = load(args.dir, args.mesh)
    key = lambda r: (r["arch"], SHAPE_ORDER.index(r["shape"]))
    recs.sort(key=key)

    if args.csv:
        print("arch,shape,flops_per_chip,bytes_per_chip,coll_bytes,compute_s,memory_s,collective_s,dominant,useful_ratio")
        for r in recs:
            if r.get("skipped"):
                print(f"{r['arch']},{r['shape']},skipped,,,,,,,")
                continue
            ro = r["roofline"]
            print(
                f"{r['arch']},{r['shape']},{r['flops_per_chip']:.3e},{r['bytes_per_chip']:.3e},"
                f"{ro['collective_bytes']:.3e},{ro['compute_s']:.3e},{ro['memory_s']:.3e},"
                f"{ro['collective_s']:.3e},{ro['dominant']},{ro['useful_flops_ratio']:.3f}"
            )
        return

    hdr = ("| arch | shape | FLOPs/chip | bytes/chip | coll bytes/chip | "
           "compute (s) | memory (s) | collective (s) | dominant | 6ND/HLO |")
    print(hdr)
    print("|" + "---|" * 10)
    for r in recs:
        if r.get("skipped"):
            print(f"| {r['arch']} | {r['shape']} | — | — | — | — | — | — | skipped | — |")
            continue
        ro = r["roofline"]
        dom = ro["dominant"].replace("_s", "")
        print(
            f"| {r['arch']} | {r['shape']} | {fmt(r['flops_per_chip'])} | "
            f"{fmt(r['bytes_per_chip'])}B | {fmt(ro['collective_bytes'])}B | "
            f"{ro['compute_s']:.2e} | {ro['memory_s']:.2e} | {ro['collective_s']:.2e} | "
            f"{dom} | {ro['useful_flops_ratio']:.2f} |"
        )


if __name__ == "__main__":
    main()
