"""Render §Dry-run / §Roofline markdown tables from results/dryrun/*.json.

    PYTHONPATH=src python scripts/render_tables.py [--out results/tables.md]
"""
import argparse
import glob
import json
import os


def fmt(x, digits=3):
    if x is None:
        return "-"
    return f"{x:.{digits}e}" if (abs(x) < 1e-2 or abs(x) >= 1e4) else f"{x:.{digits}f}"


def fmt_bytes(n):
    if n is None:
        return "-"
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--src", default="results/dryrun")
    ap.add_argument("--out", default="results/tables.md")
    args = ap.parse_args()

    rows = []
    for f in sorted(glob.glob(os.path.join(args.src, "*.json"))):
        rows.append(json.load(open(f)))

    lines = ["# Dry-run / roofline tables (generated)", ""]
    for mesh, tag in (("16x16", "single-pod (256 chips)"),
                      ("2x16x16", "multi-pod (512 chips)")):
        lines.append(f"## {tag}")
        lines.append("")
        lines.append("| cell | status | compile s | temp GB | args GB | "
                     "compute s | memory s | collective s | bottleneck | "
                     "useful flops |")
        lines.append("|---|---|---|---|---|---|---|---|---|---|")
        for r in rows:
            if r["cell"].rsplit("×", 1)[-1] != mesh:
                continue
            cell = r["cell"].rsplit("×", 1)[0]
            if r["status"] != "ok":
                lines.append(f"| {cell} | {r['status']}: "
                             f"{r.get('reason', r.get('error', ''))[:60]} "
                             f"| | | | | | | | |")
                continue
            ro = r["roofline"]
            mem = r["memory"]
            temp = (mem.get("temp_size_in_bytes") or 0) / 1e9
            arg = (mem.get("argument_size_in_bytes") or 0) / 1e9
            lines.append(
                f"| {cell} | ok | {r['compile_s']} | {temp:.1f} | {arg:.2f} "
                f"| {fmt(ro['compute_s'])} | {fmt(ro['memory_s'])} "
                f"| {fmt(ro['collective_s'])} | {ro['bottleneck']} "
                f"| {fmt(r.get('useful_flops_frac'), 2)} |")
        lines.append("")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {args.out} ({len(rows)} cells)")


if __name__ == "__main__":
    main()
