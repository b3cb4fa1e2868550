"""Render the roofline readout: EXPERIMENTS.md §Roofline mesh tables from
``experiments/dryrun/*.json`` plus the per-program search profiles the
observatory stamps onto ``experiments/BENCH_*.json`` rows (DESIGN.md §17).

  PYTHONPATH=src python -m benchmarks.report_roofline [--mesh 16x16]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

#: stamped artifacts whose rows may carry a ``roofline`` block
SEARCH_ARTIFACTS = ("BENCH_topk.json", "BENCH_serving.json",
                    "BENCH_infinity.json")


def fmt_t(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    if x >= 1e-6:
        return f"{x*1e6:.0f}us"
    return f"{x*1e9:.0f}ns"


def fmt_n(x: float) -> str:
    """Engineering-notation flops/bytes."""
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(x) >= div:
            return f"{x / div:.2f}{suf}"
    return f"{x:.0f}"


def load(mesh: str, d: str = "experiments/dryrun"):
    rows = []
    for f in sorted(glob.glob(os.path.join(d, f"*__{mesh}.json"))):
        base = os.path.basename(f)[: -len(".json")]
        if base.count("__") != 2:  # skip tagged §Perf variants
            continue
        rows.append(json.load(open(f)))
    return rows


def render(mesh: str = "16x16") -> str:
    rows = load(mesh)
    if not rows:
        return ""
    out = [
        f"| arch | shape | step | mem/dev GiB | t_compute | t_memory | t_collective | dominant | useful/HLO | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        rf = r["roofline"]
        out.append(
            "| {arch} | {shape} | {step} | {mem:.1f} | {tc} | {tm} | {tl} | {dom} | {ur} | {frac} |".format(
                arch=r["arch"], shape=r["shape"], step=r["step"],
                mem=r["memory"]["peak_estimate_gib"],
                tc=fmt_t(rf["t_compute_s"]), tm=fmt_t(rf["t_memory_s"]),
                tl=fmt_t(rf["t_collective_s"]), dom=rf["dominant"],
                ur=f"{rf.get('useful_flops_ratio', 0):.2f}",
                frac=f"{rf.get('roofline_fraction', 0):.4f}",
            )
        )
    return "\n".join(out)


def search_profiles(d: str = "experiments") -> list:
    """(source, identity, block) triples from every stamped search
    artifact whose rows carry a ``roofline`` block (error blocks and
    unstamped files are skipped — this is a reader, not a validator)."""
    out = []
    for fname in SEARCH_ARTIFACTS:
        path = os.path.join(d, fname)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        rows = doc.get("rows", []) if isinstance(doc, dict) else []
        # a share of a TPU's peak exists only for a run on a TPU
        on_tpu = isinstance(doc, dict) and \
            doc.get("meta", {}).get("backend") == "tpu"
        for r in rows:
            blocks = r.get("roofline")
            if not blocks:
                continue
            # topk rows hold a dict of variants; serving/infinity one block
            items = (blocks.items() if "program" not in blocks
                     else [(None, blocks)])
            ident = ",".join(
                f"{k}={r[k]}" for k in ("engine", "mode", "dtype", "q",
                                        "shards", "n")
                if k in r
            )
            for _, blk in items:
                if isinstance(blk, dict) and "program" in blk:
                    if not on_tpu:
                        blk = {k: v for k, v in blk.items()
                               if k != "pct_of_peak"}
                    out.append((fname, ident, blk))
    return out


def render_search(d: str = "experiments") -> str:
    """The search-program roofline table: one line per captured compiled
    program across the stamped BENCH artifacts."""
    profs = search_profiles(d)
    if not profs:
        return ""
    out = [
        "| artifact | cell | program | flops | HBM bytes | AI | predicted | measured | %-of-peak | dominant |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for fname, ident, blk in profs:
        meas = blk.get("t_measured_s")
        pct = blk.get("pct_of_peak")
        out.append(
            "| {f} | {c} | {p} | {fl} | {hb} | {ai:.3f} | {tp} | {tm} | {pk} | {dom} |".format(
                f=fname.removeprefix("BENCH_").removesuffix(".json"),
                c=ident, p=blk["program"],
                fl=fmt_n(blk["flops"]), hb=fmt_n(blk["hbm_bytes"]),
                ai=blk["intensity"], tp=fmt_t(blk["t_predicted_s"]),
                tm=fmt_t(meas) if meas else "-",
                pk=f"{pct:.2%}" if pct else "-",
                dom=blk["dominant"],
            )
        )
    return "\n".join(out)


def render_all(mesh: str = "16x16", d: str = "experiments") -> str:
    parts = []
    mesh_tbl = render(mesh)
    if mesh_tbl:
        parts += [f"## Roofline — dry-run mesh {mesh}", "", mesh_tbl, ""]
    search_tbl = render_search(d)
    if search_tbl:
        parts += ["## Roofline — compiled search programs", "", search_tbl]
    return "\n".join(parts)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--dir", default="experiments")
    args = ap.parse_args()
    print(render_all(args.mesh, args.dir))
