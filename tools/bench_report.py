"""Aggregate the per-suite benchmark artifacts into one perf dashboard.

Every benchmark that measures something durable writes an
``artifacts/BENCH_<name>.json`` (``bench_hybrid.py`` -> BENCH_hybrid,
``bench_kernels.py`` -> BENCH_poisson, ``bench_train.py`` -> BENCH_train,
...).  This tool collects them into ``artifacts/BENCH_summary.json`` — one
flat record per artifact with its schema tag and every scalar it contains
(nested keys dotted) — plus a human-readable ``BENCH_summary.md`` dashboard:
headline throughput numbers, the measured fleet parallel efficiency beside
the paper's 78% / 47x at 60 cores, and the golden-physics drift (Strouhal / C_D / C_L vs the checked-in reference).  The perf
trajectory across PRs is a single diffable file, and CI can upload the lot
as workflow artifacts.

``--check`` (CI mode) exits nonzero when no artifacts were found, any is
unreadable/untagged, a present golden-drift measurement exceeds the
golden-physics test tolerances — perf artifacts must not paper over a
physics regression — or an artifact's self-declared perf gate failed
(``gate.passed`` false, e.g. bench_megakernel's required speedup vs the
committed training baseline).

    PYTHONPATH=src python tools/bench_report.py \
        [--dir artifacts] [--out artifacts/BENCH_summary.json] [--check]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

SUMMARY_SCHEMA = "repro.bench_summary/v1"

# paper reference points the dashboard pins every run against
PAPER_TARGETS = {"efficiency_60cores": 0.78, "speedup_60cores": 47.0}
# --check fails when measured golden drift exceeds the golden-physics test
# tolerances (tests/test_golden_physics.py TOL_ST / TOL_CD / TOL_AMP)
DRIFT_TOLERANCES = {"strouhal_rel_drift": 0.015,
                    "cd_mean_rel_drift": 0.01,
                    "cl_amp_rel_drift": 0.05}

# dotted scalar keys promoted to the dashboard's headline table, with the
# format to render them in (missing keys are simply skipped per artifact)
HEADLINES = (
    ("env_steps_per_s", "{:.1f}"),
    ("gate.speedup_vs_baseline", "{:.2f}x"),
    ("gate.passed", "{}"),
    ("shares.sink_write", "{:.1%}"),
    ("speedup_packed_vs_full", "{:.2f}x"),
    ("gate.measured_efficiency", "{:.1%}"),
    ("plan.n_envs", "{}"),
    ("plan.n_ranks", "{}"),
    ("plan.backend", "{}"),
    ("plan.layout", "{}"),
)


def flatten_scalars(obj, prefix: str = "", max_depth: int = 4) -> dict:
    """Dotted-key view of every scalar (number / short string / bool) in a
    nested JSON object.  Lists are summarized by length — per-candidate
    tables stay in the source artifact, the summary tracks the headlines."""
    out = {}
    if max_depth < 0:
        return out
    if isinstance(obj, dict):
        for k, v in obj.items():
            key = f"{prefix}.{k}" if prefix else str(k)
            out.update(flatten_scalars(v, key, max_depth - 1))
    elif isinstance(obj, list):
        if prefix:
            out[f"{prefix}.len"] = len(obj)
    elif isinstance(obj, (int, float, bool)):
        out[prefix] = obj
    elif isinstance(obj, str) and len(obj) <= 80:
        out[prefix] = obj
    return out


def summarize(art_dir: Path, include_smoke: bool = False) -> dict:
    entries = {}
    for path in sorted(art_dir.glob("BENCH_*.json")):
        # smoke artifacts (tiny-shape CI runs) never enter the committed
        # trajectory: they would overwrite real measurements with noise
        if path.name == "BENCH_summary.json" or \
                (path.name.endswith("_smoke.json") and not include_smoke):
            continue
        try:
            record = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError) as exc:
            entries[path.stem] = {"error": f"{type(exc).__name__}: {exc}"}
            continue
        entries[path.stem] = {
            "file": path.name,
            "schema": record.get("schema", "<untagged>"),
            "scalars": flatten_scalars(record),
        }
    return {"schema": SUMMARY_SCHEMA,
            "n_artifacts": len(entries),
            "paper_targets": PAPER_TARGETS,
            "entries": entries}


def gate_failures(summary: dict) -> list:
    """Artifacts whose self-declared perf gate failed (``gate.passed``
    false) — e.g. bench_megakernel's required speedup vs the committed
    training baseline.  Artifacts without a gate are simply not gated."""
    out = []
    for name, entry in summary["entries"].items():
        scalars = entry.get("scalars", {})
        if scalars.get("gate.passed") is False:
            detail = ", ".join(f"{k.split('.', 1)[1]}={v}"
                               for k, v in sorted(scalars.items())
                               if k.startswith("gate.") and k != "gate.passed")
            out.append(f"{name}: gate.passed=false ({detail})")
    return out


def drift_violations(summary: dict) -> list:
    """Golden-physics drift scalars (any artifact) beyond test tolerance."""
    out = []
    for name, entry in summary["entries"].items():
        scalars = entry.get("scalars", {})
        for key, tol in DRIFT_TOLERANCES.items():
            val = scalars.get(f"golden_drift.{key}")
            if isinstance(val, (int, float)) and abs(val) > tol:
                out.append(f"{name}: golden_drift.{key}={val:+.4f} "
                           f"(|tol|={tol})")
    return out


def render_markdown(summary: dict) -> str:
    """The dashboard: headline table, paper-target comparison, physics
    drift — one glanceable file beside the machine-readable summary."""
    lines = ["# Benchmark dashboard", "",
             f"{summary['n_artifacts']} artifacts aggregated "
             f"(schema `{summary['schema']}`).", "",
             "| artifact | schema | headline |", "|---|---|---|"]
    for name, entry in sorted(summary["entries"].items()):
        if "error" in entry:
            lines.append(f"| {name} | — | UNREADABLE: {entry['error']} |")
            continue
        scalars = entry["scalars"]
        cells = [f"{key.split('.')[-1]}={fmt.format(scalars[key])}"
                 for key, fmt in HEADLINES if key in scalars]
        lines.append(f"| {name} | `{entry['schema']}` | "
                     f"{', '.join(cells) or f'{len(scalars)} scalars'} |")

    train = next((e["scalars"] for n, e in summary["entries"].items()
                  if e.get("schema", "").startswith("repro.bench_train/")),
                 None)
    lines += ["", "## Paper targets (arXiv 2402.11515)", ""]
    lines.append(f"- parallel efficiency @ 60 cores: paper "
                 f"{PAPER_TARGETS['efficiency_60cores']:.0%} "
                 f"({PAPER_TARGETS['speedup_60cores']:.0f}x); measured "
                 f"across processes in the fleet section below")
    if train and "shares.sink_write" in train:
        lines.append(f"- shares.sink_write: {train['shares.sink_write']:.1%}")

    mega = next((e["scalars"] for n, e in summary["entries"].items()
                 if e.get("schema", "").startswith("repro.bench_megakernel/")),
                None)
    if mega:
        lines += ["", "## Fused megakernel (measured vs roofline)", ""]
        hw = mega.get("roofline.hw.name", "?")
        lines.append(
            f"- fused interval: {mega.get('env_steps_per_s', 0):.1f} "
            f"env-steps/s, {mega.get('gate.speedup_vs_baseline', 0):.2f}x "
            f"vs training baseline (gate "
            f"{'PASS' if mega.get('gate.passed') else 'FAIL'}, requires "
            f"{mega.get('gate.required_speedup', 0):.1f}x)")
        if "roofline.measured_s" in mega:
            lines.append(
                f"- roofline[{hw}]: measured "
                f"{mega['roofline.measured_s']*1e3:.1f} ms/interval vs "
                f"bound {mega.get('roofline.bound_s', 0)*1e3:.1f} ms "
                f"({mega.get('roofline.dominant', '?')}-dominated); gap "
                f"{mega.get('roofline.gap', 0):.2f}x, vs compute term "
                f"{mega.get('roofline.gap_vs_compute', 0):.2f}x")
        if "parity.u_maxabs" in mega:
            lines.append(
                f"- fused-vs-reference parity (mixed vmapped batch): "
                f"max|du|={mega['parity.u_maxabs']:.1e}, "
                f"max|dp|={mega.get('parity.p_maxabs', 0):.1e}, "
                f"max|dCd|={mega.get('parity.cd_maxabs', 0):.1e}")

    fleet_entry = next(
        (e for n, e in summary["entries"].items()
         if e.get("schema", "").startswith("repro.bench_fleet/")), None)
    if fleet_entry:
        fl = fleet_entry["scalars"]
        lines += ["", "## Fleet parallel efficiency (multi-process)", ""]
        lines.append(
            f"- measured through tools/launch_fleet.py on "
            f"{fl.get('host.cores', '?')} core(s); paper: "
            f"{PAPER_TARGETS['efficiency_60cores']:.0%} at 60 cores")
        lines.append(
            f"- gate [{fl.get('gate.metric', '?')} at "
            f"{fl.get('gate.processes', '?')} processes]: "
            f"{fl.get('gate.measured_efficiency', 0):.1%} measured vs "
            f">= {fl.get('gate.required_efficiency', 0):.0%} required -> "
            f"{'PASS' if fl.get('gate.passed') else 'FAIL'}")

    lines += ["", "## Golden-physics drift", ""]
    drifted = False
    for name, entry in sorted(summary["entries"].items()):
        scalars = entry.get("scalars", {})
        row = {k: scalars.get(f"golden_drift.{k}")
               for k in DRIFT_TOLERANCES}
        if any(v is not None for v in row.values()):
            drifted = True
            lines.append(f"- {name}: " + ", ".join(
                f"{k.replace('_rel_drift', '')} {v:+.3%}"
                for k, v in row.items() if v is not None))
    if not drifted:
        lines.append("- no drift measurements in the aggregated artifacts")
    for v in drift_violations(summary):
        lines.append(f"- **OVER TOLERANCE**: {v}")
    return "\n".join(lines) + "\n"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    root = Path(__file__).resolve().parent.parent
    ap.add_argument("--dir", default=str(root / "artifacts"))
    ap.add_argument("--out", default=None,
                    help="default: <dir>/BENCH_summary.json")
    ap.add_argument("--markdown", default=None,
                    help="dashboard output (default: <dir>/BENCH_summary.md)")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero when no artifacts were found, any "
                         "failed to parse / lacks a schema tag, golden "
                         "drift exceeds test tolerance, or a perf gate "
                         "(gate.passed) failed (CI mode)")
    ap.add_argument("--include-smoke", action="store_true",
                    help="also aggregate BENCH_*_smoke.json (excluded by "
                         "default so CI smoke noise never enters the "
                         "committed trajectory)")
    args = ap.parse_args()

    art_dir = Path(args.dir)
    summary = summarize(art_dir, include_smoke=args.include_smoke)
    out = Path(args.out) if args.out else art_dir / "BENCH_summary.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(summary, indent=1, sort_keys=True))
    md = Path(args.markdown) if args.markdown else art_dir / "BENCH_summary.md"
    md.write_text(render_markdown(summary))

    for name, entry in summary["entries"].items():
        if "error" in entry:
            print(f"{name}: UNREADABLE ({entry['error']})")
            continue
        scalars = entry["scalars"]
        headline = {k: v for k, v in sorted(scalars.items())
                    if "speedup" in k or k.endswith("plan.n_envs")
                    or k.endswith("plan.n_ranks") or k.endswith("backend")
                    or k.endswith("layout")}
        print(f"{name} [{entry['schema']}]: {len(scalars)} scalars"
              + (f" | {headline}" if headline else ""))
    print(f"summary -> {out} ({summary['n_artifacts']} artifacts), "
          f"dashboard -> {md}")

    if args.check:
        problems = []
        if not summary["entries"]:
            problems.append("no artifacts found")
        problems += [f"unreadable: {n} ({e['error']})"
                     for n, e in summary["entries"].items() if "error" in e]
        problems += [f"untagged (no schema field): {n}"
                     for n, e in summary["entries"].items()
                     if e.get("schema") == "<untagged>"]
        problems += [f"golden drift over tolerance: {v}"
                     for v in drift_violations(summary)]
        problems += [f"perf gate failed: {v}"
                     for v in gate_failures(summary)]
        if problems:
            raise SystemExit("bench summary check failed:\n  "
                             + "\n  ".join(problems))


if __name__ == "__main__":
    main()
