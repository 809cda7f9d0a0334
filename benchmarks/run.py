"""Paper tables on the CPU: one module per table/figure, solver quality only.

  psnr_vs_nfe          — Figure 4 / Table 4 (+ Fig 11 BNS-vs-BST ablation)
  t2i_proxy            — Table 2 / Table 5 (CFG + preconditioning ablation)
  audio_proxy          — Figure 6 (enc-dec backbone SNR vs NFE)
  bns_vs_distillation  — Table 3 (forwards/params accounting vs PD)
  taxonomy_bench       — Figure 3 / Theorem 3.2 (exact NS conversions)
  anytime_bench        — one shared solver across NFE budgets (Sec. 6)

    python benchmarks/run.py [--quick]

Prints ``name,derived`` CSV lines (PSNR/SNR, errors, forward counts);
paper-claim PASS/FAIL notes go to log lines prefixed with '#'. Nothing
here is a speed number: serving speed is measured on the chip by
``bench/run.py`` against ``BENCHMARK.json``.
"""
from __future__ import annotations

import argparse
import os
import sys

# `python benchmarks/run.py` puts benchmarks/ itself on sys.path, not the
# repo root — the `from benchmarks import ...` section imports need the
# root (and the src tree saves callers exporting PYTHONPATH by hand)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def _taxonomy(quick, csv):
    from benchmarks import taxonomy_bench
    for r in taxonomy_bench.run(log=log):
        csv.append((f"taxonomy/{r['solver']}", f"max_err={r['max_err']:.1e}"))


def _table3(quick, csv):
    from benchmarks import bns_vs_distillation
    for r in bns_vs_distillation.run(log=log):
        csv.append((f"table3/{r['dataset']}/{r['method']}/nfe{r['nfe']}",
                    f"forwards={r['forwards']};match={r['match']}"))


def _fig4(quick, csv):
    from benchmarks import psnr_vs_nfe
    rows = psnr_vs_nfe.run(iterations=300 if quick else 3000, log=log)
    for note in psnr_vs_nfe.check_paper_claims(rows):
        log(note)
    for r in rows:
        csv.append((f"fig4/{r['scheduler']}/nfe{r['nfe']}",
                    f"bns={r['bns']:.2f};bst={r['bst']:.2f};"
                    f"midpoint={r['midpoint']:.2f};dpm2m={r['dpm2m']:.2f}"))


def _t2i(quick, csv):
    from benchmarks import t2i_proxy
    rows = t2i_proxy.run(train_steps=100 if quick else 250,
                         bns_iters=150 if quick else 400, log=log)
    for note in t2i_proxy.check_paper_claims(rows):
        log(note)
    for r in rows:
        csv.append((f"table2/w{r['w']}/nfe{r['nfe']}",
                    f"bns={r['bns']:.2f};init={r['initial_solver']:.2f};"
                    f"euler={r['euler']:.2f}"))


def _audio(quick, csv):
    from benchmarks import audio_proxy
    rows = audio_proxy.run(train_steps=80 if quick else 200,
                           bns_iters=120 if quick else 300, log=log)
    for note in audio_proxy.check_paper_claims(rows):
        log(note)
    for r in rows:
        csv.append((f"fig6/audio/nfe{r['nfe']}",
                    f"bns={r['bns']:.2f};midpoint={r['midpoint']:.2f}"))


def _anytime(quick, csv):
    from benchmarks import anytime_bench
    rows, nparams = anytime_bench.run(
        iterations=1500 if quick else 10_000,
        dedicated_iters=500 if quick else 3000, log=log)
    for note in anytime_bench.check_claims(rows):
        log(note)
    for r in rows:
        csv.append((f"anytime/nfe{r['nfe']}",
                    f"shared={r['anytime']:.2f};"
                    f"dedicated={r['dedicated']:.2f};params={nparams}"))
    for r in anytime_bench.serve_bench(iterations=200 if quick else 600,
                                       log=log):
        csv.append((f"anytime_serving/{r['name']}", r["derived"]))


SECTIONS = (_taxonomy, _table3, _fig4, _t2i, _audio, _anytime)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    csv: list[tuple[str, str]] = []
    for section in SECTIONS:
        section(args.quick, csv)
    print("name,derived")
    for name, derived in csv:
        print(f"{name},{derived}")


if __name__ == "__main__":
    main()
