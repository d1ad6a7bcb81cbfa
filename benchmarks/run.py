"""Benchmark orchestrator: one section per paper figure/table plus the
kernel microbench and the roofline summary.  Prints ``section,key,value``
CSV rows; pass --full for the paper-scale settings (slow on CPU)."""

from __future__ import annotations

import argparse


def _emit(section: str, rows: list[dict]) -> None:
    for row in rows:
        key = ",".join(f"{k}={row[k]}" for k in list(row)[:4])
        rest = {k: v for k, v in row.items() if k not in list(row)[:4]}
        print(f"{section},{key},{rest}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="fig9|fig11|fig12|overload|batched|disorder|"
                         "shard_scale|bench_e2e|kernel|roofline")
    args = ap.parse_args()
    quick = not args.full
    from repro.kernels.platform import enable_compile_cache

    enable_compile_cache()

    sections = []
    if args.only in (None, "kernel"):
        from . import kernel_bench

        sections.append(("kernel_bench", kernel_bench.main(quick=quick)))
    if args.only in (None, "fig9"):
        from . import fig9_vs_sota

        sections.append(("fig9_vs_sota", fig9_vs_sota.main(quick=quick)))
    if args.only in (None, "fig11"):
        from . import fig11_scale

        sections.append(("fig11_hamlet_vs_greta",
                         fig11_scale.main(quick=quick)))
    if args.only in (None, "fig12"):
        from . import fig12_dynamic_vs_static

        sections.append(("fig12_dynamic_vs_static",
                         fig12_dynamic_vs_static.main(quick=quick)))
    if args.only in (None, "overload"):
        from . import fig_overload

        sections.append(("fig_overload", fig_overload.main(quick=quick)))
    if args.only in (None, "batched"):
        from . import fig_batched

        sections.append(("fig_batched", fig_batched.main(quick=quick)))
    if args.only in (None, "disorder"):
        from . import fig_disorder

        sections.append(("fig_disorder", fig_disorder.main(quick=quick)))
    if args.only in (None, "shard_scale"):
        from . import fig_shard_scale

        sections.append(("fig_shard_scale",
                         fig_shard_scale.main(quick=quick)))
    if args.only in (None, "bench_e2e"):
        from . import bench_e2e

        sections.append(("bench_e2e", bench_e2e.main(quick=quick)))
    if args.only in (None, "roofline"):
        from . import roofline

        sections.append(("roofline", roofline.main(quick=quick)))

    for name, rows in sections:
        print(f"\n# {name}")
        _emit(name, rows)


if __name__ == "__main__":
    main()
