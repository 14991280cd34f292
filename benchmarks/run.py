"""Benchmark harness entry point.

One section per paper table/figure plus the framework benches.  Prints
``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,fig5,kernels,e2e,roofline,offload,gossip,hetero,shocks,fleet]
"""
from __future__ import annotations

import argparse
import sys
import time


SECTIONS = ("fig4", "fig5", "kernels", "e2e", "roofline", "offload",
            "gossip", "hetero", "shocks", "fleet", "exec", "policy")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: " + ",".join(SECTIONS))
    ap.add_argument("--fast", action="store_true",
                    help="tiny smoke grids (CI): fewer seeds/intervals, short jobs")
    args = ap.parse_args()
    only = None
    if args.only is not None:
        only = {key.strip() for key in args.only.split(",") if key.strip()}
        if not only:
            ap.error("--only: expected at least one section; "
                     f"valid choices: {', '.join(SECTIONS)}")
        unknown = sorted(only - set(SECTIONS))
        if unknown:
            ap.error(f"--only: unknown section(s) {', '.join(unknown)}; "
                     f"valid choices: {', '.join(SECTIONS)}")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    def want(name: str) -> bool:
        return only is None or name in only

    print("name,us_per_call,derived", flush=True)

    if want("fig4") or want("fig5"):
        from benchmarks import paper_figs
        sections = []
        if want("fig4"):
            sections += [paper_figs.fig4_left, paper_figs.fig4_right]
        if want("fig5"):
            sections += [paper_figs.fig5_left, paper_figs.fig5_right]
        for fn in sections:
            t = time.monotonic()
            for row in fn(fast=args.fast):
                fig, param, T, rel, ah, fh, gap = row.split(",")
                us = float(ah) * 3600 * 1e6  # adaptive wall in us
                print(f"{fig}_p{param}_T{T},{us:.0f},"
                      f"relative_runtime={rel}%;fixed_hours={fh};oracle_gap={gap}",
                      flush=True)
            sys.stderr.write(f"[bench] {fn.__name__} done in "
                             f"{time.monotonic() - t:.0f}s\n")

    if want("kernels"):
        from benchmarks import kernel_bench
        for row in kernel_bench.run_all()[1:]:
            print(row, flush=True)

    if want("e2e"):
        from benchmarks import e2e_adaptive
        for row in e2e_adaptive.run_all(fast=args.fast)[1:]:
            print(row, flush=True)

    if want("offload"):
        from benchmarks import server_offload
        t = time.monotonic()
        for row in server_offload.run_all(fast=args.fast)[1:]:
            print(row, flush=True)
        sys.stderr.write(f"[bench] server_offload done in "
                         f"{time.monotonic() - t:.0f}s\n")

    if want("gossip"):
        from benchmarks import gossip_fidelity
        t = time.monotonic()
        for row in gossip_fidelity.run_all(fast=args.fast)[1:]:
            print(row, flush=True)
        sys.stderr.write(f"[bench] gossip_fidelity done in "
                         f"{time.monotonic() - t:.0f}s\n")

    if want("hetero"):
        from benchmarks import heterogeneity
        t = time.monotonic()
        for row in heterogeneity.run_all(fast=args.fast)[1:]:
            print(row, flush=True)
        sys.stderr.write(f"[bench] heterogeneity done in "
                         f"{time.monotonic() - t:.0f}s\n")

    if want("shocks"):
        from benchmarks import correlated_churn
        t = time.monotonic()
        for row in correlated_churn.run_all(fast=args.fast)[1:]:
            print(row, flush=True)
        sys.stderr.write(f"[bench] correlated_churn done in "
                         f"{time.monotonic() - t:.0f}s\n")

    if want("fleet"):
        from benchmarks import fleet
        t = time.monotonic()
        for row in fleet.run_all(fast=args.fast)[1:]:
            print(row, flush=True)
        sys.stderr.write(f"[bench] fleet done in "
                         f"{time.monotonic() - t:.0f}s\n")

    if want("exec"):
        from benchmarks import executor_bench
        t = time.monotonic()
        for row in executor_bench.run_all(fast=args.fast)[1:]:
            print(row, flush=True)
        sys.stderr.write(f"[bench] executor_bench done in "
                         f"{time.monotonic() - t:.0f}s\n")

    if want("policy"):
        from benchmarks import policy_service_bench
        t = time.monotonic()
        for row in policy_service_bench.run_all(fast=args.fast)[1:]:
            print(row, flush=True)
        sys.stderr.write(f"[bench] policy_service_bench done in "
                         f"{time.monotonic() - t:.0f}s\n")

    if want("roofline"):
        from benchmarks import roofline
        for row in roofline.run_all()[1:]:
            print(row, flush=True)


if __name__ == "__main__":
    main()
