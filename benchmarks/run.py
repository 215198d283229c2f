# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (harness contract) and writes the underlying data to
# results/figures/*.csv.
#
#   fig2_3 — Markov-approximation validation (paper Fig. 2 & 3)
#   fig4   — algorithm-vs-benchmark delays (paper Fig. 4)
#   fig5   — completion-delay CDF / rho_s tail (paper Fig. 5)
#   fig6   — communication-rate sweep (paper Fig. 6)
#   fig7_8 — EC2 fits + evaluation (paper Fig. 7 & 8)
#   extras — coded executor / kernels / coded-grads (beyond paper)
#   backend — numpy/jax/pallas throughput record (BENCH_backend.json)
#
# Env knobs: REPRO_TRIALS (Monte-Carlo trials, default 60000; the paper used
# 1e6 — same seeds, just more samples), REPRO_RESULTS (output dir).
# The fig scripts also run standalone with --backend/--trials flags
# (`python -m benchmarks.fig4_delay --backend jax --trials 1000000`).
from __future__ import annotations


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    from . import (ablation_redundancy, backend_bench, coded_exec_bench,
                   fig2_3_markov, fig4_delay, fig5_cdf, fig6_commrate,
                   fig7_8_ec2)
    fig2_3_markov.main([])
    fig4_delay.main([])
    fig5_cdf.main([])
    fig6_commrate.main([])
    fig7_8_ec2.main()
    coded_exec_bench.main([])
    ablation_redundancy.main()
    backend_bench.main([])


if __name__ == "__main__":
    main()
