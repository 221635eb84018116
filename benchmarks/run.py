"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Set REPRO_BENCH_QUICK=1 for
the fast path (used in CI-style runs).

    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run table2 fig9  # subset
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from . import (
        fig6_decode_fanout,
        fig6_e2e_mcts,
        fig7_rl_fanout,
        fig8_async_warm,
        fig9_write_amp,
        fig10_gc_lw,
        fig11_dump_pipeline,
        fig12_stream_overlap,
        fig13_persist_recover,
        fig13b_incremental_persist,
        fig14_sharded_dump,
        roofline,
        table2_cr_latency,
        table3_fork_fanout,
        table4_breakdown,
    )

    benches = {
        "table2": table2_cr_latency.run,
        "table3": table3_fork_fanout.run,
        "table4": table4_breakdown.run,
        "fig6": fig6_e2e_mcts.run,
        "fig6_decode": fig6_decode_fanout.run,
        "fig7": fig7_rl_fanout.run,
        "fig8": fig8_async_warm.run,
        "fig9": fig9_write_amp.run,
        "fig10": fig10_gc_lw.run,
        "fig11": fig11_dump_pipeline.run,
        "fig12": fig12_stream_overlap.run,
        "fig13": fig13_persist_recover.run,
        "fig13b": fig13b_incremental_persist.run,
        "fig14": fig14_sharded_dump.run,
        "roofline": roofline.run,
    }
    selected = sys.argv[1:] or list(benches)
    print("name,us_per_call,derived")
    failed = []
    for name in selected:
        t0 = time.time()
        try:
            rows = benches[name]()
        except Exception as exc:  # keep the harness going; record the failure
            print(f"{name}/ERROR,0.0,{type(exc).__name__}: {str(exc)[:160]}")
            failed.append(name)
            continue
        for row in rows:
            print(row.csv())
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
