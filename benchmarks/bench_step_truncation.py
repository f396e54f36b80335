"""Table 4 analog: naively truncating the teacher's step budget (threshold-0
parallel finalization => ~1 step/block) vs CDLM at a comparable budget."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common
from repro.core.sampler import cdlm, fast_dllm_parallel, vanilla_blockwise


def run(csv_rows=None):
    teacher = common.get_teacher()
    student = common.get_student(teacher)

    full = common.eval_sampler(teacher, vanilla_blockwise)
    trunc = common.eval_sampler(teacher, fast_dllm_parallel,
                                conf_threshold=0.0)
    ours = common.eval_sampler(student, cdlm, conf_threshold=0.9)

    print("\n== Table 4 analog: step truncation ==")
    print(f"{'method':28s} {'steps':>7} {'lat(ms)':>9} {'score':>6}")
    for name, r in [("teacher full budget", full),
                    ("teacher truncated (naive)", trunc),
                    ("CDLM student", ours)]:
        print(f"{name:28s} {r['steps']:>7.1f} {r['latency_s']*1e3:>9.2f} "
              f"{r['score']:>6.2f}")
        if csv_rows is not None:
            csv_rows.append((f"step_truncation/{name.replace(' ', '_')}",
                             r["latency_s"] * 1e6,
                             f"score={r['score']:.2f};steps={r['steps']:.1f}"))
    assert trunc["score"] <= full["score"], "truncation should hurt"
    return csv_rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
