"""Fig. 8 analog: inference-time block-size sweep on a student trained with
a fixed block size — throughput rises with B; accuracy peaks at the
training block size (train-inference match)."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common
from repro.core.sampler import cdlm


def run(csv_rows=None):
    student = common.get_student()
    train_B = common.CDLM_CFG.block_size
    print(f"\n== Fig. 8 analog: inference block size (trained B={train_B}) ==")
    print(f"{'B':>4} {'TPS':>8} {'steps':>7} {'score':>6}")
    for B in (1, 2, 5, 10):
        if common.TASK.gen_len % B:
            continue
        r = common.eval_sampler(student, cdlm, block_size=B)
        mark = " <- train B" if B == train_B else ""
        print(f"{B:>4} {r['tps']:>8.0f} {r['steps']:>7.1f} "
              f"{r['score']:>6.2f}{mark}")
        if csv_rows is not None:
            csv_rows.append((f"block_size/B{B}", r["latency_s"] * 1e6,
                             f"score={r['score']:.2f};steps={r['steps']:.1f}"))
    return csv_rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
