"""Table 3 analog: loss-weight composition ablation.

Trains short CDLM students under different (w_distill, w_cons, w_dlm) and
reports score + refinement steps. The paper's headline findings checked
here: consistency-only collapses; distill+consistency beats distill-only on
steps at comparable quality."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common
from repro.core.sampler import cdlm

VARIANTS = [
    ("distill-only", (1.0, 0.0, 0.01)),
    ("consistency-only", (0.0, 1.0, 0.01)),
    ("distill+cons", (1.0, 0.5, 0.01)),
    ("no-dlm", (1.0, 0.5, 0.0)),
]


def run(csv_rows=None, steps=250):
    teacher = common.get_teacher()
    dataset = common.get_dataset(teacher)
    print("\n== Table 3 analog: loss-weight ablation ==")
    print(f"{'variant':18s} {'(wd,wc,wm)':>16} {'score':>6} {'steps':>7}")
    results = {}
    for name, w in VARIANTS:
        student = common.get_student(
            teacher, dataset, weights=w, steps=steps,
            cache_name=f"student_w{w[0]}_{w[1]}_{w[2]}.npz")
        r = common.eval_sampler(student, cdlm, conf_threshold=0.9)
        results[name] = r
        print(f"{name:18s} {str(w):>16} {r['score']:>6.2f} "
              f"{r['steps']:>7.1f}")
        if csv_rows is not None:
            csv_rows.append((f"loss_weights/{name}", r["latency_s"] * 1e6,
                             f"score={r['score']:.2f};steps={r['steps']:.1f}"))
    # paper row 2: consistency-only collapses
    assert results["consistency-only"]["score"] <= \
        results["distill+cons"]["score"], "consistency-only should not win"
    return csv_rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
