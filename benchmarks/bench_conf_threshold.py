"""Table 7 analog: token-confidence threshold sweep on the CDLM student —
speed must be monotone in tau; quality trades off at the aggressive end."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common
from repro.core.sampler import cdlm


def run(csv_rows=None):
    student = common.get_student()
    print("\n== Table 7 analog: tau_conf sweep (CDLM student) ==")
    print(f"{'tau':>6} {'TPS':>8} {'lat(ms)':>9} {'steps':>7} {'score':>6}")
    rows = []
    for tau in (0.95, 0.9, 0.85, 0.5):
        r = common.eval_sampler(student, cdlm, conf_threshold=tau)
        rows.append((tau, r))
        print(f"{tau:>6.2f} {r['tps']:>8.0f} {r['latency_s']*1e3:>9.2f} "
              f"{r['steps']:>7.1f} {r['score']:>6.2f}")
        if csv_rows is not None:
            csv_rows.append((f"conf_threshold/tau{tau}",
                             r["latency_s"] * 1e6,
                             f"score={r['score']:.2f};steps={r['steps']:.1f}"))
    steps = [r["steps"] for _, r in rows]
    assert steps == sorted(steps, reverse=True), \
        f"steps must decrease as tau drops: {steps}"
    return csv_rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
