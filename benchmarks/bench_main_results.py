"""Tables 1–2 analog: TPS / Latency / Total Steps / Gen Length / Score for
the naive DLM, every acceleration baseline, CDLM, and the AR reference —
on the synthetic sort task at toy scale."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common
from repro.configs.base import TrainConfig
from repro.core.sampler import SAMPLERS
from repro.training import trainer


def run(csv_rows=None):
    teacher = common.get_teacher()
    student = common.get_student(teacher)

    methods = [
        ("vanilla-DLM (teacher)", "vanilla", teacher, {}),
        ("dLLM-Cache (interval)", "interval_cache", teacher, {}),
        ("Fast-dLLM (Par.)", "fast_dllm", teacher, {}),
        ("Fast-dLLM (Par.+D.C.)", "dual_cache", teacher, {}),
        ("CDLM (ours)", "cdlm", student, {"early_stop": True}),
    ]
    # AR reference (Fig. 3): same-size model trained autoregressively
    ar_path = common._path("ar_baseline.npz")
    import jax
    from repro.checkpoint import restore, save
    from repro.models import init_model
    template = init_model(jax.random.PRNGKey(0), common.CFG)
    if os.path.exists(ar_path):
        ar_params = restore(template, ar_path)
    else:
        tcfg = TrainConfig(learning_rate=2e-3, steps=common.TEACHER_STEPS,
                           batch_size=64, remat=False)
        ar_params = trainer.train_ar(common.CFG, common.corpus(), tcfg,
                                     verbose=False)
        save(ar_params, ar_path)
    methods.append(("AR baseline", "ar", ar_params, {"early_stop": True}))

    base = None
    print(f"\n== Tables 1-2 analog (sort task, {common.CFG.n_layers}L "
          f"d{common.CFG.d_model}) ==")
    print(f"{'method':24s} {'TPS':>8} {'lat(ms)':>9} {'steps':>7} "
          f"{'genlen':>7} {'score':>6}")
    for name, key, params, kw in methods:
        r = common.eval_sampler(params, SAMPLERS[key], **kw)
        if base is None:
            base = r
        sp_t = r["tps"] / base["tps"] if base["tps"] else 0
        sp_l = base["latency_s"] / r["latency_s"] if r["latency_s"] else 0
        print(f"{name:24s} {r['tps']:>8.0f} {r['latency_s']*1e3:>9.2f} "
              f"{r['steps']:>7.1f} {r['gen_len']:>7.1f} {r['score']:>6.2f}"
              f"   (x{sp_t:.1f} TPS, x{sp_l:.1f} lat)")
        if csv_rows is not None:
            csv_rows.append((f"main_results/{key}",
                             r["latency_s"] * 1e6,
                             f"score={r['score']:.2f};steps={r['steps']:.1f};"
                             f"tps={r['tps']:.0f}"))
    return csv_rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
