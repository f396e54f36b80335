# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness driver — one module per paper table/figure:

  arithmetic_intensity  Fig. 4 + App. B.4  (analytic, exact on CPU)
  main_results          Tables 1-2         (toy-scale pipeline)
  step_truncation       Table 4
  conf_threshold        Table 7 / App. B.2
  block_size            Fig. 8 / App. B.3
  loss_weights          Table 3
  kernels               kernel-layer microbench
  serving               static vs continuous block-level batching
  trajectory            per-PR bench ratchet (append/gate/show)

Run everything:   PYTHONPATH=src python -m benchmarks.run
One benchmark:    PYTHONPATH=src python -m benchmarks.run kernels [args...]
                  (arguments after the name go to that benchmark's own
                  CLI, e.g. ``run.py serving --smoke --json out.json``)
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# subcommand -> module name under benchmarks/; every module exposes
# ``run(csv_rows=...)`` for the run-everything sweep and ``main(argv)``
# for its own CLI (trajectory has main() only — it is not a timed bench)
MODULES = {
    "arithmetic_intensity": "bench_arithmetic_intensity",
    "kernels": "bench_kernels",
    "main_results": "bench_main_results",
    "step_truncation": "bench_step_truncation",
    "conf_threshold": "bench_conf_threshold",
    "block_size": "bench_block_size",
    "loss_weights": "bench_loss_weights",
    "serving": "bench_serving",
    "trajectory": "trajectory",
}


def _import(name):
    import importlib
    return importlib.import_module(f"benchmarks.{MODULES[name]}")


def run_all() -> None:
    rows = []
    t0 = time.time()
    for name in ("arithmetic_intensity", "kernels", "main_results",
                 "step_truncation", "conf_threshold", "block_size",
                 "loss_weights", "serving"):
        mod = _import(name)
        print(f"\n##### {mod.__name__} ({time.time()-t0:.0f}s elapsed) #####")
        mod.run(csv_rows=rows)

    print("\n\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print(f"\ntotal wall time: {time.time()-t0:.0f}s")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if not argv or argv[0] in ("all",):
        run_all()
        return
    if argv[0] in ("-h", "--help"):
        print(__doc__)
        print("subcommands:", ", ".join(sorted(MODULES)), "| all")
        return
    name = argv[0]
    if name not in MODULES:
        raise SystemExit(
            f"unknown benchmark {name!r} — expected one of "
            f"{sorted(MODULES)} or 'all'")
    mod = _import(name)
    if hasattr(mod, "main"):
        ret = mod.main(argv[1:])
        if ret:
            raise SystemExit(ret)
    else:
        # table benches without their own CLI: plain run()
        if argv[1:]:
            raise SystemExit(f"benchmark {name!r} takes no arguments")
        mod.run()


if __name__ == "__main__":
    main()
