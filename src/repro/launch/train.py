"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --stage teacher --steps 500 [--reduced]

Stages: ``teacher`` (Eq.-6 DLM SFT), ``ar`` (AR baseline / rwkv path),
``cdlm`` (the full teacher->trajectories->student pipeline). On this
CPU container only ``--reduced`` configs are trainable; on a real TPU mesh
the same code path shards via ``repro.parallel`` (see launch/dryrun.py for
the production-mesh proof of every arch × shape).
"""
import argparse
import dataclasses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--stage", default="cdlm",
                    choices=["teacher", "ar", "cdlm"])
    ap.add_argument("--task", default="sort", choices=["sort", "add"])
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--student-steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--block-size", type=int, default=5)
    ap.add_argument("--lora", action="store_true")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    from repro.checkpoint import save
    from repro.configs.base import CDLMConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.core import masks
    from repro.data import Corpus, TaskSpec
    from repro.launch.compile_cache import enable_compile_cache
    from repro.training import trainer

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(dtype="float32")
    task = TaskSpec(args.task, vocab_size=cfg.vocab_size, prompt_len=15,
                    gen_len=10, sort_k=8, sort_range=24, add_digits=4)
    corpus = Corpus(task, 768, seed=0)
    tcfg = TrainConfig(learning_rate=args.lr, steps=args.steps,
                       batch_size=args.batch_size, remat=False,
                       use_lora=args.lora)

    if args.stage == "ar" or cfg.family == "ssm":
        params = trainer.train_ar(cfg, corpus, tcfg)
    elif args.stage == "teacher":
        params = trainer.train_teacher(cfg, corpus, tcfg)
    else:
        cdlm_cfg = CDLMConfig(block_size=args.block_size, gen_length=10,
                              prompt_length=15, temperatures=(0.0,))
        mode = (masks.BLOCK_CAUSAL if cfg.family == "hybrid"
                else masks.BIDIRECTIONAL)
        teacher = trainer.train_teacher(cfg, corpus, tcfg, mode=mode,
                                        block_size=args.block_size)
        ds = trainer.collect_dataset(teacher, cfg, cdlm_cfg, corpus,
                                     n_examples=128, batch=args.batch_size)
        scfg = dataclasses.replace(tcfg, steps=args.student_steps,
                                   learning_rate=5e-4)
        params = trainer.train_student(teacher, ds, cfg, cdlm_cfg, scfg)

    if args.ckpt:
        save(params, args.ckpt)
        print(f"saved -> {args.ckpt}")


if __name__ == "__main__":
    main()
