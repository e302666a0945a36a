"""Protein-backbone coordinate denoising — the reference's flagship training
example (/root/reference/denoise.py), TPU-native.

Run:  python denoise.py [--steps N] [--nodes N] [--mesh]

Uses synthetic chain-structured data (sidechainnet is not available
offline; see se3_transformer_tpu/training/denoise.py for the swap-in
point). The model/optimization hyperparameters mirror the reference
(tokens=24, dim=8, depth=2, sparse-adjacency attention, adam 1e-4,
16-step gradient accumulation via the accumulating step builder).
"""
import argparse

from se3_transformer_tpu.utils.compilation_cache import enable_compilation_cache
enable_compilation_cache()

from se3_transformer_tpu.training import DenoiseConfig, DenoiseTrainer
from se3_transformer_tpu.training.checkpoint import CheckpointManager
from se3_transformer_tpu.observability import MetricLogger


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=20)
    ap.add_argument('--nodes', type=int, default=96)
    ap.add_argument('--batch', type=int, default=1)
    ap.add_argument('--degrees', type=int, default=2)
    ap.add_argument('--accum', type=int, default=16,
                    help='gradient-accumulation micro-steps (reference: 16)')
    ap.add_argument('--mesh', action='store_true',
                    help='shard over all visible devices')
    ap.add_argument('--ckpt-dir', type=str, default=None)
    ap.add_argument('--ckpt-every', type=int, default=0,
                    help='also checkpoint every N steps (0 = only at exit)')
    ap.add_argument('--metrics', type=str, default=None)
    ap.add_argument('--telemetry', action='store_true',
                    help='first-class telemetry: on-device metric '
                         'accumulation (no per-step host sync), host '
                         'phase p50/p95 timing, retrace watchdog, and '
                         'schema\'d flush/summary JSONL records (pair '
                         'with --metrics; render via scripts/obs_report)')
    ap.add_argument('--flush-every', type=int, default=5,
                    help='telemetry flush interval in optimizer steps '
                         '(one device-to-host sync per flush)')
    ap.add_argument('--pipelined', action='store_true',
                    help='overlapped data path (training.pipeline): '
                         'batches build on a background producer thread, '
                         'transfer to device --prefetch-depth steps '
                         'ahead, the per-step batch buffers are donated, '
                         'and checkpoints write asynchronously; with '
                         '--telemetry the stream grows host_wait/'
                         'prefetch phases and schema\'d pipeline records '
                         '(gate: make pipeline-smoke)')
    ap.add_argument('--prefetch-depth', type=int, default=2,
                    help='device-resident batches ahead of the step loop')
    ap.add_argument('--cost-record', action='store_true',
                    help='emit one schema\'d `cost` record for the '
                         'compiled train step after the first step '
                         '(observability.costs: flops, peak memory '
                         'split, collective bytes; pair with --metrics '
                         '— scripts/perf_gate.py budgets the stream)')
    ap.add_argument('--dataset', type=str, default=None,
                    help='train from a PointCloudDataset .npz (see '
                         'training.dataset); --nodes becomes the bucket size')
    ap.add_argument('--guarded', action='store_true',
                    help='self-healing elastic loop (training.guardian, '
                         'docs/ROBUSTNESS.md "Training fault domain"): '
                         'NaN/spike windows roll back to the newest '
                         'restorable checkpoint and replay '
                         'deterministically, SIGTERM/SIGINT triggers one '
                         'synchronous emergency save and a resumable '
                         'exit (rc 75), and a schema\'d guard record is '
                         'banked; requires --ckpt-dir, implies '
                         '--telemetry (gate: make train-chaos-smoke)')
    ap.add_argument('--restart-budget', type=int, default=3,
                    help='guarded: rollbacks allowed before failing '
                         'loud with a structured TrainingFailed')
    ap.add_argument('--spike-zscore', type=float, default=8.0,
                    help='guarded: EMA z-score above which a window\'s '
                         'loss mean counts as a spike')
    ap.add_argument('--cpu', action='store_true',
                    help='force the CPU backend (a chip belongs to one '
                         'process at a time: a second process that needs '
                         'it fails or hangs at init)')
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update('jax_platforms', 'cpu')

    if args.guarded:
        assert args.ckpt_dir, '--guarded needs --ckpt-dir (the rollback ' \
            'target and the preemption resume point live there)'
        assert not args.dataset, \
            '--guarded trains on per-step-index synthetic batches ' \
            '(deterministic replay is what makes rollback/resume ' \
            'bit-exact); a dataset-backed guarded loop needs a ' \
            'step-indexed batch source and is not wired yet'
        args.telemetry = True      # detection rides the accumulator
    cfg = DenoiseConfig(num_nodes=args.nodes, batch_size=args.batch,
                        num_degrees=args.degrees, use_mesh=args.mesh,
                        accum_steps=args.accum, telemetry=args.telemetry,
                        flush_every=args.flush_every,
                        pipeline=args.pipelined,
                        prefetch_depth=args.prefetch_depth,
                        cost_record=args.cost_record,
                        # every pipelined batch is freshly placed by
                        # device_prefetch, so donation is safe (see the
                        # audit in parallel.sharding)
                        donate_batch=args.pipelined)
    trainer = DenoiseTrainer(cfg)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and ckpt.latest_step() is not None \
            and not args.guarded:
        trainer.init()
        state = ckpt.restore(like=(trainer.params, trainer.opt_state,
                                   trainer.step_count))
        # re-places under the trainer's sharding config (fsdp/tp): a
        # resumed run's opt state lands back in its shards, not
        # replicated until the first step
        trainer.restore(state)
        print(f'resumed from step {trainer.step_count}')

    import dataclasses
    run_meta = dict(tool='denoise', config=dataclasses.asdict(cfg))
    # context-managed: the file handle closes on EVERY exit path (the old
    # happy-path-only close() leaked it on exceptions)
    with MetricLogger(args.metrics, run_meta=run_meta) as logger:
        if args.guarded:
            import sys

            from se3_transformer_tpu.training.guardian import (
                GuardConfig, StepGuard, resume_trainer,
            )
            # guarded resume uses the guardian's donation-safe restore
            # normalization (fresh uncommitted buffers — no post-warmup
            # recompile, no aliasing of the restored arrays)
            restart = ckpt.latest_step() is not None
            if restart:
                print(f'guarded resume from step '
                      f'{resume_trainer(trainer, ckpt)}')
            guard = StepGuard(GuardConfig(
                restart_budget=args.restart_budget,
                spike_zscore=args.spike_zscore))
            result = trainer.train_guarded(
                args.steps, ckpt, guard=guard, metric_logger=logger,
                restart=restart)
            if result.exit_code:
                # 75 = preempted-resumable (a supervisor restarts),
                # 1 = diverged (fail loud)
                sys.exit(result.exit_code)
            return result.history
        if args.pipelined:
            batch_source = None
            if args.dataset:
                from se3_transformer_tpu.training.dataset import (
                    PointCloudDataset,
                )
                from se3_transformer_tpu.training.pipeline import (
                    dataset_batch_source,
                )
                ds = PointCloudDataset.load(args.dataset)
                batch_source = dataset_batch_source(
                    ds, batch_size=cfg.batch_size, bucket=cfg.num_nodes,
                    accum_steps=cfg.accum_steps, num_steps=args.steps)
            history = trainer.train_pipelined(
                args.steps, batch_source=batch_source,
                # without --telemetry the per-step records still land in
                # --metrics (same shape as the synchronous path)
                log=lambda msg: logger.log(trainer.step_count, msg=msg),
                # cost_record also needs the stream (one cost record
                # after the first step), telemetry or not
                metric_logger=logger
                if (cfg.telemetry or cfg.cost_record) else None,
                checkpoint_manager=ckpt, checkpoint_every=args.ckpt_every)
        elif args.dataset:
            from se3_transformer_tpu.training.dataset import (
                PointCloudDataset,
            )
            from se3_transformer_tpu.training.pipeline import (
                dataset_batch_source,
            )

            ds = PointCloudDataset.load(args.dataset)
            # the SAME batch assembly the pipelined path uses: with
            # accum_steps > 1 each optimizer step accumulates that many
            # DISTINCT consecutive batches (the reference's 16 distinct
            # micro-batches, denoise.py:13,55 — the old inline builder
            # stacked one batch accum times, averaging identical
            # gradients at accum-times the compute)
            stream = dataset_batch_source(
                ds, batch_size=cfg.batch_size, bucket=cfg.num_nodes,
                accum_steps=cfg.accum_steps)

            history = []
            for i in range(args.steps):
                if cfg.telemetry:
                    with trainer.phase_timer.phase('data'):
                        batch = next(stream)
                else:
                    batch = next(stream)
                if i == 0:
                    # this branch drives train_step directly, so the
                    # trainer's own first-step ledger hook never runs
                    trainer._maybe_cost_record(batch, logger, history)
                loss = trainer.train_step(batch)
                if cfg.telemetry:
                    # no per-step float(): metrics accumulate on device
                    if (i + 1) % cfg.flush_every == 0:
                        history.append(trainer.telemetry_flush(logger))
                else:
                    history.append(logger.log(trainer.step_count,
                                              loss=float(loss)))
                if (ckpt is not None and args.ckpt_every > 0
                        and trainer.step_count % args.ckpt_every == 0):
                    import contextlib
                    with (trainer.phase_timer.phase('checkpoint')
                          if cfg.telemetry else contextlib.nullcontext()):
                        ckpt.save(trainer.step_count,
                                  (trainer.params, trainer.opt_state,
                                   trainer.step_count))
            if cfg.telemetry:
                history.append(trainer.telemetry_close(logger))
        else:
            history = trainer.train(args.steps,
                                    log=lambda msg: logger.log(
                                        trainer.step_count, msg=msg),
                                    checkpoint_manager=ckpt,
                                    checkpoint_every=args.ckpt_every,
                                    metric_logger=logger
                                    if (cfg.telemetry or cfg.cost_record)
                                    else None)
        if ckpt is not None:
            ckpt.save(trainer.step_count,
                      (trainer.params, trainer.opt_state,
                       trainer.step_count))
            print(f'checkpointed at step {trainer.step_count}')
    return history


if __name__ == '__main__':
    main()
