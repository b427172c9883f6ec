"""Shared trained-model fixture for the resilience benchmarks: trains
ResNet-8 on synthetic CIFAR once and caches the checkpoint.

``make_eval_fn`` returns the shipped ``classification`` Workload
(DESIGN.md §2.7) — callable like the historical scalar eval, with the
traceable core the batched (``batch=True``) resilience engines need."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.approx.workload import Workload, classification
from repro.data.synthetic import CifarBatches
from repro.models import resnet
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import Trainer, TrainLoopConfig
from repro.train.optimizer import OptimizerConfig

from repro.data.synthetic import DATA_VERSION

CKPT_DIR = os.path.join(os.path.dirname(__file__), "results",
                        f"resnet8_ckpt_v{DATA_VERSION}")
TRAIN_STEPS = 320


def case_study_names(lib, n_mult: int) -> list[str]:
    """The paper's candidate set: Pareto selection capped at ``n_mult``,
    plus the truncation/BAM baselines Table II always reports."""
    sel = lib.case_study_selection(per_metric=10)
    names = [e.name for e in sel][:n_mult]
    for extra in ("mul8u_trunc7", "mul8u_trunc6", "mul8u_bam_h0_v4"):
        if extra in lib.entries and extra not in names:
            names.append(extra)
    return names


def restore_resnet8():
    """The committed trained ResNet-8 (``CKPT_DIR``); raises
    ``FileNotFoundError`` when the checkpoint is absent — never trains."""
    cfg = resnet.resnet_config(8)
    params = resnet.init_params(jax.random.PRNGKey(0), cfg)
    (params, _), _ = CheckpointManager(CKPT_DIR, keep=1).restore(
        (params, params))
    return cfg, params


def trained_resnet(depth: int = 8):
    mgr = CheckpointManager(CKPT_DIR, keep=1)
    if depth == 8 and mgr.latest_step() is not None:
        return restore_resnet8()
    cfg = resnet.resnet_config(depth)
    params = resnet.init_params(jax.random.PRNGKey(0), cfg)
    train_data = CifarBatches("train", 4096, 64)

    def batches():
        while True:
            for b in train_data.epoch():
                yield {"images": jnp.asarray(b["images"]),
                       "labels": jnp.asarray(b["labels"])}

    trainer = Trainer(lambda p, b: resnet.loss_fn(p, b, cfg), params,
                      OptimizerConfig(lr=3e-3, warmup_steps=20,
                                      total_steps=TRAIN_STEPS,
                                      weight_decay=1e-4),
                      TrainLoopConfig(total_steps=TRAIN_STEPS,
                                      ckpt_every=10 ** 9,
                                      ckpt_dir="/tmp/repro_bench_tmp",
                                      log_every=10 ** 9))
    trainer.run(batches(), log=lambda s: None)
    params = trainer.params
    if depth == 8:
        mgr.save(TRAIN_STEPS, (params, params))
    return cfg, params


def make_eval_fn(cfg, params, eval_n: int = 256, batch: int = 64
                 ) -> Workload:
    """Accuracy evaluator over the synthetic test set — the shipped
    ``classification`` workload: call it like a function for the
    sequential path, or hand it to ``batch=True`` sweeps to evaluate a
    whole multiplier bank in one compiled program."""
    return classification(cfg, params, eval_n=eval_n, batch=batch)
