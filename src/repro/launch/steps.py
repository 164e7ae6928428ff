"""Jitted step functions the launchers and dry-runs lower."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import TrainConfig
from repro.optim import make_optimizer


def make_train_step(model, train_cfg: TrainConfig):
    opt = make_optimizer(train_cfg)
    model = model.for_training()

    def train_step(params, opt_state, batch
                   ) -> Tuple[Any, Any, Dict[str, jnp.ndarray]]:
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch,
                                      remat=train_cfg.remat)
        params, opt_state = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss)
        return params, opt_state, metrics

    return train_step, opt


def make_prefill_step(model, cache_len: int):
    def prefill_step(params, batch):
        return model.prefill(params, batch, cache_len=cache_len)
    return prefill_step


def make_decode_step(model):
    def decode_step(params, cache, batch):
        return model.decode_step(params, cache, batch["tokens"])
    return decode_step
