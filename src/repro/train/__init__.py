from .losses import cross_entropy
from .train_step import (TrainConfig, init_train_state, make_grads_fn,
                         make_loss_fn, make_train_step)
from .trainer import Trainer, TrainerConfig

__all__ = ["TrainConfig", "Trainer", "TrainerConfig", "cross_entropy",
           "init_train_state", "make_grads_fn", "make_loss_fn",
           "make_train_step"]
