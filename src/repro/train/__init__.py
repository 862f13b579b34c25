from .optimizer import AdamWConfig, AdamWState, adamw_init, adamw_update, lr_at
from .trainer import (
    FailureInjector,
    StragglerWatchdog,
    Trainer,
    TrainerConfig,
    cross_entropy,
    make_loss_fn,
    make_stitched_train_step,
    make_train_step,
)
