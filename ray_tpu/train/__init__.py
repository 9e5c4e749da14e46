"""Public names of `ray_tpu.train`, bound on first use (PEP 562): a driver
that only launches a `JaxTrainer` must not import jax — the chip belongs to
the worker, and a parent that has initialised a backend holds it."""

import importlib

_EXPORTS = {
    "ray_tpu.train.step": ("TrainState", "make_train_step", "make_init_fn",
                           "batch_sharding"),
    "ray_tpu.train.predictor": ("BatchPredictor", "JaxPredictor", "Predictor"),
    "ray_tpu.train.trainer": ("DataParallelTrainer", "JaxTrainer"),
    "ray_tpu.train.checkpointing": (
        "abstract_like", "gc_checkpoints", "latest_checkpoint",
        "load_checkpoint", "restore_sharded", "save_checkpoint",
        "save_sharded"),
    "ray_tpu.train.sklearn": ("SklearnPredictor", "SklearnTrainer"),
    "ray_tpu.train.huggingface": ("TransformersTrainer",),
    "ray_tpu.train.gbdt": ("GBDTPredictor", "GBDTTrainer", "LightGBMTrainer",
                           "LightGBMPredictor", "XGBoostPredictor",
                           "XGBoostTrainer"),
}
_MODULE_OF = {name: mod for mod, names in _EXPORTS.items() for name in names}
__all__ = sorted(_MODULE_OF)


def __getattr__(name):
    mod = _MODULE_OF.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value
    return value
