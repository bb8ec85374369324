"""Model zoo (reference analogs: PaddleNLP gpt/llama/bert configs used by
test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py and
paddle.vision.models; BASELINE.json workload configs).

Submodules import lazily — `from paddle_tpu.models import gpt` etc.
"""
import importlib

__all__ = ["gpt", "gpt_hybrid", "llama", "bert", "moe", "sdar_moe", "jamba",
           "lfm2_moe", "resnet"]


def __getattr__(name):
    if name == "resnet":
        return importlib.import_module("paddle_tpu.vision.models.resnet")
    if name in __all__:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(name)
